"""The OLMoE family (``model_type: olmoe``), as ``dlrover_tpu.models.moe``
computes it and as this file's plain reference computes it again.

Layer equations, from the Hugging Face ``olmoe`` implementation and
arXiv:2409.02060 (OLMoE: Open Mixture-of-Experts Language Models), for
hidden states ``x`` of width ``d``:

- attention (pre-norm residual, as Llama)::

      y = RMSNorm(x; attn_norm)
      q = RMSNorm(y Wq; q_norm)     # over the WHOLE h*hd-wide projection,
      k = RMSNorm(y Wk; k_norm)     # before the split into heads and rotary
      v = y Wv
      q, k -> heads of hd; rotary (theta) on q and k, first half of a
              head against its second half
      x = x + CausalSoftmaxAttention(q, k, v) Wo

- expert layer (pre-norm residual), ``E`` experts, ``k`` a token::

      y = RMSNorm(x; mlp_norm)
      p = softmax(y Wr)             # float32, over all E experts
      (p_1..p_k, e_1..e_k) = the k largest p and their experts
      # norm_topk_prob false: the weights are those p as they are
      x = x + sum_j p_j * Wdown_{e_j} (silu(Wgate_{e_j} y) * (Wup_{e_j} y))

  Dropless: every (token, choice) pair is computed whatever the load.

- loss: mean next-token cross-entropy + ``router_aux_loss_coef`` x the
  load-balancing loss ``E * sum_i f_i P_i`` (``f_i``: share of the
  (token, choice) pairs that went to expert ``i``; ``P_i``: mean router
  probability of expert ``i``), taken per layer and averaged over the
  layers. Departures, listed under ``assumed`` in the configuration: the
  coefficient 0.01 is the HF default (the catalog's copy of config.json
  leaves the key out); HF pools the layers' router outputs before the
  product where the program, and so this reference, averages per-layer
  products; the paper's router z-loss is left out, as HF leaves it out.

``reference_loss`` is float32 at matmul precision "highest", with no
kernel and no sort: a Python loop over the experts, each applied to all
tokens and weighted by that token's ``p`` for it or 0. It imports nothing
of ``dlrover_tpu``.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from benchmarks.harness import moe_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sizes(config: dict) -> dict:
    dim = config["hidden_size"]
    n_heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or dim // n_heads
    if head_dim * n_heads != dim:
        raise ValueError(
            "the program's MoeConfig derives head_dim = dim / n_heads; "
            f"{config['name']} has head_dim {head_dim} x {n_heads} != {dim}"
        )
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("attention_bias", False), ("clip_qkv", None),
                      ("rope_scaling", None), ("model_type", "olmoe")):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/moe.py computes ({want!r})"
            )
    return dict(
        n_layers=config["num_hidden_layers"], dim=dim, n_heads=n_heads,
        n_kv_heads=config["num_key_value_heads"], head_dim=head_dim,
        ffn_dim=config["intermediate_size"],
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
    )


def _aux_coef(config: dict) -> float:
    return float(config["assumed"]["router_aux_loss_coef"])


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import moe
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed = config["assumed"]
    cfg = moe.MoeConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["dim"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        n_kv_heads=sizes["n_kv_heads"], ffn_dim=sizes["ffn_dim"],
        n_experts=sizes["n_experts"],
        experts_per_token=sizes["experts_per_token"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        qk_norm=True,    # model_type olmoe; config.json has no key for it
        router_aux_coef=_aux_coef(config),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/moe.py remats a whole layer or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/moe.py initialises with sigma 0.02 only")
    specs = moe.param_specs(cfg)
    init = jax.jit(
        lambda key: moe.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs),
    )
    loss_fn = lambda p, t: moe.loss_fn(p, t, cfg, mesh)

    def reference(params, tokens):
        loss = reference_loss(params, tokens, config)
        _report_routing(moe, cfg, params, tokens, config)
        return loss

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        loss_fn=loss_fn,
        param_count=moe.param_count(cfg),
        flops_per_token=lambda seq: moe_flops.moe_decoder_flops_per_token(
            seq=seq, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2,
        # so the CE is ln V + dim x sigma^2 / 2; router logits of the
        # same variance route almost uniformly, where the aux loss is 1
        expected_first_loss=math.log(sizes["vocab_size"])
        + sizes["dim"] * std * std / 2.0 + _aux_coef(config) * 1.0,
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rotary(x, theta):
    # x: (b, s, heads, head_dim)
    s, hd = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_attention(x, lp, sizes, theta, eps, qk_norm=True):
    b, s, _ = x.shape
    h, kvh, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    y = _rms_norm(x, lp["attn_norm"], eps)
    q, k, v = y @ lp["wq"], y @ lp["wk"], y @ lp["wv"]
    if qk_norm:
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    q = _rotary(q.reshape(b, s, h, hd), theta)
    k = _rotary(k.reshape(b, s, kvh, hd), theta)
    v = v.reshape(b, s, kvh, hd)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return x + attn.reshape(b, s, h * hd) @ lp["wo"]


def _ref_router(y, router, k, norm_topk_prob):
    """(t, d) -> probs (t, E), per-expert weight (t, E): a token's p for
    the experts it chose, 0 for the others."""
    probs = jax.nn.softmax(y @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chose = top_e[:, :, None] == jnp.arange(probs.shape[1])[None, None, :]
    return probs, jnp.sum(jnp.where(chose, top_p[:, :, None], 0.0), axis=1), \
        top_e


def _ref_experts(y, weight, lp, n_experts):
    """Every expert on every token, weighted; no sort, no gather."""
    def one(i, out):
        hidden = jax.nn.silu(y @ lp["w_gate"][i]) * (y @ lp["w_up"][i])
        return out + weight[:, i, None] * (hidden @ lp["w_down"][i])

    # a loop over the experts (rolled, so that 64 experts trace once)
    return jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(y))


def _ref_expert_layer(x, lp, sizes, eps, norm_topk_prob):
    b, s, d = x.shape
    e, k = sizes["n_experts"], sizes["experts_per_token"]
    y = _rms_norm(x, lp["mlp_norm"], eps).reshape(b * s, d)
    probs, weight, _ = _ref_router(y, lp["router"], k, norm_topk_prob)
    out = _ref_experts(y, weight, lp, e)
    fraction = jnp.sum(weight > 0, axis=0) / (b * s * k)
    aux = e * jnp.sum(fraction * jnp.mean(probs, axis=0))
    return x + out.reshape(b, s, d), aux


def _ref_head_loss(x, final_norm, lm_head, tokens, eps):
    logits = _rms_norm(x, final_norm, eps) @ lm_head
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def reference_loss(params, tokens, config: dict, qk_norm: bool = True) -> float:
    """Cross-entropy + aux loss of ``tokens`` (b, s) under ``params``
    (the program's parameter tree, any dtype), one layer cast to float32
    at a time so that it fits beside a full device. ``qk_norm`` is the
    family's own (true); the benchmark's tests switch it off to show
    that the comparison sees the term."""
    sizes = _sizes(config)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    norm_topk_prob = bool(config["norm_topk_prob"])
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda table, t: _f32(table)[t])
        attention = jax.jit(
            lambda x, lp: _ref_attention(x, _f32(lp), sizes, theta, eps,
                                         qk_norm))
        experts = jax.jit(
            lambda x, lp: _ref_expert_layer(x, _f32(lp), sizes, eps,
                                            norm_topk_prob))
        head = jax.jit(
            lambda x, fn, w, t: _ref_head_loss(x, _f32(fn), _f32(w), t, eps))
        x = embed(params["embed"], tokens)
        aux_sum = 0.0
        for i in range(sizes["n_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = attention(x, lp)
            x, aux = experts(x, lp)
            aux_sum += float(aux)
        ce = float(head(x, params["final_norm"], params["lm_head"], tokens))
    return ce + _aux_coef(config) * aux_sum / sizes["n_layers"]


# ---------------------------------------------------------------------------
# What a loss cannot show: the expert layer alone, program against
# reference on the same input (logged outside the timed window)
# ---------------------------------------------------------------------------

def _report_routing(moe, cfg, params, tokens, config: dict) -> None:
    """Log, for the first layer's expert block on the seeded batch: the
    share of (token, choice) pairs on which the program's router and the
    reference's agree, the largest error of the block's output, and how
    uneven the load is (the operator's gauge ``moe.load_max_over_mean``).
    With bf16 activations some 8th-against-9th choices flip; the loss
    barely moves with them, which is why the share is reported."""
    from dlrover_tpu.observability import trace

    sizes = _sizes(config)
    eps = float(config["rms_norm_eps"])
    k, e = sizes["experts_per_token"], sizes["n_experts"]
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = params["embed"][tokens].astype(cfg.dtype)      # (b, s, d)
    b, s, d = x.shape

    @jax.jit
    def program(lp, x):
        from dlrover_tpu.ops import rms_norm

        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(cfg, lp["router"], y.reshape(b * s, d))
        out, _ = moe.moe_mlp(cfg, lp, y)
        return out, top_e

    @jax.jit
    def reference(lp, x):
        lp = _f32(lp)
        y = _rms_norm(x.astype(jnp.float32), lp["mlp_norm"], eps)
        y = y.reshape(b * s, d)
        _, weight, top_e = _ref_router(
            y, lp["router"], k, bool(config["norm_topk_prob"]))
        return _ref_experts(y, weight, lp, e).reshape(b, s, d), top_e

    out, top_e = program(lp, x)
    with jax.default_matmul_precision("highest"):
        want, want_e = reference(lp, x)
    chosen = jax.nn.one_hot(top_e, e, dtype=jnp.int32).sum(1)       # (t, E)
    want_chosen = jax.nn.one_hot(want_e, e, dtype=jnp.int32).sum(1)
    agree = float(jnp.sum(chosen * want_chosen)) / (b * s * k)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    scale = float(jnp.max(jnp.abs(want)))
    load = chosen.sum(0)
    max_over_mean = float(jnp.max(load)) * e / (b * s * k)
    trace.gauge("moe.load_max_over_mean", max_over_mean)
    print(f"[olmoe] expert layer 0 on the seeded batch: choices agree on "
          f"{100.0 * agree:.3f} % of {b * s * k} (token, choice) pairs; "
          f"largest output error {err:.3e} of {scale:.3e}; load max/mean "
          f"{max_over_mean:.3f}", flush=True)
