"""The kimi_linear family (``model_type: kimi_linear``,
Kimi-Linear-48B-A3B), as ``dlrover_tpu.models.kimi_linear`` computes it
and as this file's plain reference computes it again.

Layer equations, from the model's config.json and arXiv 2510.26692,
hidden width ``d``; every block is ``h += Attn(RMSNorm(h)); h +=
FFN(RMSNorm(h))``, ``x`` the normed input:

- which attention a layer has is ``linear_attn_config``'s two lists
  (numbered from 1): KDA in ``kda_layers``, latent attention in
  ``full_attn_layers``; the first ``first_k_dense_replace`` layers have a
  dense SwiGLU, the others the expert layer.
- KDA layer, head ``h`` of ``num_heads``, ``dk = dv = head_dim``::

      q~, k~, v~ = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
      Conv: depthwise over time, causal, short_conv_kernel_size taps
      q_t = L2norm(q~_t^h) dk^-1/2;  k_t = L2norm(k~_t^h);  v_t = v~_t^h
      g_t = -exp(A_log^h) softplus((x W_f1 W_f2)_t^h + dt_bias^h)  in R^dk
      b_t = sigmoid(x w_b^h)
      S' = Diag(exp(g_t)) S_(t-1);  S_t = S' + b_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t                            S in R^(dk x dv), S_0 = 0
      y = W_o concat_h[RMSNorm_dv(o_t^h) * sigmoid((x W_g1 W_g2 + b_g2)_t^h)]

- latent layer: ``q = x W_q`` -> heads of [128 | 64]; ``[c | k_r] = x
  W_kva``; ``[k_n | v]`` a head ``= RMSNorm(c) W_kvb``; ``k = [k_n |
  k_r]``, ``k_r`` one vector for all heads; no rotary (``mla_use_nope``);
  causal softmax at scale 192^-1/2; ``W_o``.
- expert layer: ``sc = sigmoid(x W_r)`` in float32 over ``num_experts``
  (published: 256); the ``k`` experts with the largest ``sc + b_corr``;
  ``w = sc[chosen] / sum(sc[chosen]) x routed_scaling_factor``; ``sum_j
  w_j Expert_j(x) + Shared(x)``, all SwiGLU. This chip holds experts
  ``first_expert .. + num_experts - 1`` of ``published_num_experts``: a
  pair that chose another adds nothing.
- loss: mean next-token cross-entropy over ``RMSNorm(h) W_head``.

What config.json does not say is under ``assumed`` in the configuration.

The reference is float32 at matmul precision "highest": the delta rule
**token by token** (a ``lax.scan`` over time, no chunks), attention by
explicit scores and mask, the expert layer a loop over the held experts,
each on all tokens. It imports nothing of ``dlrover_tpu``; what every
reference shares (norm, SwiGLU, CE, casts, the plain expert layer) is
``families/xing4.py``'s.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families import xing4
from benchmarks.families.xing4 import (
    _f32, _ref_ce, _rms_norm, _row_rel, _shifted, _swiglu)
from benchmarks.harness import kimi_linear_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("model_type", "kimi_linear"), ("mla_use_nope", True),
                      ("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("q_lora_rank", None),
                      ("rope_scaling", None),
                      ("num_nextn_predict_layers", 0)):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/kimi_linear.py computes ({want!r})"
            )
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one k/v head a query head")
    linear = config["linear_attn_config"]
    return dict(
        n_layers=config["num_hidden_layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        n_dense_layers=config["first_k_dense_replace"],
        dim=config["hidden_size"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        conv_size=linear["short_conv_kernel_size"],
        n_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_ffn_dim=config["intermediate_size"],
        expert_ffn_dim=config["moe_intermediate_size"],
        n_experts=config.get("published_num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_token"],
        n_shared_experts=config["num_shared_experts"],
        vocab_size=config["vocab_size"],
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import kimi_linear
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed = config["assumed"]
    cfg = kimi_linear.KimiLinearConfig(
        **sizes,
        kda_chunk=int(assumed["kda_chunk"]),
        first_expert=int(config.get("first_expert", 0)),
        norm_topk_prob=bool(config["moe_renormalize"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        scoring=config["moe_router_activation_func"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/kimi_linear.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/kimi_linear.py initialises with sigma 0.02")
    specs = kimi_linear.param_specs(cfg)
    init = jax.jit(
        lambda key: kimi_linear.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs),
    )

    def reference(params, tokens):
        want = reference_pieces(params, tokens, config)
        ok = _compare(cfg, mesh, params, tokens, want)
        return want["ce"] if ok else float("nan")

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        loss_fn=lambda p, t: kimi_linear.loss_fn(p, t, cfg, mesh),
        param_count=kimi_linear.param_count(cfg),
        flops_per_token=lambda seq: kimi_linear_flops.flops_per_token(
            seq=seq, chunk=cfg.kda_chunk, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2
        expected_first_loss=(
            math.log(sizes["vocab_size"]) + sizes["dim"] * std * std / 2),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _ref_conv(x, weight):
    """``x (b, s, c)``, ``weight (c, w)``: tap ``w - 1`` is the token's
    own, tap ``w - 1 - i`` the token ``i`` steps before it."""
    w = weight.shape[1]
    out = jnp.zeros_like(x)
    for back in range(w):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]
        out = out + shifted * weight[:, w - 1 - back]
    return out


def ref_delta_rule(q, k, v, g, beta):
    """The recurrence as written, a token a step: ``q, k, g (b, s, h,
    dk)``, ``v (b, s, h, dv)``, ``beta (b, s, h)`` -> ``o (b, s, h,
    dv)``."""
    b, s, h, dk = q.shape

    def step(S, xs):
        q, k, v, g, beta = xs                          # (b, h, d), (b, h)
        S = jnp.exp(g)[..., None] * S
        err = v - jnp.einsum("bhde,bhd->bhe", S, k)
        S = S + (beta[..., None] * k)[..., None] * err[..., None, :]
        return S, jnp.einsum("bhde,bhd->bhe", S, q)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def _ref_kda(y, lp, config):
    """``y (b, s, d)``, already pre-normed -> the KDA layer's output."""
    b, s, _ = y.shape
    linear = config["linear_attn_config"]
    h, d = linear["num_heads"], linear["head_dim"]

    def conv(name):
        return jax.nn.silu(_ref_conv(y @ lp[f"w_{name}"], lp[f"conv_{name}"])
                           ).reshape(b, s, h, d)

    q = _l2_norm(conv("q")) * d ** -0.5
    k = _l2_norm(conv("k"))
    v = conv("v")
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        (y @ lp["w_f1"]) @ lp["w_f2"] + lp["dt_bias"]).reshape(b, s, h, d)
    beta = jax.nn.sigmoid(y @ lp["w_b"])
    o = ref_delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid(
        (y @ lp["w_g1"]) @ lp["w_g2"] + lp["b_g2"]).reshape(b, s, h, d)
    o = _rms_norm(o, lp["o_norm"], float(config["rms_norm_eps"])) * gate
    return o.reshape(b, s, h * d) @ lp["w_o"]


def _ref_latent(y, lp, config):
    """``y (b, s, d)``, already pre-normed -> latent attention's output:
    explicit scores, no rotary."""
    b, s, _ = y.shape
    h = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rkv = config["v_head_dim"], config["kv_lora_rank"]
    q = (y @ lp["w_q"]).reshape(b, s, h, dn + dr)
    kva = y @ lp["w_kva"]
    kv = (_rms_norm(kva[..., :rkv], lp["kv_a_norm"],
                    float(config["rms_norm_eps"])) @ lp["w_kvb"]
          ).reshape(b, s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(kva[:, :, None, rkv:], (b, s, h, dr))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     kv[..., dn:])
    return out.reshape(b, s, h * dv) @ lp["w_o"]


def _ref_expert_layer(y, lp, config):
    """``y (b, s, d)``, pre-normed -> (held experts' part + shared expert,
    chosen experts (t, k)): ``families/xing4.py``'s plain expert layer
    (sigmoid scores, the k largest of score + bias, renormalised and
    scaled, a loop over the held experts, the shared expert), which reads
    the same quantities under ``deepseek_v3``'s key names."""
    return xing4._ref_expert_layer(y, lp, {
        "num_experts_per_tok": config["num_experts_per_token"],
        "norm_topk_prob": config["moe_renormalize"],
        "routed_scaling_factor": config["routed_scaling_factor"],
        "n_shared_experts": config["num_shared_experts"],
        "first_expert": config.get("first_expert", 0),
    })


def _ref_block(x, lp, config, cast=lambda a: a):
    """One block; which attention and which feed-forward it has is read
    off the leaves it was given. ``cast`` rounds the weights and each
    sublayer's input and output (``second_reading``)."""
    eps = float(config["rms_norm_eps"])
    lp = jax.tree.map(cast, lp)
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    x = x + cast((_ref_kda if "a_log" in lp else _ref_latent)(y, lp, config))
    y = cast(_rms_norm(x, lp["mlp_norm"], eps))
    if "router" in lp:
        return x + cast(_ref_expert_layer(y, lp, config)[0])
    return x + cast(_swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]))


def layers_of(params):
    """The layers' parameter trees, first to last: run by run (the runs'
    keys sort in layer order), layer by layer inside a run."""
    for name in sorted(params["runs"]):
        slab = params["runs"][name]
        for i in range(jax.tree.leaves(slab)[0].shape[0]):
            yield jax.tree.map(lambda a: a[i], slab)


def plain_loss(params, tokens, config: dict):
    """The CE of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as it
    stands."""
    x = params["embed"][tokens]
    for lp in layers_of(params):
        x = _ref_block(x, lp, config)
    return _ref_ce(x, params["final_norm"], params["lm_head"],
                   _shifted(tokens, 1), float(config["rms_norm_eps"]))


def _round_trip(dtype):
    return lambda a: a.astype(dtype).astype(jnp.float32)


def reference_pieces(params, tokens, config: dict, cast=None) -> dict:
    """What the comparisons read, from the reference: ``ce``, the
    residual after the last block ``hidden`` and, on the embedded batch
    as input, the first expert-layer KDA block's attention output
    ``kda``, the first latent block's ``latent``, the first block's
    expert-layer output ``expert`` and its router's choices ``top_e``.
    ``params`` is the program's tree in any dtype; one block is cast to
    float32 at a time so that it fits beside a full device. ``cast``
    (``second_reading``) rounds weights and sublayer inputs and outputs."""
    eps = float(config["rms_norm_eps"])
    cast = cast or (lambda a: a)
    block = jax.jit(lambda x, lp: _ref_block(x, _f32(lp), config, cast))
    embed = jax.jit(lambda table, t: cast(_f32(table))[t])

    @jax.jit
    def sublayers(x, kp, mp):
        kp, mp = jax.tree.map(cast, (_f32(kp), _f32(mp)))
        kda = _ref_kda(cast(_rms_norm(x, kp["attn_norm"], eps)), kp, config)
        latent = _ref_latent(
            cast(_rms_norm(x, mp["attn_norm"], eps)), mp, config)
        expert, top_e = _ref_expert_layer(
            cast(_rms_norm(x, kp["mlp_norm"], eps)), kp, config)
        return cast(kda), cast(latent), cast(expert), top_e

    with jax.default_matmul_precision("highest"):
        x0 = x = embed(params["embed"], tokens)
        kp = mp = None
        for lp in layers_of(params):
            x = block(x, lp)
            if "router" in lp and "a_log" in lp and kp is None:
                kp = lp
            if "router" in lp and "w_kva" in lp and mp is None:
                mp = lp
        ce = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, cast(_f32(norm)), cast(_f32(w)), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
        kda, latent, expert, top_e = sublayers(x0, kp, mp)
    return {"ce": float(ce), "hidden": x, "kda": kda, "latent": latent,
            "expert": expert, "top_e": top_e}


def reference_loss(params, tokens, config: dict) -> float:
    return reference_pieces(params, tokens, config)["ce"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
#
# Each limit lies between two readings on the chip at the published
# widths (my chip runs, PR 33; PERF.md section 6): the largest the bf16
# program gave against the float32 reference over the builder's seeds,
# and what the reference itself gives against float32 when its weights
# and each sublayer's input and output are rounded to float8_e4m3fn, the
# nearest precision below the bfloat16 the configuration states
# (``second_reading``, two seeds; rounded to bfloat16 the same way it
# reads 0.0088-0.0104 / 0.0027 / 0.0022 / 0.0023 / 99.7-99.8 % / 0.0004-
# 0.0020 and passes every limit). The float8 path fails (a), (b) and (c)
# and passes (d) and the job's loss tolerance: no CE at random init sees
# a precision.
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block: median over the tokens of
    # |program - reference| / |reference| along the row. bf16: 0.0192 to
    # 0.0221 (six seeds); float8: 0.224, 0.227. The tokens whose 8th and
    # 9th expert swap under bf16 are not in a median
    "hidden_rel_median": 0.06,
    # (b) on one input (the embedded batch), the same way: the first
    # expert block's KDA output (bf16 0.0059-0.0060; float8 0.076, 0.077),
    # the latent layer's attention output (0.0047-0.0048; 0.074, 0.075),
    # the first expert block's expert output over the tokens whose
    # choices agree (0.0040; 0.066, 0.067)
    "kda_rel_median": 0.02,
    "latent_rel_median": 0.02,
    "expert_rel_median": 0.015,
    # (c) share of (token, choice) pairs the routers agree on: both route
    # in float32, the program from a bf16 pre-norm; near-ties flip. bf16:
    # 0.9966-0.9985; float8: 0.953, 0.955
    "router_agree_min": 0.98,
    # (d) the CE alone against the reference's: 0.0001-0.0030 over six
    # seeds, and 0.0012 and 0.0023 under float8: no precision moves it, a
    # dropped term or a wrong target does; about three times the first
    # reading (here it is the job's own loss difference, held to half the
    # job's tolerance)
    "ce_abs": 0.01,
}


def program_pieces(cfg, mesh, params, tokens) -> dict:
    """The program's side of ``reference_pieces``."""
    from dlrover_tpu.models import kimi_linear, moe, xing4
    from dlrover_tpu.models.llama import _shift_targets
    from dlrover_tpu.ops import cross_entropy_sums, rms_norm

    def first(attn):
        i = [r[:2] for r in cfg.runs].index((attn, "moe"))
        return jax.tree.map(
            lambda a: a[0], params["runs"][kimi_linear.run_name(i)])

    b, s = tokens.shape

    @jax.jit
    def run(params, kp, mp, tokens):
        hidden = kimi_linear.forward_layers(params, tokens, cfg, mesh)
        nll, n = cross_entropy_sums(
            rms_norm(hidden, params["final_norm"], cfg.norm_eps),
            params["lm_head"], _shift_targets(tokens),
            chunk_size=cfg.ce_chunk_size, mesh=mesh)
        x = params["embed"][tokens].astype(cfg.dtype)
        kda_out = kimi_linear.kda_attention(
            cfg, kp, rms_norm(x, kp["attn_norm"], cfg.norm_eps))
        latent = xing4.latent_attention(
            cfg, mesh, None, None, mp,
            rms_norm(x, mp["attn_norm"], cfg.norm_eps))
        y = rms_norm(x, kp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(
            cfg.as_moe(), kp["router"], y.reshape(b * s, -1),
            kp["router_bias"])
        expert = moe.moe_mlp(cfg.as_moe(), kp, y, mesh)[0]
        return {"ce": nll / jnp.maximum(n, 1.0), "hidden": hidden,
                "kda": kda_out, "latent": latent, "expert": expert,
                "top_e": top_e}

    out = run(params, first("kda"), first("mla"), tokens)
    return dict(out, ce=float(out["ce"]))


def readings(got: dict, want: dict, n_experts: int) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's."""
    k = want["top_e"].shape[1]
    chosen = jax.nn.one_hot(got["top_e"], n_experts, dtype=jnp.int32).sum(1)
    want_chosen = jax.nn.one_hot(
        want["top_e"], n_experts, dtype=jnp.int32).sum(1)           # (t, E)
    same = jnp.sum(chosen * want_chosen, axis=1)                    # (t,)

    def median(name, rows=slice(None)):
        return float(jnp.median(_row_rel(got[name], want[name])[rows]))

    return {
        "hidden_rel_median": median("hidden"),
        "kda_rel_median": median("kda"),
        "latent_rel_median": median("latent"),
        "expert_rel_median": median("expert", same == k),
        "router_agree_min": float(jnp.sum(same)) / (same.shape[0] * k),
        "ce_abs": abs(got["ce"] - want["ce"]),
    }


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items()
    }
    print(f"[kimi_linear] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in LIMITS), flush=True)
    return all(ok.values())


def _compare(cfg, mesh, params, tokens, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each and returns whether all
    hold."""
    got = program_pieces(cfg, mesh, params, tokens)
    held = np.asarray(jnp.sum(
        (got["top_e"] >= cfg.first_expert)
        & (got["top_e"] < cfg.first_expert + cfg.as_moe().n_held)))
    return _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}; {int(held)} of "
        f"{got['top_e'].size} pairs chose a held expert; CE "
        f"{got['ce']:.5f} / {want['ce']:.5f})",
        readings(got, want, cfg.n_experts))


def second_reading(config: dict, seed: int, seq: int = 512) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/train_loop.py`` makes from ``seed``. By hand,
    on the chip::

        python -c "import json
        from benchmarks.families import kimi_linear as f
        f.second_reading(json.load(open(
            'benchmarks/configs/kimi-linear-48b-a3b-1chip.json')), 3)"
    """
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam = build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    want = reference_pieces(params, tokens, config)
    passed = {}
    for name, dtype in (("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        got = reference_pieces(params, tokens, config, _round_trip(dtype))
        passed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed} "
            f"(CE {got['ce']:.5f} / {want['ce']:.5f})",
            readings(got, want, fam.cfg.n_experts))
    return passed
