"""The xing4 family (``model_type: xing4_0``, Xing4.0-29B-A4B), as
``dlrover_tpu.models.xing4`` computes it and as this file's plain
reference computes it again.

Layer equations, from the model's config.json (its keys are
``deepseek_v3``'s plus ``hc_*`` / ``mhc_*``) and arXiv 2512.24880
(manifold-constrained hyper-connections), hidden width ``d``, ``n =
hc_mult`` residual streams ``X (n, b, s, d)``:

- every sublayer ``F`` (attention or feed-forward, each with its own
  ``phi (n, d, n(n+2))``, ``alpha (3,)``, ``b (n(n+2),)``)::

      u      = vec(X) / rms(vec(X))           # a token's n*d values
      H~     = u phi                           # [pre n | post n | res n*n]
      H_pre  = sigmoid(alpha_pre H~_pre + b_pre)
      H_post = 2 sigmoid(alpha_post H~_post + b_post)
      M      = exp(clip(alpha_res mat(H~_res) + b_res, clamp_min, clamp_max))
      20 x:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
      y      = sum_i H_pre[i] X[i]
      X'[i]  = sum_j M[i, j] X[j] + H_post[i] F(RMSNorm(y; w_F))

- latent attention::

      c_q = RMSNorm(y W_qa);  q = c_q W_qb -> heads of [nope 128 | rope 64]
      [c_kv | k_r] = y W_kva;  [k_nope | v] per head = RMSNorm(c_kv) W_kvb
      rotary (yarn frequencies) on q's rope part and on k_r, first half of
      the 64 against the second; k = [k_nope | k_r, one for all heads]
      out = softmax_causal(s q k^T) v, s = 192^-0.5 (0.1 mscale_all_dim
      ln(factor) + 1)^2;  attn = concat(out) W_o

- expert layer: ``sc = sigmoid(y W_r)``; the ``k`` experts with the
  largest ``sc + b_corr``; ``w = sc[chosen] / sum(sc[chosen]) x
  routed_scaling_factor``; ``sum_j w_j Expert_j(y) + Shared(y)``, all
  SwiGLU. This chip holds experts ``first_expert .. + n_routed_experts -
  1`` of ``published_n_routed_experts``: a pair that chose another adds
  nothing. Dense layer: one SwiGLU.
- ``x = sum_i X[i]``; ``CE_main`` over ``RMSNorm(x) W_head``; the
  multi-token module ``[RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_i)] W_eh`` ->
  n streams -> one expert block -> sum -> RMSNorm -> ``W_head`` against
  ``t_{i+2}``; ``loss = CE_main + mtp_loss_weight CE_mtp``.

What config.json does not say is under ``assumed`` in the configuration.

The reference is float32 at matmul precision "highest", with no kernel,
no sort (a loop over the held experts, each on all tokens, weighted by
the token's ``w`` or 0) and attention by explicit scores and mask. It
imports nothing of ``dlrover_tpu``.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import xing4_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("attention_bias", False), ("model_type", "xing4_0"),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1)):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/xing4.py computes ({want!r})"
            )
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one k/v head a query head")
    if config["rope_scaling"]["type"] != "yarn":
        raise ValueError("models/xing4.py computes yarn frequencies only")
    dense = config["first_k_dense_replace"]
    return dict(
        n_dense_layers=dense,
        n_moe_layers=config["num_hidden_layers"] - dense,
        mtp_depth=config["num_nextn_predict_layers"],
        dim=config["hidden_size"], n_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_ffn_dim=config["intermediate_size"],
        expert_ffn_dim=config["moe_intermediate_size"],
        n_experts=config.get("published_n_routed_experts",
                             config["n_routed_experts"]),
        experts_held=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        hc_mult=config["hc_mult"], vocab_size=config["vocab_size"],
    )


def _mtp_weight(config: dict) -> float:
    return float(config["assumed"]["mtp_loss_weight"])


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import xing4
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed, yarn = config["assumed"], config["rope_scaling"]
    cfg = xing4.Xing4Config(
        **sizes,
        first_expert=int(config.get("first_expert", 0)),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling=float(config["routed_scaling_factor"]),
        scoring=config["scoring_func"],
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        mtp_loss_weight=_mtp_weight(config),
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_max=int(yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/xing4.py remats a whole block or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/xing4.py initialises with sigma 0.02 only")
    specs = xing4.param_specs(cfg)
    init = jax.jit(
        lambda key: xing4.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs),
    )

    def reference(params, tokens):
        terms = reference_terms(params, tokens, config)
        ok = _compare(xing4, cfg, mesh, params, tokens, config, terms)
        return terms["loss"] if ok else float("nan")

    ce_at_init = math.log(sizes["vocab_size"]) + sizes["dim"] * std * std / 2
    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        loss_fn=lambda p, t: xing4.loss_fn(p, t, cfg, mesh),
        param_count=xing4.param_count(cfg),
        flops_per_token=lambda seq: xing4_flops.flops_per_token(
            seq=seq, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2
        # under either head, so each CE is ln V + dim x sigma^2 / 2
        expected_first_loss=(1.0 + _mtp_weight(config)
                             * bool(sizes["mtp_depth"])) * ce_at_init,
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def yarn_inv_freq(rot_dim, theta, factor, original_max, beta_fast,
                  beta_slow):
    """The closed form, in numpy: pair i's frequency theta^(-2i/rot_dim),
    divided by ``factor`` past pair ``high``, kept up to pair ``low``,
    blended linearly between."""
    def pair(beta):
        return (rot_dim * math.log(original_max / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), rot_dim - 1)
    i = np.arange(rot_dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / rot_dim)
    mask = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (extra / factor * (1.0 - mask) + extra * mask).astype(np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rotary(x, inv_freq, magnitude):
    # x: (b, s, heads, rot_dim)
    s, rd = x.shape[1], x.shape[3]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * magnitude)[None, :, None, :]
    sin = (jnp.sin(angles) * magnitude)[None, :, None, :]
    x1, x2 = x[..., : rd // 2], x[..., rd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_attention(y, lp, config):
    """``y (b, s, d)``, already pre-normed -> the attention output."""
    b, s, _ = y.shape
    h = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rkv = config["v_head_dim"], config["kv_lora_rank"]
    eps, yarn = float(config["rms_norm_eps"]), config["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(
        dr, float(config["rope_theta"]), yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"]))
    m = _mscale(yarn["factor"], yarn["mscale_all_dim"])
    magnitude = _mscale(yarn["factor"], yarn["mscale"]) / m
    scale = (dn + dr) ** -0.5 * m * m
    q = (_rms_norm(y @ lp["w_qa"], lp["q_a_norm"], eps) @ lp["w_qb"]
         ).reshape(b, s, h, dn + dr)
    kva = y @ lp["w_kva"]
    kv = (_rms_norm(kva[..., :rkv], lp["kv_a_norm"], eps) @ lp["w_kvb"]
          ).reshape(b, s, h, dn + dv)
    q_rope = _rotary(q[..., dn:], inv_freq, magnitude)
    k_rope = _rotary(kva[:, :, None, rkv:], inv_freq, magnitude)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     kv[..., dn:])
    return out.reshape(b, s, h * dv) @ lp["w_o"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _ref_router(yt, lp, config):
    """``yt (t, d)`` -> per-expert weight (t, E) over ALL the experts the
    router scores (a token's w for the experts it chose, 0 for the
    others) and the chosen experts (t, k)."""
    sc = jax.nn.sigmoid(yt @ lp["router"])
    _, top_e = jax.lax.top_k(sc + lp["router_bias"],
                             config["num_experts_per_tok"])
    top_s = jnp.take_along_axis(sc, top_e, axis=1)
    if config["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    top_s = top_s * float(config["routed_scaling_factor"])
    chose = top_e[:, :, None] == jnp.arange(sc.shape[1])[None, None, :]
    return jnp.sum(jnp.where(chose, top_s[:, :, None], 0.0), axis=1), top_e


def _ref_expert_layer(y, lp, config):
    """``y (b, s, d)``, pre-normed -> (held experts' part + shared expert,
    chosen experts (t, k))."""
    b, s, d = y.shape
    yt = y.reshape(b * s, d)
    weight, top_e = _ref_router(yt, lp, config)
    first = int(config.get("first_expert", 0))

    def one(i, out):
        return out + weight[:, first + i, None] * _swiglu(
            yt, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])

    # a loop over the held experts (rolled, so that they trace once),
    # every one on every token; an absent expert is not in the loop
    out = jax.lax.fori_loop(0, lp["w_gate"].shape[0], one, jnp.zeros_like(yt))
    if config["n_shared_experts"]:
        out = out + _swiglu(yt, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out.reshape(b, s, d), top_e


def ref_hc_coefficients(X, phi, alpha, bias, config):
    """``X (n, b, s, d)`` -> ``H_pre (b, s, n)``, ``H_post (b, s, n)``,
    ``H_res (b, s, n, n)``."""
    n, b, s, d = X.shape
    vec = jnp.moveaxis(X, 0, 2).reshape(b, s, n * d)    # [X[0] ; X[1] ; ..]
    u = vec * jax.lax.rsqrt(
        jnp.mean(vec * vec, axis=-1, keepdims=True)
        + float(config["rms_norm_eps"]))
    raw = u @ phi.reshape(n * d, -1)
    pre = alpha[0] * raw[..., :n] + bias[:n]
    post = alpha[1] * raw[..., n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    m = jnp.exp(jnp.clip(res, float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"])))
    hc_eps = float(config["hc_eps"])
    for _ in range(int(config["hc_sinkhorn_iters"])):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)   # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)   # rows
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


def _ref_sublayer(X, lp, name, config, fn):
    h_pre, h_post, h_res = ref_hc_coefficients(
        X, lp[f"{name}_phi"], lp[f"{name}_alpha"], lp[f"{name}_bias"], config)
    z = fn(jnp.einsum("bsn,nbsd->bsd", h_pre, X))
    return (jnp.einsum("bsij,jbsd->ibsd", h_res, X)
            + jnp.einsum("bsi,bsd->ibsd", h_post, z))


def _ref_block(X, lp, config):
    eps = float(config["rms_norm_eps"])
    X = _ref_sublayer(
        X, lp, "hc_attn", config,
        lambda y: _ref_attention(_rms_norm(y, lp["attn_norm"], eps), lp,
                                 config))

    def feed_forward(y):
        y = _rms_norm(y, lp["mlp_norm"], eps)
        if "router" in lp:
            return _ref_expert_layer(y, lp, config)[0]
        return _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"])

    return _ref_sublayer(X, lp, "hc_mlp", config, feed_forward)


def _ref_ce(x, norm, lm_head, targets, eps):
    """Mean CE of ``x (b, s, d)`` against ``targets (b, s)``, -1 = none."""
    logp = jax.nn.log_softmax(_rms_norm(x, norm, eps) @ lm_head, axis=-1)
    gold = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    valid = targets >= 0
    return -jnp.sum(jnp.where(valid, gold, 0.0)) / jnp.sum(valid)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _shifted(tokens, by):
    return jnp.pad(tokens[:, by:], ((0, 0), (0, by)), constant_values=-1)


def _join(e, h, mp, eps):
    """The multi-token module's input: [RMSNorm(emb) ; RMSNorm(hidden)]
    W_eh."""
    return jnp.concatenate(
        [_rms_norm(e, mp["enorm"], eps), _rms_norm(h, mp["hnorm"], eps)],
        -1) @ mp["w_eh"]


def plain_terms(params, tokens, config: dict, embed=None, block=None,
                join=None, ce=None):
    """``(CE_main, CE_mtp, hidden)`` of ``tokens`` (b, s) under float32
    ``params``: the equations of the module docstring composed once,
    differentiable as it stands. ``reference_terms`` hands in the same
    pieces jitted and cast a block at a time."""
    sizes = _sizes(config)
    eps = float(config["rms_norm_eps"])
    embed = embed or (lambda table, t: table[t])
    block = block or (lambda X, lp: _ref_block(X, lp, config))
    join = join or (lambda e, h, mp: _join(e, h, mp, eps))
    ce = ce or (lambda x, norm, w, t: _ref_ce(x, norm, w, t, eps))

    def streams(x):
        return jnp.broadcast_to(x[None], (sizes["hc_mult"],) + x.shape)

    X = streams(embed(params["embed"], tokens))
    for slab in ("dense", "layers"):
        depth = jax.tree.leaves(params[slab])[0].shape[0]
        for i in range(depth):
            X = block(X, jax.tree.map(lambda a: a[i], params[slab]))
    hidden = jnp.sum(X, axis=0)
    ce_main = ce(hidden, params["final_norm"], params["lm_head"],
                 _shifted(tokens, 1))
    if not sizes["mtp_depth"]:
        return ce_main, 0.0, hidden
    mp = params["mtp"]
    nxt = jnp.maximum(_shifted(tokens, 1), 0)
    heads = {k: mp[k] for k in ("enorm", "hnorm", "w_eh")}
    X = streams(join(embed(params["embed"], nxt), hidden, heads))
    X = block(X, jax.tree.map(lambda a: a[0], mp["block"]))
    ce_mtp = ce(jnp.sum(X, axis=0), mp["norm"], params["lm_head"],
                _shifted(tokens, 2))
    return ce_main, ce_mtp, hidden


def plain_loss(params, tokens, config: dict):
    ce_main, ce_mtp, _ = plain_terms(params, tokens, config)
    return ce_main + _mtp_weight(config) * ce_mtp


def reference_terms(params, tokens, config: dict) -> dict:
    """``{"loss", "ce_main", "ce_mtp", "hidden"}`` of ``tokens`` (b, s)
    under ``params`` (the program's parameter tree, any dtype), one block
    cast to float32 at a time so that it fits beside a full device.
    ``hidden`` is the summed streams before the final norm."""
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        ce_main, ce_mtp, hidden = plain_terms(
            params, tokens, config,
            embed=jax.jit(lambda table, t: _f32(table)[t]),
            block=jax.jit(lambda X, lp: _ref_block(X, _f32(lp), config)),
            join=jax.jit(lambda e, h, mp: _join(e, h, _f32(mp), eps)),
            ce=jax.jit(lambda x, norm, w, t: _ref_ce(
                x, _f32(norm), _f32(w), t, eps)),
        )
        ce_main, ce_mtp = float(ce_main), float(ce_mtp)
    return {"loss": ce_main + _mtp_weight(config) * ce_mtp,
            "ce_main": ce_main, "ce_mtp": ce_mtp, "hidden": hidden}


def reference_loss(params, tokens, config: dict) -> float:
    return reference_terms(params, tokens, config)["loss"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init each CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
#
# Each limit lies between two readings on the chip at the published
# widths (my chip runs, PR 31; PERF.md section 6): the largest the bf16
# program gave against the float32 reference over 8 seeds, and what the
# reference itself gives against float32 when its weights, norms, rotary,
# SwiGLUs and sublayer inputs and outputs are rounded to float8_e4m3fn,
# the nearest precision below the bfloat16 the configuration states
# (rounded to bfloat16 the same way it reads 0.0101 / 0.0039 / 0.0042,
# beside the program's own readings). That float8 path passes the job's
# loss tolerance (its loss is 0.0015-0.0049 off) and fails (a) and (b).
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) a token's summed streams: median over the tokens of
    # |program - reference| / |reference| along the row. bf16: 0.0117 to
    # 0.0122; float8: 0.259, 0.262. The few tokens whose 4th and 5th
    # expert swap under bf16 (p99 0.15-0.22) are not in a median
    "hidden_rel_median": 0.05,
    # (b) the first expert block's attention output and expert output on
    # one input, the same way (expert: over the tokens whose choices
    # agree). bf16: 0.00565-0.00586 and 0.00400-0.00401; float8: 0.096,
    # 0.099 and 0.0875, 0.0879
    "attention_rel_median": 0.025,
    "expert_rel_median": 0.02,
    # (c) share of (token, choice) pairs the routers agree on: both route
    # in float32, the program from a bf16 pre-norm; near-ties flip. bf16:
    # 0.9956-0.9995; under float8 only 82 % of the tokens keep all four
    "router_agree_min": 0.98,
    # (d) H_res of the program, float32: |row sum - 1| after the last
    # round's row normalisation (hc_eps 1e-6 is what is left: 1.2e-6 to
    # 1.3e-6), and |column sum - 1|, which 20 rounds bring only so far
    # from exp(6 I + small): near a permutation Sinkhorn converges
    # slowly (5.5e-4 to 7.0e-4). No precision moves these; a Sinkhorn
    # that stops early or normalises one side does
    "h_res_row_max": 1e-5,
    "h_res_col_max": 5e-3,
    # (e) each CE alone against the reference's. bf16: 0.0003-0.0035;
    # float8: 0.017, 0.018
    "ce_abs": 0.008,
}


def _row_rel(got, want):
    got = got.astype(jnp.float32).reshape(-1, got.shape[-1])
    want = want.reshape(-1, want.shape[-1])
    return jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
        jnp.linalg.norm(want, axis=-1), 1e-30)


def _compare(xing4, cfg, mesh, params, tokens, config, terms) -> bool:
    """The comparisons (a) to (e); logs each and returns whether all hold."""
    eps = float(config["rms_norm_eps"])
    k, e = config["num_experts_per_tok"], cfg.n_experts
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    b, s = tokens.shape

    @jax.jit
    def program(params, lp, tokens):
        from dlrover_tpu.models import moe
        from dlrover_tpu.ops import rms_norm

        ce_main, ce_mtp, hidden = xing4.loss_terms(params, tokens, cfg, mesh)
        x = params["embed"][tokens].astype(cfg.dtype)
        attn = xing4.latent_attention(
            cfg, mesh, *xing4.rotary_tables(cfg, tokens), lp,
            rms_norm(x, lp["attn_norm"], cfg.norm_eps))
        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(
            cfg.as_moe(), lp["router"], y.reshape(b * s, -1),
            lp["router_bias"])
        expert = moe.moe_mlp(cfg.as_moe(), lp, y, mesh)[0]
        _, _, h_res = xing4.hc_coefficients(
            cfg, lp["hc_attn_phi"], lp["hc_attn_alpha"], lp["hc_attn_bias"],
            jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape))
        return hidden, ce_main, ce_mtp, attn, expert, top_e, h_res

    @jax.jit
    def reference(params, lp, tokens):
        lp = _f32(lp)
        x = params["embed"][tokens].astype(cfg.dtype).astype(jnp.float32)
        attn = _ref_attention(_rms_norm(x, lp["attn_norm"], eps), lp, config)
        expert, top_e = _ref_expert_layer(
            _rms_norm(x, lp["mlp_norm"], eps), lp, config)
        return attn, expert, top_e

    hidden, ce_main, ce_mtp, attn, expert, top_e, h_res = program(
        params, lp, tokens)
    with jax.default_matmul_precision("highest"):
        want_attn, want_expert, want_e = reference(params, lp, tokens)
    chosen = jax.nn.one_hot(top_e, e, dtype=jnp.int32).sum(1)       # (t, E)
    want_chosen = jax.nn.one_hot(want_e, e, dtype=jnp.int32).sum(1)
    same = jnp.sum(chosen * want_chosen, axis=1)                    # (t,)
    expert_rel = _row_rel(expert, want_expert)
    held = np.asarray(jnp.sum(
        chosen[:, cfg.first_expert:cfg.first_expert + cfg.as_moe().n_held]))
    got = {
        "hidden_rel_median": float(jnp.median(
            _row_rel(hidden, terms["hidden"]))),
        "attention_rel_median": float(jnp.median(_row_rel(attn, want_attn))),
        "expert_rel_median": float(jnp.median(expert_rel[same == k])),
        "router_agree_min": float(jnp.sum(same)) / (b * s * k),
        "h_res_row_max": float(
            jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0))),
        "h_res_col_max": float(
            jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0))),
        "ce_abs": max(abs(float(ce_main) - terms["ce_main"]),
                      abs(float(ce_mtp) - terms["ce_mtp"])),
    }
    ok = {
        name: (got[name] >= limit if name.endswith("_min")
               else got[name] <= limit)
        for name, limit in LIMITS.items()
    }
    print(f"[xing4] program against reference on the seeded batch "
          f"({b * s} tokens; {int(held)} of {b * s * k} pairs chose a held "
          f"expert): " + "; ".join(
              f"{name} {got[name]:.4g} (limit {LIMITS[name]:g}, "
              f"{'ok' if ok[name] else 'FAILED'})" for name in LIMITS)
          + f"; CE_main {float(ce_main):.5f} / {terms['ce_main']:.5f}, "
          f"CE_mtp {float(ce_mtp):.5f} / {terms['ce_mtp']:.5f}; hidden "
          f"rel p99 {float(jnp.quantile(_row_rel(hidden, terms['hidden']), 0.99)):.4g}",
          flush=True)
    return all(ok.values())
