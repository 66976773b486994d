"""The dots3 family (dots3-note-prev, language model), as
``dlrover_tpu.models.dots3`` computes it and as this file's plain
reference computes it again.

Layer equations, from the model's config.json and what ``assumed`` in the
configuration states (hidden ``d``; no bias anywhere but the indexer's
LayerNorm; untied head); ``y = RMSNorm(x; attn_norm)``, eps 1e-5::

    full layer (layer_types[l] == "full_attention"), heads h held:
      c_q  = a_q RMSNorm(y W_qa),  a_q = sqrt(d / q_lora_rank)
      q_h  = c_q W_qb,h in R^(nope + rope); rotary(theta) on the last rope
      [c | k_r] = y W_kva;  c_kv = a_kv RMSNorm(c), a_kv = sqrt(d / kv_rank)
      [k_h^nope | v_h] = c_kv W_kvb,h;  k_h = [k_h^nope | rotary(k_r)]
      indexer, on sg(y), sg(c_q):
        qI_j = c_q W_Iq,j;  kI = LayerNorm(y W_Ik); rotary on the first
        rope dims of each;  w = y W_Iw / sqrt(index_n_heads index_head_dim)
        I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])          float32
      S_t  = the index_topk keys s <= t of largest I[t, s] (ties to the
             lower s; all of them while t < index_topk)
      o_h  = softmax_{s in S_t}(q_h . k_h / sqrt(nope + rope)) v_h
      attn = concat_h(sigmoid(y W_g)_h o_h) W_o
      L_I  = mean_t KL(p^_t || softmax_{s in S_t} I[t, s]),
             p[t, s] = sum_h P[t, h, s], p^ = p / sum_s p, a constant
    window layer ("sliding_attention"): the same at the swa_* ranks,
      widths, heads and theta, no indexer, keys 0 <= t - s < window
    x = x + attn;  u = RMSNorm(x; mlp_norm)
    layer < first_k_dense_replace:  x = x + SwiGLU(u)
    else: sc = sigmoid(u W_r) float32; the k largest of sc + bias;
          w = sc_chosen / sum(sc_chosen) * routed_scaling_factor
          x = x + sum_j w_j SwiGLU_{e_j}(u) + SwiGLU_shared(u)

Final RMSNorm, the head, ``loss = CE + mean over full layers of L_I``.
This chip holds heads ``first_head ..`` of a layer's published heads and
experts ``first_expert ..`` of ``published_n_routed_experts``: an absent
head adds nothing to ``attn`` nor to ``p``, a pair that chose an absent
expert nothing.

The reference is float32 at matmul precision "highest", ``jax.numpy``
alone: the indexer and attention by explicit scores **in blocks of query
rows** (so that 8192 positions fit beside the state), the selection by
``lax.top_k``, the expert layer a loop over the held experts, the
cross-entropy in blocks of rows. It imports nothing of ``dlrover_tpu``;
what every reference shares (norm, casts, the row-wise relative error,
the sigmoid router and its expert loop, the blocked cross-entropy) is
``families/xing4.py``'s and ``families/smallthinker.py``'s.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.smallthinker import _ref_ce, _rotary, _round_trip
from benchmarks.families.xing4 import (
    _f32, _ref_expert_layer, _ref_router, _rms_norm, _row_rel, _shifted,
    _swiglu)
from benchmarks.harness import dots3_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

Q_BLOCK = 256      # queries a block of the reference's attention
I_BLOCK = 128      # queries a block of the reference's indexer

FULL, WINDOW = "F", "S"


def kinds_of(config: dict):
    return dots3_flops.kinds_of(config)


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import dots3
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/dots3.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/dots3.py initialises with sigma 0.02 only")
    # the file's head and expert counts are the held ones; the program's
    # config keeps the published beside them
    published = {
        key: config.get("published_" + key, config[key])
        for key in ("num_attention_heads", "swa_num_attention_heads",
                    "n_routed_experts")}
    cfg = dots3.Dots3Config.from_hf(
        dict(config, **published),
        heads_held=config["num_attention_heads"],
        swa_heads_held=config["swa_num_attention_heads"],
        first_head=int(config.get("first_head", 0)),
        swa_first_head=int(config.get("swa_first_head", 0)),
        experts_held=config["n_routed_experts"],
        first_expert=int(config.get("first_expert", 0)),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = dots3.param_specs(cfg)
    # assumed.out_proj_std: the sigma of the projections that close a
    # residual branch, where the configuration states one
    out_scale = (float(assumed["out_proj_std"]) / std
                 if "out_proj_std" in assumed else None)

    def init_params(key):
        params = dots3.init_params(cfg, key)
        if out_scale is None:
            return params

        def scaled(lp):
            return {name: (w * out_scale).astype(w.dtype)
                    if name in ("w_o", "w_down", "ws_down") else w
                    for name, w in lp.items()}

        return dict(params, **{
            group: {name: scaled(lp) for name, lp in params[group].items()}
            for group in ("dense", "layers", "tail")})

    init = jax.jit(init_params, out_shardings=named_shardings(mesh, specs))

    def reference(params, tokens):
        want = reference_pieces(params, tokens, config)
        ok = _compare(cfg, mesh, params, tokens, config, want)
        return want["ce"] + want["l_i"] if ok else float("nan")

    vocab, dim = config["vocab_size"], config["hidden_size"]
    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(lambda p, t: dots3.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: dots3.loss_fn(p, t, cfg, mesh),
        param_count=dots3.param_count(cfg),
        flops_per_token=lambda seq: dots3_flops.flops_per_token(config, seq),
        # random weights at sigma give logits of variance dim x sigma^2;
        # the indexer's KL at init is what the configuration states
        expected_first_loss=(
            math.log(vocab) + dim * std * std / 2
            + float(assumed.get("indexer_loss_at_init", 0.0))),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _latent_sizes(config: dict, kind: str) -> dict:
    pre = "" if kind == FULL else "swa_"
    return dict(
        heads=config[pre + "num_attention_heads"],
        q_rank=config[pre + "q_lora_rank"],
        kv_rank=config[pre + "kv_lora_rank"],
        nope=config[pre + "qk_nope_head_dim"],
        rope=config[pre + "qk_rope_head_dim"],
        v=config[pre + "v_head_dim"],
        theta=float(config[pre + "rope_theta"]),
    )


def _rotary_last(x, n: int, theta: float):
    """Rotary on the last ``n`` of ``x (b, s, heads, d)``."""
    return jnp.concatenate(
        [x[..., :-n], _rotary(x[..., -n:], theta)], axis=-1)


def _ref_qkv(y, lp, config, kind: str):
    """``y (b, s, d)`` pre-normed -> ``q, k (b, s, h, nope + rope)``, ``v
    (b, s, h, v)``, ``c_q (b, s, q_rank)``."""
    z = _latent_sizes(config, kind)
    b, s, d = y.shape
    eps = float(config["rms_norm_eps"])
    rescale = bool(config["apply_mla_qkv_lora_rescale"])
    a_q = (d / z["q_rank"]) ** 0.5 if rescale else 1.0
    a_kv = (d / z["kv_rank"]) ** 0.5 if rescale else 1.0
    c_q = a_q * _rms_norm(y @ lp["w_qa"], lp["q_a_norm"], eps)
    q = (c_q @ lp["w_qb"]).reshape(b, s, z["heads"], z["nope"] + z["rope"])
    q = _rotary_last(q, z["rope"], z["theta"])
    kva = y @ lp["w_kva"]
    c_kv = a_kv * _rms_norm(kva[..., :z["kv_rank"]], lp["kv_a_norm"], eps)
    kv = (c_kv @ lp["w_kvb"]).reshape(b, s, z["heads"], z["nope"] + z["v"])
    k_rope = _rotary(kva[:, :, None, z["kv_rank"]:], z["theta"])
    k = jnp.concatenate([
        kv[..., :z["nope"]],
        jnp.broadcast_to(k_rope, (b, s, z["heads"], z["rope"]))], axis=-1)
    return q, k, kv[..., z["nope"]:], c_q


def _ref_index_scores(y, c_q, lp, config, cast=lambda a: a):
    """The indexer's ``I (b, s, s)``, a block of query rows at a time."""
    b, s, _ = y.shape
    hi, di = config["index_n_heads"], config["index_head_dim"]
    rope, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    q = (c_q @ lp["idx_wq"]).reshape(b, s, hi, di)
    k = y @ lp["idx_wk"]
    k = (k - jnp.mean(k, -1, keepdims=True)) * jax.lax.rsqrt(
        jnp.var(k, -1, keepdims=True) + eps)
    k = (k * lp["idx_k_norm"] + lp["idx_k_bias"])[:, :, None, :]
    # rotary on the first rope dims of each
    q = jnp.concatenate([_rotary(q[..., :rope], theta), q[..., rope:]], -1)
    k = jnp.concatenate([_rotary(k[..., :rope], theta), k[..., rope:]], -1)
    q, k = cast(q), cast(k[:, :, 0])
    w = cast((y @ lp["idx_ww"]) * (hi ** -0.5 * di ** -0.5))
    block = I_BLOCK if s % I_BLOCK == 0 else s

    def one(args):
        qb, wb = args
        dots = jnp.einsum("bqhd,bkd->bqhk", qb, k)
        return jnp.sum(wb[..., None] * jax.nn.relu(dots), axis=2)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // block, block, hi, di), 1, 0),
        jnp.moveaxis(w.reshape(b, s // block, block, hi), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


def _causal(s: int):
    pos = jnp.arange(s)
    return pos[None, :] <= pos[:, None]


def _ref_selection(scores, topk: int):
    """``(b, s, s)`` bool: the ``topk`` causal keys of largest score a
    row (``lax.top_k``: ties to the lower ``s``), all while ``t < topk``."""
    b, s, _ = scores.shape
    causal = _causal(s)
    if topk >= s:
        return jnp.broadcast_to(causal, scores.shape)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    neg = jnp.where(causal, scores, -jnp.inf)

    def one(rows):                                       # (b, block, s)
        _, idx = jax.lax.top_k(rows, topk)
        return jnp.zeros(rows.shape, bool).at[
            jnp.arange(b)[:, None, None],
            jnp.arange(block)[None, :, None], idx].set(True)

    out = jax.lax.map(
        one, jnp.moveaxis(neg.reshape(b, s // block, block, s), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s) & causal


def _window_mask(s: int, window: int):
    pos = jnp.arange(s)
    ago = pos[:, None] - pos[None, :]
    return (ago >= 0) & (ago < window)


def _ref_masked_attention(q, k, v, mask, scale: float):
    """softmax over the keys ``mask (b or 1, s, s)`` names -> ``(out (b,
    s, h, v), the probabilities summed over the heads (b, s, s))``:
    explicit scores over all the keys, a block of queries at a time,
    recomputed in a backward pass."""
    b, s, h, d = q.shape
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    mask = jnp.broadcast_to(mask, (b, s, s))

    @jax.checkpoint
    def one(args):
        qb, mb = args                       # (b, block, h, d), (b, block, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(mb[:, None], scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v), jnp.sum(p, axis=1)

    out, p = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0),
        jnp.moveaxis(mask.reshape(b, s // block, block, s), 1, 0)))
    return (jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1]),
            jnp.moveaxis(p, 0, 1).reshape(b, s, s))


def _ref_indexer_loss(scores, p, mask):
    """Mean over the rows of ``KL(p^ || softmax over the mask of
    scores)``."""
    target = p / jnp.sum(p, axis=-1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    kl = jnp.where(
        mask & (target > 0),
        target * (jnp.log(jnp.where(target > 0, target, 1.0)) - logq), 0.0)
    return jnp.sum(kl) / (scores.shape[0] * scores.shape[1])


def _ref_attention(y, lp, config, kind: str, cast=lambda a: a, select=None):
    """``y (b, s, d)`` pre-normed -> dict: ``attn`` the sublayer's output;
    ``gate``; of a full layer ``scores``, ``mask`` (its own selection, or
    ``select`` where given: the program's) and ``l_i``."""
    b, s, _ = y.shape
    # the definition's stop-gradients: the indexer reads constants, and
    # L_I's target is one
    sg = jax.lax.stop_gradient
    q, k, v, c_q = _ref_qkv(y, lp, config, kind)
    scale = q.shape[-1] ** -0.5
    out = {}
    if kind == FULL:
        out["scores"] = _ref_index_scores(sg(y), sg(c_q), lp, config, cast)
        mask = out["mask"] = (
            _ref_selection(sg(out["scores"]), config["index_topk"])
            if select is None else select)
    else:
        mask = _window_mask(s, config["sliding_window_size"])[None]
    o, p = _ref_masked_attention(q, k, v, mask, scale)
    if kind == FULL:
        out["l_i"] = _ref_indexer_loss(out["scores"], sg(p), mask)
    out["gate"] = jax.nn.sigmoid(y @ lp["w_g"])
    out["attn"] = (o * out["gate"][..., None]).reshape(b, s, -1) @ lp["w_o"]
    return out


def _ref_block(x, lp, config, kind: str, cast=lambda a: a, select=None):
    """One layer -> dict: ``after`` the residual after it, ``u`` the
    feed-forward's normed input, ``ffn`` its output, ``top_e`` the
    router's choices (an expert layer), and `_ref_attention`'s.
    ``cast`` rounds the weights and each sublayer's input and output
    (``second_reading``)."""
    eps = float(config["rms_norm_eps"])
    lp = jax.tree.map(cast, lp)
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    out = _ref_attention(y, lp, config, kind, cast, select)
    out["attn"] = cast(out["attn"])
    x = x + out["attn"]
    u = out["u"] = cast(_rms_norm(x, lp["mlp_norm"], eps))
    if "router" in lp:
        ffn, out["top_e"] = _ref_expert_layer(u, lp, config)
    else:
        ffn = _swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
    out["ffn"] = cast(ffn)
    out["after"] = x + out["ffn"]
    return out


def layers_of(params, config: dict):
    """The layers' parameter trees, first to last, as the program's layout
    keeps them: ``dense`` a tree a layer, ``layers`` stacked a position
    of the period (layer ``n_dense + r p + i`` is row ``r`` of ``pos i``),
    ``tail`` a tree a layer."""
    def numbered(group):
        return [group[name] for name in sorted(
            group, key=lambda name: int(name.lstrip("layerpos")))]

    yield from numbered(params["dense"])
    slabs = numbered(params["layers"])
    if slabs:
        for row in range(jax.tree.leaves(slabs[0])[0].shape[0]):
            for slab in slabs:
                yield jax.tree.map(lambda a: a[row], slab)
    yield from numbered(params["tail"])


def plain_loss(params, tokens, config: dict):
    """``(CE, L_I)`` of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as it
    stands (the selection and ``p`` are constants)."""
    x = params["embed"][tokens]
    kinds = kinds_of(config)
    l_i = 0.0
    for lp, kind in zip(layers_of(params, config), kinds):
        out = _ref_block(x, lp, config, kind)
        x = out["after"]
        l_i = l_i + out.get("l_i", 0.0)
    ce = _ref_ce(x, params["final_norm"], params["lm_head"],
                 _shifted(tokens, 1), float(config["rms_norm_eps"]))
    return ce, l_i / max(kinds.count(FULL), 1)


def _ref_core_grads(q, k, v, mask, dt, cast):
    """What holds the attention *backward* to the definition: q, k, v
    rounded to the activation dtype (the operands both sides read), a
    seeded cotangent ``g`` of the core's output, and dq, dk, dv of
    `_ref_masked_attention` under ``mask`` there, in float32. Returns
    ``((q, k, v, g), (dq, dk, dv))``."""
    q, k, v = (a.astype(dt) for a in (q, k, v))
    g = jax.random.normal(
        jax.random.key(0), v.shape, jnp.float32).astype(dt)
    _, vjp = jax.vjp(
        lambda q, k, v: _ref_masked_attention(
            q, k, v, mask, q.shape[-1] ** -0.5)[0],
        *(cast(_f32(a)) for a in (q, k, v)))
    return (q, k, v, g), tuple(cast(d) for d in vjp(cast(_f32(g))))


def reference_pieces(params, tokens, config: dict, cast=None,
                     inputs=None) -> dict:
    """What the comparisons read, from the reference: ``ce``, ``l_i``;
    ``hidden``, the residual after the last block; and of each layer
    ``resid[i]``, the residual *before* it, and `_ref_block`'s dict
    ``layer[i]``. ``params`` is the program's tree in any dtype; one
    layer is cast to float32 at a time so that it fits beside a full
    device. ``cast`` (``second_reading``) rounds weights and sublayer
    inputs and outputs; the pieces are then read on ``inputs[i]`` (the
    float32 reference's ``resid``), as the program's are, beside the
    rounded chain."""
    eps = float(config["rms_norm_eps"])
    cast = cast or (lambda a: a)
    block = jax.jit(
        lambda x, lp, kind: _ref_block(x, _f32(lp), config, kind, cast),
        static_argnums=2)
    embed = jax.jit(lambda table, t: cast(_f32(table))[t])
    kinds = kinds_of(config)
    out = {"resid": [], "layer": []}
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i, (lp, kind) in enumerate(
                zip(layers_of(params, config), kinds)):
            # the pieces wait on the host: the state fills the device
            out["resid"].append(jax.device_get(x))
            read = block(x if inputs is None else inputs[i], lp, kind)
            x = read["after"] if inputs is None else block(
                x, lp, kind)["after"]
            out["layer"].append(jax.device_get(read))
            del read
        ce = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, cast(_f32(norm)), cast(_f32(w)), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
    l_i = sum(float(r["l_i"]) for r in out["layer"] if "l_i" in r)
    return dict(out, ce=float(ce), l_i=l_i / max(kinds.count(FULL), 1),
                hidden=jax.device_get(x))


def reference_loss(params, tokens, config: dict) -> float:
    want = reference_pieces(params, tokens, config)
    return want["ce"] + want["l_i"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
# Except for (a), each piece is the program's layer on the *reference's*
# input to that layer (rounded to the activation dtype), so that a
# reading is one layer's error and not the chain's.
#
# Each limit lies between two readings on the chip at the published
# widths and 8192 positions (my chip runs, PR 40; PERF.md section 6): the
# largest the bf16 program gave against the float32 reference over the
# cell's six seeds, and what `second_reading` gave on seed 1618033989:
# the reference with its weights and each sublayer's input and output
# rounded to float8_e4m3fn, the nearest precision below the bfloat16 the
# configuration states (it fails ten of the twelve it reads; rounded to
# bfloat16 the same way it passes them all), and the reference's router
# scores and indexer scores alone in bfloat16, where float32 is stated
# (that fails (h) twice; no limit on the selection can tell a bfloat16 I
# from the program: it flips 0.05 % of the pairs, the program's bf16
# operands 0.21 %).
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward: median over the tokens of |program - reference| /
    # |reference| along the row. bf16: 0.00642-0.00644; float8: 0.670
    "hidden_rel_median": 0.03,
    # (b) the residual after each layer, the layer given the reference's
    # input: the largest of the layers' medians. bf16: 0.00350-0.00351;
    # float8: 0.597
    "resid_rel_median": 0.01,
    # (c) the indexer's scores of each full layer, over the causal
    # entries of a row: the largest of the layers' medians (bf16
    # operands, float32 products and sums). bf16: 0.00619-0.00623;
    # float8: 0.103
    "index_rel_median": 0.02,
    # (d) share of the reference's selected pairs that the program
    # selects too, the least of the full layers: random weights put keys
    # at the threshold on rounding. bf16: 0.9979 on every seed; float8:
    # 0.9666
    "select_agree_min": 0.985,
    # (e) each full layer's attention output (gate and W_o included)
    # against the reference's *given the program's selection*: the
    # largest of the layers' medians (bf16: 0.01142-0.01145; float8: 1,
    # outputs of 1e-4-sigma projections lie under float8's smallest
    # number); and the first window layer's over the positions past the
    # window (0.01045-0.01047; 1)
    "sel_attn_rel_median": 0.03,
    "window_attn_rel_median": 0.03,
    # (f) the gate, sigmoid(y W_g): the largest |program - reference|
    # over all layers, heads and tokens (values in (0, 1)). bf16:
    # 0.0048-0.0055; float8: 0.0748
    "gate_abs_max": 0.02,
    # (g) the first expert layer's output over the tokens whose choices
    # agree (bf16: 0.00536-0.00538; float8: 1); the dense layer's SwiGLU
    # (0.00479-0.00480; 1)
    "expert_rel_median": 0.02,
    "dense_mlp_rel_median": 0.02,
    # (h) share of (token, choice) pairs the routers agree on, the least
    # of the expert layers (bf16: 0.9959-0.9962; float8: 0.9384; the
    # reference's own scores in bfloat16: 0.977); and on the program's
    # own input, the router's arithmetic alone (1 on every seed;
    # bfloat16 scores: 0.977)
    "router_agree_min": 0.98,
    "router_same_input_min": 0.999,
    # (i) the attention *backward*: dq, dk, dv of the _sel kernels (first
    # full layer, under the program's selection) and of the _swa kernels
    # (first window layer) against the blocked float32 reference's vjp
    # on the same rounded operands and one seeded cotangent: the 99th
    # percentile over the (token, head) rows, the largest of the three.
    # bf16: 0.00631-0.00637 and 0.00737-0.00763. One reading only
    # (`second_reading` takes no vjp): the limits are
    # smallthinker-ep4-1chip-steady's for the same kernels' plain and
    # window forms, whose float8 readings were 0.178 and 0.087
    "sel_attn_grad_rel_p99": 0.03,
    "window_attn_grad_rel_p99": 0.025,
    # (j) the CE alone (0.00002-0.00013; float8 0.0082: no precision
    # moves a CE at random init, a dropped term or a wrong target does;
    # the harness's accepted cells' limit leaves 76 times of room), and
    # L_I relative to the reference's (0.00389-0.00397: the program's is
    # 0.0028 lower on every seed, its p from bf16 q and k; float8 0.0020,
    # bfloat16 0.00003: no precision moves it either)
    "ce_abs": 0.01,
    "l_i_rel": 0.02,
}


def program_pieces(cfg, mesh, params, tokens, inputs) -> dict:
    """The program's side of ``reference_pieces``; ``inputs[i]`` is the
    reference's residual before layer ``i``."""
    from dlrover_tpu.models import dots3, moe
    from dlrover_tpu.models.llama import _shift_targets
    from dlrover_tpu.models.xing4 import latent_attention
    from dlrover_tpu.ops import cross_entropy_sums, rms_norm, rope_frequencies

    mcfg = cfg.as_moe()
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    @jax.jit
    def whole(params, tokens):
        hidden, l_i = dots3.forward_layers(params, tokens, cfg, mesh)
        nll, n = cross_entropy_sums(
            rms_norm(hidden, params["final_norm"], cfg.norm_eps),
            params["lm_head"], _shift_targets(tokens),
            chunk_size=cfg.ce_chunk_size, mesh=mesh)
        full = max(cfg.layer_kinds.count(dots3.FULL), 1)
        return nll / jnp.maximum(n, 1.0), jnp.sum(l_i) / (
            full * tokens.size), hidden

    def layer(lp, x, kind):
        x = x.astype(cfg.dtype)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        shape = cfg.latent(kind)
        inv_freq = rope_frequencies(shape.qk_rope_dim, shape.rope_theta)
        out = {"gate": jax.nn.sigmoid(y @ lp["w_g"].astype(cfg.dtype))}
        if kind == dots3.FULL:
            attend, aux = dots3.selected_attention(
                cfg, mesh, positions, inv_freq, lp, y)
            out["attn"] = latent_attention(
                shape, mesh, positions, inv_freq, lp, y, attend=attend)
            out["scores"], out["mask"] = aux["scores"], aux["mask"] != 0
        else:
            out["attn"] = latent_attention(
                shape, mesh, positions, inv_freq, lp, y, window=cfg.window)
        x = x + out["attn"]
        u = out["u"] = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if "router" in lp:
            _, _, out["top_e"] = moe.route(
                mcfg, lp["router"], u.reshape(b * s, -1),
                bias=lp.get("router_bias"))
        out["after"] = dots3.feed_forward_half(cfg, mesh, lp, x, u)
        out["ffn"] = out["after"] - x
        return out

    layer = jax.jit(layer, static_argnums=2)
    ce, l_i, hidden = whole(params, tokens)
    return {
        "ce": float(ce), "l_i": float(l_i), "hidden": jax.device_get(hidden),
        "layer": [jax.device_get(layer(
            dots3.layer_params(cfg, params, i), x, cfg.layer_kinds[i]))
            for i, x in enumerate(inputs)]}


def _program_core_grads(mesh, q, k, v, g, mask, window):
    """The three kernels alone, as the layer calls them: under ``mask``
    (a full layer's selection) or, where it is None, under ``window``."""
    from dlrover_tpu.ops.attention import flash_attention

    def grads(q, k, v, g, select):
        return jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, mesh=mesh, window=window, select=select),
            q, k, v)[1](g)

    return jax.jit(grads)(
        q, k, v, g, None if mask is None else jnp.asarray(mask, jnp.int8))


def _chosen(top_e, n_experts: int):
    """``top_e (t, k)`` -> (t, n_experts): 1 where the token chose it."""
    return jax.nn.one_hot(top_e, n_experts, dtype=jnp.int32).sum(1)


def _causal_row_rel(got, want):
    """Row-wise relative error over the causal entries of ``(b, s, s)``."""
    keep = _causal(want.shape[-1])
    return _row_rel(jnp.where(keep, got, 0.0), jnp.where(keep, want, 0.0))


def readings(got: dict, want: dict, config: dict) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's. ``want["given"][i]`` is the reference's layer
    ``i`` under ``got``'s selection, where the caller made one."""
    kinds = kinds_of(config)
    n_experts = config.get(
        "published_n_routed_experts", config["n_routed_experts"])
    k = config["num_experts_per_tok"]
    full = [i for i, kind in enumerate(kinds) if kind == FULL]
    moe_layers = [i for i, r in enumerate(want["layer"]) if "top_e" in r]
    g, w = got["layer"], want["layer"]

    def median(a, b, rows=slice(None)):
        return float(jnp.median(_row_rel(a, b)[rows]))

    agreed = {i: jnp.sum(_chosen(g[i]["top_e"], n_experts)
                         * _chosen(w[i]["top_e"], n_experts), axis=1)
              for i in moe_layers}
    out = {
        "hidden_rel_median": median(got["hidden"], want["hidden"]),
        "resid_rel_median": max(
            median(g[i]["after"], w[i]["after"]) for i in range(len(kinds))),
        "gate_abs_max": max(
            float(jnp.max(jnp.abs(_f32(g[i]["gate"]) - w[i]["gate"])))
            for i in range(len(kinds))),
        "ce_abs": abs(got["ce"] - want["ce"]),
    }
    if want["l_i"]:
        out["l_i_rel"] = abs(got["l_i"] - want["l_i"]) / want["l_i"]
    if full:
        given = want.get("given", {i: w[i] for i in full})
        out["index_rel_median"] = max(
            float(jnp.median(_causal_row_rel(
                g[i]["scores"], w[i]["scores"]))) for i in full)
        out["select_agree_min"] = min(
            float(jnp.sum(g[i]["mask"] & w[i]["mask"])
                  / jnp.sum(w[i]["mask"])) for i in full)
        out["sel_attn_rel_median"] = max(
            median(g[i]["attn"], given[i]["attn"]) for i in full)
    if WINDOW in kinds:
        i = kinds.index(WINDOW)
        b, s = w[i]["attn"].shape[:2]
        past = np.tile(
            np.arange(s) >= min(config["sliding_window_size"], s - 1), b)
        out["window_attn_rel_median"] = median(
            g[i]["attn"], w[i]["attn"], past)
    if moe_layers:
        i = moe_layers[0]
        out["expert_rel_median"] = median(
            g[i]["ffn"], w[i]["ffn"], agreed[i] == k)
        out["router_agree_min"] = min(
            float(jnp.sum(a)) / (a.shape[0] * k) for a in agreed.values())
    dense = [i for i in range(len(kinds)) if i not in moe_layers]
    if dense:
        out["dense_mlp_rel_median"] = median(
            g[dense[0]]["ffn"], w[dense[0]]["ffn"])
    if "top_e_on_u" in want:
        out["router_same_input_min"] = min(
            float(jnp.sum(_chosen(g[i]["top_e"], n_experts)
                          * _chosen(want["top_e_on_u"][i], n_experts)))
            / g[i]["top_e"].size for i in moe_layers)
    for name in ("sel_attn_grad_rel_p99", "window_attn_grad_rel_p99"):
        if name in got:
            out[name] = max(
                float(jnp.percentile(_row_rel(a, b), 99.0))
                for a, b in zip(got[name], want[name]))
    return out


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[dots3] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok), flush=True)
    return all(ok.values())


def _compare(cfg, mesh, params, tokens, config, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each and returns whether all
    hold."""
    from dlrover_tpu.observability import trace

    kinds = kinds_of(config)
    got = program_pieces(cfg, mesh, params, tokens, want["resid"])
    layers = list(layers_of(params, config))
    with jax.default_matmul_precision("highest"):
        # the reference's full layers under the program's selection
        given = jax.jit(lambda x, lp, mask: _ref_block(
            x, _f32(lp), config, FULL, select=mask))
        want = dict(want, given={
            i: jax.device_get(given(
                want["resid"][i], layers[i], got["layer"][i]["mask"]))
            for i, kind in enumerate(kinds) if kind == FULL})
        # the reference's router on the program's own normed input
        route = jax.jit(lambda u, lp: _ref_router(
            _f32(u).reshape(-1, u.shape[-1]), _f32(lp), config)[1])
        want["top_e_on_u"] = {
            i: route(got["layer"][i]["u"], {
                "router": layers[i]["router"],
                "router_bias": layers[i]["router_bias"]})
            for i in range(len(kinds)) if "router" in layers[i]}
    # the backward of the two kinds of kernels, the first layer of each
    # (the program's calls outside the reference's matmul precision: a
    # kernel's bf16 product takes no float32 precision)
    dt = cfg.dtype
    eps = float(config["rms_norm_eps"])
    for name, kind in (("sel_attn_grad_rel_p99", FULL),
                       ("window_attn_grad_rel_p99", WINDOW)):
        if kind not in kinds:
            continue
        i = kinds.index(kind)
        mask = (got["layer"][i]["mask"] if kind == FULL else
                _window_mask(tokens.shape[1],
                             config["sliding_window_size"])[None])
        with jax.default_matmul_precision("highest"):
            operands, grads = jax.jit(
                lambda x, lp, mask, kind=kind: _ref_core_grads(
                    *_ref_qkv(_rms_norm(x, _f32(lp["attn_norm"]), eps),
                              _f32(lp), config, kind)[:3],
                    mask, dt, lambda a: a))(want["resid"][i], layers[i], mask)
        want[name] = jax.device_get(grads)
        del grads
        got[name] = jax.device_get(_program_core_grads(
            mesh, *operands, mask if kind == FULL else None,
            None if kind == FULL else cfg.window))
        del operands
    selected = [int(jnp.sum(r["mask"])) for r in got["layer"] if "mask" in r]
    if selected:
        # counted on the batch, not assumed
        trace.gauge("dsa.selected_pairs", selected[0])
    held = sum(int(np.asarray(jnp.sum(
        (r["top_e"] >= cfg.first_expert)
        & (r["top_e"] < cfg.first_expert + cfg.as_moe().n_held))))
        for r in got["layer"] if "top_e" in r)
    pairs = sum(r["top_e"].size for r in got["layer"] if "top_e" in r)
    return _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}, top-{cfg.index_topk}, "
        f"window {cfg.window}; selected pairs a full layer {selected}; "
        f"{held} of {pairs} pairs chose a held expert; CE "
        f"{got['ce']:.5f} / {want['ce']:.5f}; L_I {got['l_i']:.5f} / "
        f"{want['l_i']:.5f})",
        readings(got, want, config))


def second_reading(config: dict, seed: int, seq: int = 8192) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/finetune_loop.py`` makes from ``seed``; then
    the three places float32 is stated, each alone in bfloat16: the
    indexer's scores, the router's logits. By hand, on the chip::

        python -c "import json
        from benchmarks.families import dots3 as f
        f.second_reading(json.load(open(
            'benchmarks/configs/dots3-note-prev-ep32-1chip.json')), 3)"
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
        got = reference_pieces(params, tokens, config, _round_trip(dtype),
                               inputs=want["resid"])
        passed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed} "
            f"(CE {got['ce']:.5f} / {want['ce']:.5f}; L_I {got['l_i']:.5f}"
            f" / {want['l_i']:.5f})", readings(got, want, config))
    # the indexer's scores and the router's logits alone in bfloat16
    kinds = kinds_of(config)
    bf16 = _round_trip(jnp.bfloat16)
    eps, k = float(config["rms_norm_eps"]), config["num_experts_per_tok"]
    layers = list(layers_of(params, config))
    rounded = {"layer": [dict(r) for r in want["layer"]], **{
        key: want[key] for key in ("hidden", "ce", "l_i")}}
    logits = jax.jit(lambda u, lp: jax.nn.sigmoid(
        u.reshape(-1, u.shape[-1]) @ _f32(lp["router"])))
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(kinds):
            r = rounded["layer"][i]
            if kind == FULL:
                r["scores"] = bf16(r["scores"])
                r["mask"] = _ref_selection(r["scores"], config["index_topk"])
            if "top_e" in r:
                r["top_e"] = jax.lax.top_k(
                    bf16(logits(r["u"], layers[i])), k)[1]
        want_u = dict(want, top_e_on_u={
            i: r["top_e"] for i, r in enumerate(want["layer"])
            if "top_e" in r})
    passed["float32_parts_bfloat16"] = _report(
        f"the reference with its indexer's scores and its router's scores "
        f"rounded to bfloat16 against float32, seed {seed}",
        readings(rounded, want_u, config))
    return passed
