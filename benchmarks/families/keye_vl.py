"""The keye_vl family (Keye-VL-2.0-30B-A3B, language model), as
``dlrover_tpu.models.keye_vl`` computes it and as this file's plain
reference computes it again.

Layer equations, from the model's config.json and what ``assumed`` in the
configuration states (hidden ``d`` 2048; no bias anywhere but the
indexer's LayerNorm; untied head; all layers alike); a token ``t`` has
three positions ``p_0[t], p_1[t], p_2[t]`` (time, height, width)::

    x_0 = E[tokens];  y = RMSNorm(x; attn_norm), eps 1e-6
    q_h = RMSNorm_128(y W_q)_h  (32 heads of 128),  k_g = RMSNorm_128(y W_k)_g,
    v_g = (y W_v)_g  (4 heads of 128); one norm weight of 128 for all heads
    rotary on the whole head, halves against halves: pair i of 64 (channels
      i and i + 64) turns by p_c(i)[t] theta^(-2i/128); c(i) = 0 for i < 16,
      1 for 16 <= i < 40, 2 for i >= 40 (mrope_section [16, 24, 24])
    indexer, on sg(y):
      qI_j = y W_Iq,j  (16 heads of 64);  kI = LayerNorm(y W_Ik)  (one key)
      the same rotary on all 64 channels of each: pair i of 32 by
      p_c'(i)[t] theta^(-2i/64), sections [8, 12, 12]
      w = y W_Iw / sqrt(16 * 64)
      I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])               float32
    S_t  = the topk keys s <= t of largest I[t, s] (ties to the lower s;
           all of them while t < topk)
    o_h  = softmax_{s in S_t}(q_h . k_(h // 8) / sqrt(128)) v_(h // 8)
    h    = x + concat_h(o_h) W_o
    L_I  = mean_t KL(p^_t || softmax_{s in S_t} I[t, s]),
           p[t, s] = sum_h P[t, h, s], p^ = p / sum_s p, a constant
    u    = RMSNorm(h; mlp_norm);  r = softmax(u W_r) over 128, float32
    the 8 largest r, w_j = r_j / sum of the 8
    x'   = h + sum_j w_j SwiGLU_{e_j}(u)          experts of 768

Final RMSNorm, the head, ``loss = CE + mean over layers of L_I``. This
chip holds experts ``first_expert ..`` of ``published_num_experts`` and
ids ``0 ..`` of ``published_vocab_size``: a pair that chose an absent
expert adds nothing. The positions are the fixed layout
``assumed.positions`` states (`positions_for`): text runs and image spans
by the Qwen2-VL rule, the same for the program and for the reference.

The reference is float32 at matmul precision "highest", ``jax.numpy``
alone, and shares no code with the program: its own rotary from the
three rows, the indexer and attention by explicit scores **in blocks of
256 query rows**, the selection by a stable sort, its own KL, the expert
layer a loop over the held experts, the cross-entropy in blocks of rows.
It imports nothing of ``dlrover_tpu``; what every reference shares
(norm, casts, the row-wise relative error, the blocked cross-entropy,
the KL of a masked row) is the other families' files'.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.dots3 import (
    _causal, _causal_row_rel, _chosen, _ref_indexer_loss)
from benchmarks.families.smallthinker import _ref_ce, _round_trip
from benchmarks.families.xing4 import (
    _f32, _rms_norm, _row_rel, _shifted, _swiglu)
from benchmarks.harness import keye_vl_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

Q_BLOCK = 256      # queries a block of the reference's attention and indexer


def positions_for(config: dict, batch: int, seq: int) -> np.ndarray:
    """``assumed.positions``' layout as ``(3, batch, seq)`` int32:
    ``segments`` times (text, then an image of ``grid`` merged patches);
    a text run counts up on all three rows from one past the largest
    position so far, an image starting at ``p`` has row 0 at ``p``, row 1
    at ``p`` + its patch's row, row 2 at ``p`` + its patch's column, and
    what follows starts at ``p + max(grid)``."""
    layout = config["assumed"]["positions"]
    segments, (gh, gw) = int(layout["segments"]), layout["grid"]
    text = seq // segments - gh * gw
    if seq % segments or text <= 0:
        raise ValueError(
            f"{seq} positions are not {segments} segments of text and an "
            f"image of {gh} x {gw}")
    rows, start = [], 0
    for _ in range(segments):
        run = start + np.arange(text)
        rows.append(np.stack([run, run, run]))
        p = start + text
        patch = np.arange(gh * gw)
        rows.append(np.stack(
            [np.full(gh * gw, p), p + patch // gw, p + patch % gw]))
        start = p + max(gh, gw)
    one = np.concatenate(rows, axis=1).astype(np.int32)       # (3, seq)
    return np.ascontiguousarray(
        np.broadcast_to(one[:, None, :], (3, batch, seq)))


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import keye_vl
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/keye_vl.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/keye_vl.py initialises with sigma 0.02 only")
    # the file's expert count is the held one; the program's config keeps
    # the published beside it
    cfg = keye_vl.KeyeVLConfig.from_hf(
        dict(config, num_experts=config.get(
            "published_num_experts", config["num_experts"])),
        experts_held=config["num_experts"],
        first_expert=int(config.get("first_expert", 0)),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = keye_vl.param_specs(cfg)
    # assumed.out_proj_std: the sigma of the projections that close a
    # residual branch, where the configuration states one
    out_scale = (float(assumed["out_proj_std"]) / std
                 if "out_proj_std" in assumed else None)

    def init_params(key):
        params = keye_vl.init_params(cfg, key)
        if out_scale is None:
            return params
        return dict(params, layers={
            name: (w * out_scale).astype(w.dtype)
            if name in ("wo", "w_down") else w
            for name, w in params["layers"].items()})

    init = jax.jit(init_params, out_shardings=named_shardings(mesh, specs))

    def positions(tokens):
        return positions_for(config, *tokens.shape)

    def reference(params, tokens):
        return compare(cfg, mesh, params, tokens, config)

    vocab, dim = config["vocab_size"], config["hidden_size"]
    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(lambda p, t: keye_vl.live_rows(
            p, t, cfg, mesh, positions(t))),
        loss_fn=lambda p, t: keye_vl.loss_fn(p, t, cfg, mesh, positions(t)),
        param_count=keye_vl.param_count(cfg),
        flops_per_token=lambda seq: keye_vl_flops.flops_per_token(config, seq),
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

def _sections(config: dict, width: int):
    """``mrope_section`` for a head of ``width``: as published at the main
    head's, in proportion at the indexer's."""
    return [n * width // config["head_dim"]
            for n in config["rope_scaling"]["mrope_section"]]


def _rotary3(x, positions, theta: float, sections, angle_dtype=jnp.float32):
    """``x (b, s, heads, d)`` turned by the three rows ``positions (3, b,
    s)``: pair ``i`` (channels ``i`` and ``i + d / 2``) by the row whose
    section holds it. ``angle_dtype``: what the angles are formed in
    (``second_reading``)."""
    d = x.shape[-1]
    row_of_pair = np.repeat(np.arange(len(sections)), sections)   # (d / 2,)
    inv_freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    p = jnp.moveaxis(jnp.asarray(positions)[row_of_pair], 0, -1)  # (b, s, d/2)
    angles = (p.astype(angle_dtype)
              * jnp.asarray(inv_freq, angle_dtype)).astype(jnp.float32)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_qkv(y, lp, config, positions, angle_dtype=jnp.float32):
    """``y (b, s, d)`` pre-normed -> ``q (b, s, h, hd)``, ``k, v (b, s,
    hkv, hd)``, q and k through the norm a head and rotary."""
    b, s, _ = y.shape
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], float(config["rms_norm_eps"])
    theta, sec = float(config["rope_theta"]), _sections(config, hd)
    q = _rms_norm((y @ lp["wq"]).reshape(b, s, h, hd), lp["q_norm"], eps)
    k = _rms_norm((y @ lp["wk"]).reshape(b, s, hkv, hd), lp["k_norm"], eps)
    v = (y @ lp["wv"]).reshape(b, s, hkv, hd)
    return (_rotary3(q, positions, theta, sec, angle_dtype),
            _rotary3(k, positions, theta, sec, angle_dtype), v)


def _blocks(x, block: int):
    """``(b, s, ...)`` -> ``(s / block, b, block, ...)``."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // block, block, *x.shape[2:]), 1, 0)


def _unblocks(x):
    """`_blocks`' inverse on a ``lax.map``'s result."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _ref_index_inputs(y, lp, config, positions, cast=lambda a: a):
    """The indexer's ``(q (b, s, hi, di), k (b, s, di), w (b, s, hi))``
    from the layer's normed input."""
    b, s, _ = y.shape
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    sec = _sections(config, di)
    q = (y @ lp["idx_wq"]).reshape(b, s, hi, di)
    k = y @ lp["idx_wk"]
    k = (k - jnp.mean(k, -1, keepdims=True)) * jax.lax.rsqrt(
        jnp.var(k, -1, keepdims=True) + eps)
    k = (k * lp["idx_k_norm"] + lp["idx_k_bias"])[:, :, None, :]
    return (cast(_rotary3(q, positions, theta, sec)),
            cast(_rotary3(k, positions, theta, sec)[:, :, 0]),
            cast((y @ lp["idx_ww"]) * (hi ** -0.5 * di ** -0.5)))


def _ref_index_scores(q, k, w, accumulate=None):
    """``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])`` ``(b, s, s)``, a
    block of query rows at a time, recomputed in a backward pass.
    ``accumulate``: a rounding applied to every partial sum, of a dot
    product's 8-channel chunks and of the heads (``second_reading``)."""
    s, hi, di = q.shape[1:]
    block = Q_BLOCK if s % Q_BLOCK == 0 else s

    @jax.checkpoint
    def one(args):
        qb, wb = args
        if accumulate is None:
            dots = jnp.einsum("bqhd,bkd->bqhk", qb, k)
            return jnp.sum(wb[..., None] * jax.nn.relu(dots), axis=2)
        dots = 0.0
        for c in range(0, di, 8):
            dots = accumulate(dots + jnp.einsum(
                "bqhd,bkd->bqhk", qb[..., c:c + 8], k[..., c:c + 8]))
        total = 0.0
        for j in range(hi):
            total = accumulate(
                total + wb[:, :, j, None] * jax.nn.relu(dots[:, :, j]))
        return total

    return _unblocks(jax.lax.map(one, (_blocks(q, block), _blocks(w, block))))


def _ref_selection(scores, topk: int):
    """``(b, s, s)`` bool: the ``topk`` causal keys of largest score a
    row, by a stable descending sort (ties to the lower ``s``), all while
    ``t < topk``."""
    b, s, _ = scores.shape
    causal = _causal(s)
    if topk >= s:
        return jnp.broadcast_to(causal, scores.shape)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s

    def one(rows):                                       # (b, block, s)
        order = jnp.argsort(-rows, axis=-1, stable=True)[..., :topk]
        return jnp.zeros(rows.shape, bool).at[
            jnp.arange(b)[:, None, None],
            jnp.arange(block)[None, :, None], order].set(True)

    neg = jnp.where(causal, scores, -jnp.inf)
    return _unblocks(jax.lax.map(one, _blocks(neg, block))) & causal


def _ref_masked_attention(q, k, v, mask, scale: float):
    """softmax over the keys ``mask (b, s, s)`` names, query head ``j`` on
    key head ``j // group`` -> ``(out (b, s, h, hd), the probabilities
    summed over the heads (b, s, s))``: explicit scores over all the
    keys, a block of queries at a time, recomputed in a backward pass."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    block = Q_BLOCK if s % Q_BLOCK == 0 else s

    @jax.checkpoint
    def one(args):
        qb, mb = args             # (b, block, hkv, g, hd), (b, block, s)
        scores = jnp.einsum("bqngd,bknd->bngqk", qb, k) * scale
        p = jax.nn.softmax(
            jnp.where(mb[:, None, None], scores, -jnp.inf), -1)
        return (jnp.einsum("bngqk,bknd->bqngd", p, v),
                jnp.sum(p, axis=(1, 2)))

    out, p = jax.lax.map(one, (
        _blocks(q.reshape(b, s, hkv, h // hkv, hd), block),
        _blocks(jnp.broadcast_to(mask, (b, s, s)), block)))
    return _unblocks(out).reshape(b, s, h, hd), _unblocks(p)


def _ref_router(ut, lp, config):
    """``ut (t, d)`` -> per-expert weight ``(t, E)`` over all the experts
    the router scores (a token's weight for the 8 it chose, renormalised;
    0 for the others) and the chosen experts ``(t, k)``."""
    probs = jax.nn.softmax(ut @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    weights = jnp.zeros_like(probs).at[
        jnp.arange(ut.shape[0])[:, None], top_e].set(top_p)
    return weights, top_e


def _ref_expert_layer(u, lp, config):
    """The held experts, each on all the tokens, weighted by the router's
    choice: ``(out (b, s, d), top_e (t, k))``."""
    b, s, d = u.shape
    ut = u.reshape(b * s, d)
    weights, top_e = _ref_router(ut, lp, config)
    first = int(config.get("first_expert", 0))
    out = jnp.zeros_like(ut)
    for e in range(lp["w_gate"].shape[0]):
        out = out + weights[:, first + e, None] * _swiglu(
            ut, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return out.reshape(b, s, d), top_e


def _ref_block(x, lp, config, positions, cast=lambda a: a, select=None,
               angle_dtype=jnp.float32, accumulate=None):
    """One layer -> dict: ``after`` the residual after it, ``attn`` the
    attention sublayer's output, ``q`` after rotary, ``index_inputs`` the
    indexer's q, k, w and ``scores`` of them, ``mask`` (its own
    selection, or ``select`` where given: the program's),
    ``l_i``, ``u`` the expert layer's normed input, ``ffn`` its output,
    ``top_e`` the router's choices. ``cast`` rounds the weights and each
    sublayer's input and output (``second_reading``)."""
    b, s, _ = x.shape
    eps = float(config["rms_norm_eps"])
    sg = jax.lax.stop_gradient
    lp = jax.tree.map(cast, lp)
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    q, k, v = _ref_qkv(y, lp, config, positions, angle_dtype)
    out = {"q": q, "index_inputs": _ref_index_inputs(
        sg(y), lp, config, positions, cast)}
    out["scores"] = _ref_index_scores(*out["index_inputs"], accumulate)
    mask = out["mask"] = (
        _ref_selection(sg(out["scores"]), config["sa_config"]["topk"])
        if select is None else select)
    o, p = _ref_masked_attention(q, k, v, mask, q.shape[-1] ** -0.5)
    out["l_i"] = _ref_indexer_loss(out["scores"], sg(p), mask)
    out["attn"] = cast(o.reshape(b, s, -1) @ lp["wo"])
    x = x + out["attn"]
    u = out["u"] = cast(_rms_norm(x, lp["mlp_norm"], eps))
    ffn, out["top_e"] = _ref_expert_layer(u, lp, config)
    out["ffn"] = cast(ffn)
    out["after"] = x + out["ffn"]
    return out


def layers_of(params):
    """The layers' parameter trees, first to last (the program stacks them
    on a leading axis)."""
    for row in range(jax.tree.leaves(params["layers"])[0].shape[0]):
        yield jax.tree.map(lambda a: a[row], params["layers"])


def plain_loss(params, tokens, config: dict, positions=None):
    """``(CE, L_I)`` of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as it
    stands (the selection and ``p`` are constants)."""
    if positions is None:
        positions = positions_for(config, *tokens.shape)
    x = params["embed"][tokens]
    l_i = 0.0
    for lp in layers_of(params):
        out = _ref_block(x, lp, config, positions)
        x, l_i = out["after"], l_i + out["l_i"]
    ce = _ref_ce(x, params["final_norm"], params["lm_head"],
                 _shifted(tokens, 1), float(config["rms_norm_eps"]))
    return ce, l_i / config["num_hidden_layers"]


def _ref_grad_operands(x, lp, mask, config: dict, positions, dt):
    """What both sides' backward passes read, of one layer on its input
    ``x``: the reference's q, k, v and the indexer's q, k rounded to the
    activation dtype ``dt`` and its w (float32 on both sides), a seeded
    cotangent ``g`` of the attention's output, and ``p``, the
    head-summed probabilities of those q and k under ``mask`` (the
    program's selection): L_I's constant target. Returns ``((q, k, v, g,
    iq, ik, iw), p)``."""
    lp = _f32(lp)
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    q, k, v = (a.astype(dt) for a in _ref_qkv(y, lp, config, positions))
    iq, ik, iw = _ref_index_inputs(y, lp, config, positions)
    g = jax.random.normal(jax.random.key(0), q.shape, jnp.float32).astype(dt)
    p = _ref_masked_attention(*_f32((q, k, v)), mask, q.shape[-1] ** -0.5)[1]
    return (q, k, v, g, iq.astype(dt), ik.astype(dt), iw), p


def _ref_grads(q, k, v, g, iq, ik, iw, p, mask, cast=lambda a: a):
    """What holds the *backward* passes to the definition, in float32:
    dq, dk, dv of `_ref_masked_attention` under ``mask`` for the
    cotangent ``g``, and the gradient of L_I (`_ref_indexer_loss` of
    `_ref_index_scores`, target ``p``) in the indexer's q, k, w.
    ``cast`` rounds the seven operands (``second_reading``): the results
    stay float32, so a reading is the operands' precision and no
    underflow of a narrow result."""
    q, k, v, g, iq, ik, iw = (cast(_f32(a)) for a in (q, k, v, g, iq, ik, iw))
    _, vjp = jax.vjp(
        lambda q, k, v: _ref_masked_attention(
            q, k, v, mask, q.shape[-1] ** -0.5)[0], q, k, v)
    d_index = jax.grad(
        lambda iq, ik, iw: _ref_indexer_loss(
            _ref_index_scores(iq, ik, iw), p, mask),
        argnums=(0, 1, 2))(iq, ik, iw)
    return tuple(vjp(g)) + tuple(d_index)


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
# Except for (a), each piece is the program's layer on the *reference's*
# input to that layer (rounded to the activation dtype), so that a
# reading is one layer's error and not the chain's. A layer's pieces are
# compared on the device and only the numbers leave it: at 16384
# positions a layer's scores are 1 GiB a side.
#
# Each limit lies between two readings on the chip at the published
# widths and 16384 positions (my chip runs, PR 54; PERF.md section 6):
# the largest the bf16 program gave against the float32 reference over
# its seeds, and what `second_reading` gave on seeds 2147480033, ..34,
# 2147482002 and ..03: the reference with its weights and each
# sublayer's input and output rounded to float8_e4m3fn, the nearest
# precision below the bfloat16 the configuration states (it fails
# twelve of the thirteen it reads; rounded to bfloat16 the same way it
# passes them all); the indexer's scores accumulated in bfloat16 where
# float32 is stated (fails (c)'s second); the rotary angles formed in
# bfloat16 (fails (e) and (h)).
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward: median over the tokens of |program - reference| /
    # |reference| along the row. bf16: 0.00460-0.00461 on every seed;
    # float8, the rounded reference's own chain from its rounded table:
    # 0.0457-0.0458 (angles formed in bfloat16: 0.0119)
    "hidden_rel_median": 0.015,
    # (b) the residual after each of the four layers, the layer given the
    # reference's input: the largest of the layers' medians. bf16:
    # 0.00290; float8: 0.0154
    "resid_rel_median": 0.01,
    # (c) the indexer's scores, over the causal entries of a row: the
    # largest of the layers' medians (bf16 operands, float32 products
    # and sums; bf16: 0.00536-0.00540; float8: 0.0935); and against the
    # definition in float32 **on the side's own q, k, w**, the indexer's
    # arithmetic alone, where the operands' rounding cancels: sums kept
    # in bfloat16 read the first like bf16 operands do (0.00529) and the
    # second tells them apart (the program 0; sums in bfloat16 0.00529)
    "index_rel_median": 0.02,
    "index_same_input_rel_median": 5e-4,
    # (d) share of the reference's selected pairs that the program
    # selects too, the least of the layers: random weights put keys at the
    # threshold on rounding. bf16: 0.9973 on every seed (the reference
    # rounded to bfloat16, another rounding of the same sums: 0.9978);
    # float8: 0.9546
    "select_agree_min": 0.985,
    # (e) each layer's attention output (W_o included) against the
    # reference's *given the program's selection*: the largest of the
    # layers' medians. bf16: 0.00532-0.00535; float8: 1 (outputs of
    # 1e-4-sigma projections lie under float8's smallest number); rotary
    # angles formed in bfloat16: 0.448
    "sel_attn_rel_median": 0.03,
    # (f) L_I a layer, relative to the reference's: the largest. bf16:
    # 1.1e-5-6.0e-5; float8: 0.0025-0.0029
    "l_i_rel": 7.5e-4,
    # (g) the expert layer's output over the tokens whose eight choices
    # agree and hold a held expert, the largest of the layers' medians
    # (bf16: 0.00614-0.00616; float8: 1); the share of (token, choice)
    # pairs the routers agree on, the least of the layers (0.9964-0.9968;
    # float8: 0.9547), and on the program's own input, the router's
    # arithmetic alone (1; float8: 0.966)
    "expert_rel_median": 0.02,
    "router_agree_min": 0.98,
    "router_same_input_min": 0.999,
    # (h) q after the norm a head and rotary, at the image spans (the
    # tokens whose three rows differ): the 99th percentile over their
    # (token, head) rows, the largest of the layers. bf16: 0.00395-0.00396;
    # float8: 0.0521; rotary angles formed in bfloat16 (at position 12415
    # a bfloat16 holds multiples of 64): 0.838
    "image_rope_rel_p99": 0.02,
    # (i) the CE alone (7.6e-6-4.9e-5: no precision moves a CE at random
    # init, a dropped term or a wrong target does; the harness's accepted
    # cells' limit)
    "ce_abs": 0.01,
    # (j) the *backward* passes on the first layer's operands, both sides
    # reading the same q, k, v and indexer's q, k rounded to bfloat16,
    # the program's selection and the reference's p: dq, dk, dv of the
    # `_sel` kernels at group 8 for one seeded cotangent, and the
    # gradient of L_I in the indexer's q, k, w through `indexer_loss` and
    # the one `dsa_index_bwd` kernel at 64-wide heads, against the
    # blocked float32 reference's: the 99th percentile over a gradient's
    # rows, the largest of each three. bf16 kernels, eight seeds:
    # 0.00284-0.00285 and 0.00271-0.00273; the reference on the seven
    # operands rounded to float8 (its results left in float32, so that
    # no reading is a narrow result's underflow): 0.0780-0.0781 and 1
    # (L_I's gradient is softmax(I) - p^ over the selection, a
    # difference of two nearly uniform rows that a 9 % error of the
    # scores turns); with w alone rounded to bfloat16 the indexer's
    # reads 0.0124-0.0125
    "sel_attn_grad_rel_p99": 0.015,
    "index_grad_rel_p99": 0.05,
}


def _layer_readings(got: dict, want: dict, given_attn, top_e_on_u,
                    image, config: dict) -> dict:
    """The numbers ``LIMITS`` bounds of one layer: ``got`` one side's
    pieces, ``want`` the float32 reference's, ``given_attn`` the
    reference's attention output under ``got``'s selection,
    ``top_e_on_u`` the reference's router on ``got``'s normed input,
    ``image (b s,)`` bool the tokens whose rows differ."""
    n_experts = config.get("published_num_experts", config["num_experts"])
    k = config["num_experts_per_tok"]
    first, held = int(config.get("first_expert", 0)), config["num_experts"]

    def median(a, b, rows=None):
        rel = _row_rel(a, b)
        if rows is None:
            return jnp.median(rel)
        return jnp.nanmedian(jnp.where(rows, rel, jnp.nan))

    chose_got = _chosen(got["top_e"], n_experts)
    agreed = jnp.sum(chose_got * _chosen(want["top_e"], n_experts), axis=1)
    holds = jnp.any((want["top_e"] >= first) & (want["top_e"] < first + held),
                    axis=1)
    heads = got["q"].shape[2]
    q_rel = _row_rel(got["q"], want["q"])              # (b s heads,)
    return {
        "resid_rel_median": median(got["after"], want["after"]),
        "index_rel_median": jnp.median(
            _causal_row_rel(got["scores"], want["scores"])),
        "index_same_input_rel_median": jnp.median(_causal_row_rel(
            got["scores"], _ref_index_scores(*_f32(got["index_inputs"])))),
        "select_agree_min": (jnp.sum(got["mask"] & want["mask"])
                             / jnp.sum(want["mask"])),
        "sel_attn_rel_median": median(got["attn"], given_attn),
        "l_i_rel": jnp.abs(got["l_i"] - want["l_i"]) / want["l_i"],
        "expert_rel_median": median(
            got["ffn"], want["ffn"], (agreed == k) & holds),
        "router_agree_min": jnp.sum(agreed) / (agreed.shape[0] * k),
        "router_same_input_min": jnp.sum(
            chose_got * _chosen(top_e_on_u, n_experts)) / got["top_e"].size,
        "image_rope_rel_p99": jnp.nanpercentile(
            jnp.where(jnp.repeat(image, heads), q_rel, jnp.nan), 99.0),
        "selected_pairs": jnp.sum(got["mask"]),
        "held_pairs": jnp.sum(
            (got["top_e"] >= first) & (got["top_e"] < first + held)),
    }


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[keye_vl] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok), flush=True)
    return all(ok.values())


def _reference_fns(config: dict, positions):
    """The reference's jitted pieces, float32 (the callers set the matmul
    precision around their calls)."""
    block = jax.jit(lambda x, lp: _ref_block(x, _f32(lp), config, positions))
    given = jax.jit(lambda x, lp, mask: _ref_block(
        x, _f32(lp), config, positions, select=mask)["attn"])
    route = jax.jit(lambda u, lp: _ref_router(
        _f32(u).reshape(-1, u.shape[-1]), {"router": _f32(lp["router"])},
        config)[1])
    compare_layer = jax.jit(lambda got, want, attn, top_e, image:
                            _layer_readings(got, want, attn, top_e, image,
                                            config))
    return block, given, route, compare_layer


def _grad_readings(got, want) -> dict:
    """``got``, ``want``: `_ref_grads`' six. The 99th percentile over a
    gradient's rows of the row-wise relative error, the largest of the
    attention's three and of the indexer's three."""
    worst = [jnp.percentile(_row_rel(a, b), 99.0) for a, b in zip(got, want)]
    return {"sel_attn_grad_rel_p99": jnp.max(jnp.stack(worst[:3])),
            "index_grad_rel_p99": jnp.max(jnp.stack(worst[3:]))}


def _walk(params, tokens, config: dict, positions, side,
          side_grads=None) -> dict:
    """The reference's chain over the layers and, a layer at a time on the
    device, the readings of ``side(lp, x) -> pieces`` (the program's
    layer, or a rounded reference's) on the reference's input ``x``
    against the reference's own; of the first layer also the backward
    passes, ``side_grads(*operands, p, mask) -> six gradients`` against
    `_ref_grads` on `_ref_grad_operands` under the side's selection.
    Returns the layers' worst readings, and ``ce``, ``l_i``, ``hidden``
    of the reference."""
    eps = float(config["rms_norm_eps"])
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    block, given, route, compare_layer = _reference_fns(config, positions)
    operands_of = jax.jit(lambda x, lp, mask: _ref_grad_operands(
        x, lp, mask, config, positions, dt))
    ref_grads, compare_grads = jax.jit(_ref_grads), jax.jit(_grad_readings)
    image = jnp.asarray(np.any(positions != positions[:1], axis=0).reshape(-1))
    per_layer, l_i = [], 0.0
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda table, t: _f32(table)[t])(params["embed"], tokens)
    for lp in layers_of(params):
        with jax.default_matmul_precision("highest"):
            want = block(x, lp)
        # the program's calls outside the reference's matmul precision: a
        # kernel's bf16 product takes no float32 precision
        got = side(lp, x)
        with jax.default_matmul_precision("highest"):
            read = compare_layer(
                got, want, given(x, lp, got["mask"]), route(got["u"], lp),
                image)
        per_layer.append({k: float(v) for k, v in read.items()})
        l_i += float(want["l_i"])
        after, mask = want["after"], got["mask"]
        del want, got
        if side_grads is not None and len(per_layer) == 1:
            with jax.default_matmul_precision("highest"):
                operands, p = operands_of(x, lp, mask)
                wanted = ref_grads(*operands, p, mask)
            grads = side_grads(*operands, p, mask)
            per_layer[0].update({k: float(v) for k, v in compare_grads(
                grads, wanted).items()})
            del operands, p, wanted, grads
        x = after
        del mask
    with jax.default_matmul_precision("highest"):
        ce = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, _f32(norm), _f32(w), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
    # a reading's worst over the layers
    out = {name: (min if name.endswith("_min") else max)(
        r[name] for r in per_layer if name in r)
        for name in per_layer[0] if name in LIMITS}
    return dict(out, per_layer=per_layer, ce=float(ce),
                l_i=l_i / len(per_layer), hidden=x)


def program_fns(cfg, mesh, positions):
    """The program's side: ``(whole(params, tokens) -> (CE, L_I, hidden),
    layer(lp, x) -> pieces, grads(*operands, p, mask) -> the six
    gradients of `_ref_grads` from the layer's own calls)``."""
    from dlrover_tpu.models import keye_vl, moe
    from dlrover_tpu.ops import dsa, rms_norm
    from dlrover_tpu.ops.attention import flash_attention

    @jax.jit
    def whole(params, tokens):
        hidden, l_i = keye_vl.forward_layers(
            params, tokens, cfg, mesh, positions)
        x = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
        ce = keye_vl.stack.next_token_loss(
            x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
        return ce, jnp.sum(l_i) / (cfg.n_layers * tokens.size), hidden

    @jax.jit
    def layer(lp, x):
        b, s, _ = x.shape
        x = x.astype(cfg.dtype)
        tables = keye_vl.rotary_tables(cfg, positions)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        pieces = keye_vl.projections(cfg, tables, lp, y)
        out = {"q": pieces[0], "index_inputs": pieces[3:]}
        out["attn"], l_i, mask, out["scores"] = keye_vl.attention(
            cfg, mesh, tables, lp, y)
        out["mask"], out["l_i"] = mask != 0, l_i / (b * s)
        x = x + out["attn"]
        u = out["u"] = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        _, _, out["top_e"] = moe.route(
            cfg.as_moe(), lp["router"], u.reshape(b * s, -1))
        out["after"] = keye_vl.expert_half(cfg, mesh, lp, x, u)
        out["ffn"] = out["after"] - x
        return out

    @jax.jit
    def grads(q, k, v, g, iq, ik, iw, p, mask):
        # the kernels' backward passes alone, as `dsa.selected_attention`
        # calls them: the `_sel` pair at the family's group, and L_I's
        # path through `indexer_loss` and the three index kernels
        select = mask.astype(jnp.int8)
        d_attn = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, mesh=mesh, scale=cfg.softmax_scale,
            select=select), q, k, v)[1](g)
        d_index = jax.grad(lambda iq, ik, iw: dsa.indexer_loss(
            dsa.index_scores(iq, ik, iw, mesh=mesh), p, select)
            / (mask.shape[0] * mask.shape[1]), argnums=(0, 1, 2))(iq, ik, iw)
        return tuple(d_attn) + tuple(d_index)

    return whole, layer, grads


def compare(cfg, mesh, params, tokens, config: dict) -> float:
    """The comparisons of ``LIMITS`` on ``tokens``: logs each and returns
    the reference's loss, NaN unless all hold."""
    from dlrover_tpu.observability import trace

    positions = positions_for(config, *tokens.shape)
    whole, layer, grads = program_fns(cfg, mesh, positions)
    read = _walk(params, tokens, config, positions, layer, grads)
    ce, l_i, hidden = whole(params, tokens)
    read["hidden_rel_median"] = float(
        jnp.median(_row_rel(hidden, read.pop("hidden"))))
    read["ce_abs"] = abs(float(ce) - read["ce"])
    layers = read.pop("per_layer")
    # counted on the batch, not assumed
    trace.gauge("attn.selected_pairs", layers[0]["selected_pairs"])
    ok = _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, {len(layers)} layers, top-{cfg.index_topk}, largest "
        f"position {int(positions.max())}; selected pairs a layer "
        f"{[int(r['selected_pairs']) for r in layers]}; pairs that chose a "
        f"held expert {[int(r['held_pairs']) for r in layers]} of "
        f"{tokens.size * cfg.experts_per_token}; L_I a layer, reference "
        f"{read['l_i']:.5f} in the mean, program's whole forward "
        f"{float(l_i):.5f}; CE {float(ce):.5f} / {read['ce']:.5f})", read)
    for i, r in enumerate(layers):
        print(f"[keye_vl] layer {i}: " + " ".join(
            f"{name}={value:.4g}" for name, value in r.items()), flush=True)
    return read["ce"] + read["l_i"] if ok else float("nan")


def reference_loss(params, tokens, config: dict) -> float:
    with jax.default_matmul_precision("highest"):
        ce, l_i = jax.jit(lambda p, t: plain_loss(_f32(p), t, config))(
            params, tokens)
    return float(ce) + float(l_i)


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading, each against the reference in float32
    on the batch and the weights ``jobs/finetune_loop.py`` makes from
    ``seed``: the reference with its weights and each sublayer's input
    and output rounded to ``float8_e4m3fn`` (which has to fail at least
    one limit) and to ``bfloat16`` (which has to pass them all); the
    indexer's scores **accumulated in bfloat16** (every partial sum of a
    dot product's 8-channel chunks and of the heads rounded), where
    float32 is stated; the rotary angles **formed in bfloat16** (at
    position 12415 a bfloat16 holds no odd number). Every side reads
    ``hidden_rel_median`` from its own chain over the layers, and the
    two rounded ones the backward pieces, `_ref_grads` on the rounded
    operands. By hand, on the chip::

        python -c "import json
        from benchmarks.families import keye_vl as f
        f.second_reading(json.load(open(
            'benchmarks/configs/keye-vl-2.0-30b-a3b-ep8-1chip.json')), 3)"
    """
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam = build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    positions = positions_for(config, *tokens.shape)
    bf16 = _round_trip(jnp.bfloat16)
    variants = {
        "float8_e4m3fn": dict(cast=_round_trip(jnp.float8_e4m3fn)),
        "bfloat16": dict(cast=bf16),
        "scores_accumulated_in_bfloat16": dict(accumulate=bf16),
        "angles_in_bfloat16": dict(angle_dtype=jnp.bfloat16),
    }
    passed = {}
    for name, how in variants.items():
        cast = how.get("cast", lambda a: a)
        rounded = jax.jit(lambda lp, x, how=how: _ref_block(
            x, _f32(lp), config, positions, **how))
        rounded_grads = jax.jit(lambda *a, cast=cast: _ref_grads(*a, cast))

        def side(lp, x, rounded=rounded):
            with jax.default_matmul_precision("highest"):
                return rounded(lp, x)

        def side_grads(*a, rounded_grads=rounded_grads):
            with jax.default_matmul_precision("highest"):
                return rounded_grads(*a)

        read = _walk(params, tokens, config, positions, side,
                     side_grads if "cast" in how else None)
        # the side's own chain, as `compare` reads the program's
        with jax.default_matmul_precision("highest"):
            x = jax.jit(lambda table, t: cast(_f32(table))[t])(
                params["embed"], tokens)
        for lp in layers_of(params):
            x = side(lp, x)["after"]
        read["hidden_rel_median"] = float(
            jnp.median(_row_rel(x, read.pop("hidden"))))
        for key in ("per_layer", "ce", "l_i"):
            read.pop(key)
        passed[name] = _report(
            f"reference with {name} against float32, seed {seed}", read)
    return passed
