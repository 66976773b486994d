"""The smallthinker family (SmallThinker-21BA3B-Instruct), as
``dlrover_tpu.models.smallthinker`` computes it and as this file's plain
reference computes it again.

Layer equations, from the model's config.json (hidden ``d``; no bias
anywhere; untied head); layer ``l`` has ``r_l = rope_layout[l]`` and
``w_l = sliding_window_layout[l]``::

    y  = RMSNorm(x; attn_norm)
    z  = y W_r                    float32: the router reads the
                                  attention's input
    (z_1..z_k, e_1..e_k) = the k largest z and their experts
    p  = softmax(z_1..z_k)        moe_primary_router_apply_softmax and
                                  norm_topk_prob: the softmax over all the
                                  experts renormalised over the chosen k
    q  = y W_q -> heads of head_dim;  k, v = y W_k, y W_v -> kv heads
    if r_l: rotary (theta, no scaling, a head's first half against its
            second) on q and k
    a  = softmax(q k^T / sqrt(head_dim)) v, causal; if w_l: query i sees
         key j iff 0 <= i - j < sliding_window_size
    x  = x + a W_o
    u  = RMSNorm(x; mlp_norm)
    x  = x + sum_j p_j W_down_{e_j} (relu(W_gate_{e_j} u) * (W_up_{e_j} u))

Final RMSNorm, the head, mean next-token cross-entropy. This chip holds
experts ``first_expert .. + moe_num_primary_experts - 1`` of
``published_moe_num_primary_experts``: a pair that chose another adds
nothing. What config.json does not say is under ``assumed`` in the
configuration.

The reference is float32 at matmul precision "highest": attention by
explicit scores and mask **in blocks of queries** (so that 16384
positions fit beside the state), the expert layer a loop over the held
experts, each on all tokens, the cross-entropy in blocks of rows. It
imports nothing of ``dlrover_tpu``; what every reference shares (norm,
casts, the row-wise relative error) is ``families/xing4.py``'s.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.xing4 import _f32, _rms_norm, _row_rel, _shifted
from benchmarks.harness import smallthinker_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

Q_BLOCK = 256      # queries a block of the reference's attention
CE_BLOCK = 2048    # rows a block of its cross-entropy


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", False), ("rope_scaling", None),
                      ("moe_primary_router_apply_softmax", True)):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/smallthinker.py computes ({want!r})")
    return dict(
        n_layers=config["num_hidden_layers"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], ffn_dim=config["moe_ffn_hidden_size"],
        n_experts=config.get("published_moe_num_primary_experts",
                             config["moe_num_primary_experts"]),
        experts_held=config["moe_num_primary_experts"],
        experts_per_token=config["moe_num_active_primary_experts"],
        rope_layout=tuple(config["rope_layout"]),
        window_layout=tuple(config["sliding_window_layout"]),
        window=config["sliding_window_size"],
        vocab_size=config["vocab_size"],
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import smallthinker
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed = config["assumed"]
    cfg = smallthinker.SmallThinkerConfig(
        **sizes,
        first_expert=int(config.get("first_expert", 0)),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]),
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/smallthinker.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    if std != 0.02:
        raise ValueError("models/moe.py initialises with sigma 0.02 only")
    specs = smallthinker.param_specs(cfg)
    # assumed.out_proj_std: the sigma of the two projections that close a
    # residual branch (wo, w_down), where the configuration states one
    # apart from models/moe.py's sigma / sqrt(2 x layers)
    out_scale = (float(assumed["out_proj_std"])
                 / (std / (2 * cfg.n_layers) ** 0.5)
                 if "out_proj_std" in assumed else None)

    def init_params(key):
        params = smallthinker.init_params(cfg, key)
        if out_scale is None:
            return params
        return dict(params, layers={
            pos: {name: (w * out_scale).astype(w.dtype)
                  if name in ("wo", "w_down") else w
                  for name, w in lp.items()}
            for pos, lp in params["layers"].items()})

    init = jax.jit(init_params, out_shardings=named_shardings(mesh, specs))

    def reference(params, tokens):
        want = reference_pieces(params, tokens, config)
        ok = _compare(cfg, mesh, params, tokens, config, want)
        return want["ce"] if ok else float("nan")

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        # jobs/finetune_loop.py: the optimizer the configuration states
        # (arguments of TrainConfig) and the expert layers' live rows
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(
            lambda p, t: smallthinker.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: smallthinker.loss_fn(p, t, cfg, mesh),
        param_count=smallthinker.param_count(cfg),
        flops_per_token=lambda seq: smallthinker_flops.flops_per_token(
            seq=seq, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2
        expected_first_loss=(
            math.log(sizes["vocab_size"]) + sizes["dim"] * std * std / 2),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _rotary(x, theta):
    """``x (b, s, heads, d)``: a head's first half against its second."""
    s, d = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_qkv(y, lp, config, rotary: bool):
    """``y (b, s, d)``, already pre-normed -> q (b, s, h, hd), k and v
    (b, s, hkv, hd), rotary applied where the layer has it."""
    b, s, _ = y.shape
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = (y @ lp["wq"]).reshape(b, s, h, hd)
    k = (y @ lp["wk"]).reshape(b, s, hkv, hd)
    v = (y @ lp["wv"]).reshape(b, s, hkv, hd)
    if rotary:
        theta = float(config["rope_theta"])
        q, k = _rotary(q, theta), _rotary(k, theta)
    return q, k, v


def _ref_attention_core(q, k, v, window):
    """softmax(q k^T / sqrt(hd)) v -> (b, s, h, hd): explicit scores over
    all the keys, a block of queries at a time; the mask is the
    definition's, position by position. A block is recomputed in a
    backward pass, so that a vjp at 16384 positions keeps no scores."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    # query head i reads kv head i // (h / hkv)
    q = q.reshape(b, s // block, block, hkv, h // hkv, hd)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, start = args                          # (b, block, hkv, g, hd)
        qpos = start + jnp.arange(block)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) * hd ** -0.5
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(
        one, (jnp.moveaxis(q, 1, 0), jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def _ref_attention(y, lp, config, rotary: bool, window):
    """``y (b, s, d)``, already pre-normed -> the attention sublayer's
    output."""
    b, s, _ = y.shape
    out = _ref_attention_core(*_ref_qkv(y, lp, config, rotary), window)
    return out.reshape(b, s, -1) @ lp["wo"]


def _ref_attention_grads(x, lp, config, rotary: bool, window, cast):
    """What holds the attention *backward* to the definition: the layer's
    q, k, v on the residual ``x``, rounded to the activation dtype (the
    operands both sides read), a seeded cotangent ``g`` of the core's
    output, and dq, dk, dv of ``_ref_attention_core`` there, in float32
    (``cast`` rounds its operands and its results). Returns
    ``((q, k, v, g), (dq, dk, dv))``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    q, k, v = (a.astype(dt) for a in _ref_qkv(y, lp, config, rotary))
    g = jax.random.normal(jax.random.key(0), q.shape, jnp.float32).astype(dt)
    _, vjp = jax.vjp(
        lambda q, k, v: _ref_attention_core(q, k, v, window),
        *(cast(_f32(a)) for a in (q, k, v)))
    return (q, k, v, g), tuple(cast(d) for d in vjp(cast(_f32(g))))


def _ref_router(yt, lp, config):
    """``yt (t, d)`` -> per-expert weight (t, E) over ALL the experts the
    router scores (a token's p for the experts it chose, 0 for the
    others) and the chosen experts (t, k)."""
    z = yt @ lp["router"]
    top_z, top_e = jax.lax.top_k(z, config["moe_num_active_primary_experts"])
    if config["norm_topk_prob"]:
        top_p = jax.nn.softmax(top_z, axis=-1)
    else:
        top_p = jnp.exp(top_z - jax.nn.logsumexp(z, axis=-1, keepdims=True))
    chose = top_e[:, :, None] == jnp.arange(z.shape[1])[None, None, :]
    return jnp.sum(jnp.where(chose, top_p[:, :, None], 0.0), axis=1), top_e


def _reglu(u, gate, up, down):
    return (jax.nn.relu(u @ gate) * (u @ up)) @ down


def _ref_expert_layer(y, u, lp, config):
    """The router on ``y (b, s, d)``, the experts on ``u (b, s, d)`` ->
    (the held experts' part, chosen experts (t, k))."""
    b, s, d = u.shape
    ut = u.reshape(b * s, d)
    weight, top_e = _ref_router(y.reshape(b * s, d), lp, config)
    first = int(config.get("first_expert", 0))

    def one(i, out):
        return out + weight[:, first + i, None] * _reglu(
            ut, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])

    # a loop over the held experts (rolled, so that they trace once),
    # every one on every token; an absent expert is not in the loop
    out = jax.lax.fori_loop(0, lp["w_gate"].shape[0], one, jnp.zeros_like(ut))
    return out.reshape(b, s, d), top_e


def _ref_block(x, lp, config, rotary: bool, window, cast=lambda a: a):
    """One layer -> (the residual after it, the attention sublayer's
    output, the expert layer's, the chosen experts). ``cast`` rounds the
    weights and each sublayer's input and output (``second_reading``)."""
    eps = float(config["rms_norm_eps"])
    lp = jax.tree.map(cast, lp)
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    attn = cast(_ref_attention(y, lp, config, rotary, window))
    x = x + attn
    u = cast(_rms_norm(x, lp["mlp_norm"], eps))
    expert, top_e = _ref_expert_layer(y, u, lp, config)
    expert = cast(expert)
    return x + expert, attn, expert, top_e


def _ref_ce(x, norm, lm_head, targets, eps):
    """Mean CE of ``x (b, s, d)`` against ``targets (b, s)``, -1 = none;
    the logits a block of rows at a time."""
    d = x.shape[-1]
    rows = _rms_norm(x, norm, eps).reshape(-1, d)
    targets = targets.reshape(-1)
    block = CE_BLOCK if rows.shape[0] % CE_BLOCK == 0 else rows.shape[0]

    def one(args):
        r, t = args
        logp = jax.nn.log_softmax(r @ lm_head, axis=-1)
        gold = jnp.take_along_axis(
            logp, jnp.maximum(t, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(t >= 0, gold, 0.0))

    sums = jax.lax.map(
        one, (rows.reshape(-1, block, d), targets.reshape(-1, block)))
    return -jnp.sum(sums) / jnp.sum(targets >= 0)


def kinds_of(config: dict):
    """``(rotary, window or None)`` of each layer, first to last."""
    return [(bool(r), config["sliding_window_size"] if w else None)
            for r, w in zip(config["rope_layout"],
                            config["sliding_window_layout"])]


def period_of(config: dict) -> int:
    kinds = kinds_of(config)
    n = len(kinds)
    return next(p for p in range(1, n + 1) if n % p == 0 and all(
        kinds[i] == kinds[i % p] for i in range(n)))


def layers_of(params):
    """The layers' parameter trees, first to last: the program stacks
    them a position of the period (``pos0`` holds layers 0, p, 2p, ..),
    so layer ``l`` is row ``l // p`` of position ``l % p``."""
    slabs = [params["layers"][name] for name in sorted(
        params["layers"], key=lambda name: int(name[3:]))]
    for row in range(jax.tree.leaves(slabs[0])[0].shape[0]):
        for slab in slabs:
            yield jax.tree.map(lambda a: a[row], slab)


def plain_loss(params, tokens, config: dict):
    """The CE of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as
    it stands."""
    x = params["embed"][tokens]
    for lp, kind in zip(layers_of(params), kinds_of(config)):
        x = _ref_block(x, lp, config, *kind)[0]
    return _ref_ce(x, params["final_norm"], params["lm_head"],
                   _shifted(tokens, 1), float(config["rms_norm_eps"]))


def _round_trip(dtype):
    """Round to ``dtype`` and back. The barrier keeps the two conversions
    apart: on the chip XLA drops a narrowing it sees widened again at once
    (the float8 reading of the gradient pieces came out 0 without it)."""
    return lambda a: jax.lax.optimization_barrier(
        a.astype(dtype)).astype(jnp.float32)


def reference_pieces(params, tokens, config: dict, cast=None,
                     inputs=None) -> dict:
    """What the comparisons read, from the reference: ``ce``; ``hidden``,
    the residual after the last block; and of each layer of the first
    period: ``resid[i]``, the residual *before* it, ``after[i]`` the
    residual after it, ``attn[i]`` the attention sublayer's output,
    ``top_e[i]`` its router's choices; of the first layer ``expert``,
    the expert layer's output; of the first layer of each kind
    ``attn_operands[i]`` and ``attn_grads[i]`` (``_ref_attention_grads``).
    ``params`` is the program's tree in any
    dtype; one layer is cast to float32 at a time so that it fits
    beside a full device. ``cast`` (``second_reading``) rounds weights
    and sublayer inputs and outputs; the first period's pieces are then
    read on ``inputs[i]`` (the float32 reference's ``resid``), as the
    program's are, beside the rounded chain."""
    eps = float(config["rms_norm_eps"])
    cast = cast or (lambda a: a)
    block = jax.jit(
        lambda x, lp, rotary, window: _ref_block(
            x, _f32(lp), config, rotary, window, cast),
        static_argnums=(2, 3))
    embed = jax.jit(lambda table, t: cast(_f32(table))[t])
    period = period_of(config)
    kinds = kinds_of(config)
    # the first layer without a window and the first with one
    grad_layers = {[w for _, w in kinds].index(w) for _, w in kinds[:period]}
    grads = jax.jit(
        lambda x, lp, rotary, window: _ref_attention_grads(
            x, _f32(lp), config, rotary, window, cast),
        static_argnums=(2, 3))
    out = {"resid": [], "after": [], "attn": [], "top_e": [],
           "attn_operands": {}, "attn_grads": {}}
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i, (lp, kind) in enumerate(zip(layers_of(params), kinds)):
            if i < period:
                out["resid"].append(x)
            if i in grad_layers:
                out["attn_operands"][i], out["attn_grads"][i] = grads(
                    x if inputs is None else inputs[i], lp, *kind)
            if i < period and inputs is not None:
                after, attn, expert, top_e = block(inputs[i], lp, *kind)
                x = block(x, lp, *kind)[0]
            else:
                after, attn, expert, top_e = block(x, lp, *kind)
                x = after
            if i < period:
                out["after"].append(after)
                out["attn"].append(attn)
                out["top_e"].append(top_e)
            if i == 0:
                out["expert"] = expert
        ce = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, cast(_f32(norm)), cast(_f32(w)), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
    return dict(out, ce=float(ce), hidden=x)


def reference_loss(params, tokens, config: dict) -> float:
    return reference_pieces(params, tokens, config)["ce"]


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
# widths and 16384 positions, at the configuration's init (out_proj_std
# 1e-4; my chip runs, PR 37, the review round's last call; PERF.md
# section 6): the largest the bf16 program gave against the float32
# reference over seven seeds of the cell, and what the reference itself
# gives against float32 when its weights and each sublayer's input and
# output are rounded to float8_e4m3fn, the nearest precision below the
# bfloat16 the configuration states (``second_reading``, seeds
# 1618033989, 2147483777, 987654323; rounded to bfloat16 the same way it
# reads 0.00023 / 0.00007 / 0.0029 / 0.0031 / 0.0031 / 99.81-99.83 % /
# 0.0052 / 0.0037 / 0.000005-0.00002 and passes every limit). The float8
# path fails all but (f) and passes the job's loss tolerance: no CE at
# random init sees a precision. (At models/moe.py's init, which the
# first round ran, the first readings were 0.0055-0.0057 / 0.0045 /
# 0.0043 / 0.0026 / 0.0052 / 99.72-99.77 % / 0.0057 / 0.0060-0.0069.)
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward (the scan over periods): median over the tokens of
    # |program - reference| / |reference| along the row. bf16:
    # 0.00642-0.00643; float8: 0.0768-0.0770
    "hidden_rel_median": 0.03,
    # (b) the residual after each layer of the first period, the layer
    # given the reference's input: the largest of the layers' medians.
    # With branches that add little to their input this reads the
    # input's own rounding; (c), (d) and (g) read the branches. bf16:
    # 0.00286 on every seed; float8: 0.0220-0.0222
    "resid_rel_median": 0.008,
    # (c) the attention sublayer's output (W_o included) of the first
    # layer without a window (bf16 0.00425-0.00431; float8 1: outputs of
    # 1e-4-sigma projections lie under float8's smallest number), and of
    # the first with one **over the positions past the window only**
    # (before them a window masks nothing a causal mask leaves;
    # 0.00531-0.00537; 1)
    "full_attn_rel_median": 0.03,
    "window_attn_rel_median": 0.02,
    # (d) the first layer's expert output over the tokens whose choices
    # agree. bf16: 0.00451; float8: 1
    "expert_rel_median": 0.02,
    # (e) share of (token, choice) pairs the routers agree on, the least
    # of the first period's layers: both route in float32 on the same
    # input, the program from a bf16 pre-norm; near-ties flip. bf16:
    # 0.9974-0.9978; float8: 0.9602-0.9609
    "router_agree_min": 0.98,
    # (g) the attention *backward*: dq, dk, dv of the flash kernels alone
    # (group 7, 16384 positions; with the window the _swa kernels' band
    # walk) against the blocked float32 reference's vjp, on the
    # reference's q, k, v of the first layer of each kind (rounded to
    # the activation dtype, so that both sides read the same operands)
    # and one seeded cotangent: the 99th percentile over the (token,
    # head) rows of |program - reference| / |reference|, the largest of
    # the three. A percentile and not the median: a band walk that drops
    # a block at one edge is wrong in a few rows of a hundred. bf16:
    # 0.00564-0.00566 and 0.00420-0.00422 (0.0060-0.0069 at moe.py's
    # init); float8: 0.178-0.183 and 0.0868-0.0869
    "full_attn_grad_rel_p99": 0.03,
    "window_attn_grad_rel_p99": 0.025,
    # (h) share of pairs the routers agree on when the reference routes
    # on the program's *own* pre-normed input (in float32 both): the
    # router's arithmetic alone, where (e) also carries the bf16 norm.
    # Second reading: the reference's logits rounded to bfloat16, the
    # precision below the float32 the configuration states for the
    # router. program: 1 on every seed (14); bfloat16 logits:
    # 0.9960-0.9967
    "router_same_input_min": 0.999,
    # (f) the CE alone against the reference's: 0.000005-0.00011 over the
    # seeds, and 0.0001-0.0014 under float8: no precision moves it, a
    # dropped term or a wrong target does (it is the job's own loss
    # difference, held to half the job's tolerance: the limit of the
    # harness's accepted cells leaves the first reading 90 times of room)
    "ce_abs": 0.01,
}


def program_pieces(cfg, mesh, params, tokens, inputs, operands) -> dict:
    """The program's side of ``reference_pieces``; ``inputs[i]`` is the
    reference's residual before layer ``i`` of the first period,
    ``operands[i]`` its ``attn_operands``. Also ``y[i]``, the layer's
    pre-normed input, which its router read."""
    from dlrover_tpu.models import moe, smallthinker
    from dlrover_tpu.models.llama import _shift_targets
    from dlrover_tpu.ops import cross_entropy_sums, rms_norm
    from dlrover_tpu.ops.attention import flash_attention

    mcfg = cfg.as_moe()
    b, s = tokens.shape

    @jax.jit
    def whole(params, tokens):
        hidden = smallthinker.forward_layers(params, tokens, cfg, mesh)
        nll, n = cross_entropy_sums(
            rms_norm(hidden, params["final_norm"], cfg.norm_eps),
            params["lm_head"], _shift_targets(tokens),
            chunk_size=cfg.ce_chunk_size, mesh=mesh)
        return nll / jnp.maximum(n, 1.0), hidden

    def layer(lp, x, rotary, window):
        x = x.astype(cfg.dtype)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        attn = smallthinker.attention(cfg, mesh, lp, y, rotary, window)
        _, _, top_e = moe.route(mcfg, lp["router"], y.reshape(b * s, -1))
        u = rms_norm(x + attn, lp["mlp_norm"], cfg.norm_eps)
        expert = moe.moe_mlp(mcfg, lp, u, mesh, route_on=y)[0]
        return smallthinker.block(cfg, mesh, rotary, window, lp, x), \
            attn, expert, top_e, y

    def attn_grads(q, k, v, g, window):
        # the three kernels alone, as the layer calls them
        _, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, mesh=mesh, window=window), q, k, v)
        return vjp(g)

    layer = jax.jit(layer, static_argnums=(2, 3))
    attn_grads = jax.jit(attn_grads, static_argnums=4)
    ce, hidden = whole(params, tokens)
    out = {"ce": float(ce), "hidden": hidden, "after": [], "attn": [],
           "top_e": [], "y": [], "attn_grads": {}}
    for i, x in enumerate(inputs):
        lp = smallthinker.layer_params(cfg, params, i)
        after, attn, expert, top_e, y = layer(lp, x, *cfg.kinds[i])
        out["after"].append(after)
        out["attn"].append(attn)
        out["top_e"].append(top_e)
        out["y"].append(y)
        if i == 0:
            out["expert"] = expert
        if i in operands:
            out["attn_grads"][i] = attn_grads(*operands[i], cfg.kinds[i][1])
    return out


def _chosen(top_e, n_experts: int):
    """``top_e (t, k)`` -> (t, n_experts): 1 where the token chose it."""
    return jax.nn.one_hot(top_e, n_experts, dtype=jnp.int32).sum(1)


def readings(got: dict, want: dict, kinds, n_experts: int) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's."""
    k = want["top_e"][0].shape[1]

    def agree(i):
        return jnp.sum(_chosen(got["top_e"][i], n_experts)
                       * _chosen(want["top_e"][i], n_experts), axis=1)  # (t,)

    def median(a, b, rows=slice(None)):
        return float(jnp.median(_row_rel(a, b)[rows]))

    def grads_p99(i):
        return max(float(jnp.percentile(_row_rel(a, b), 99.0)) for a, b in zip(
            got["attn_grads"][i], want["attn_grads"][i]))

    period = len(want["attn"])
    windows = [w for _, w in kinds[:period]]
    agreed = [agree(i) for i in range(period)]
    out = {
        "hidden_rel_median": median(got["hidden"], want["hidden"]),
        "resid_rel_median": max(
            median(got["after"][i], want["after"][i])
            for i in range(period)),
        "expert_rel_median": median(
            got["expert"], want["expert"], agreed[0] == k),
        "router_agree_min": min(
            float(jnp.sum(a)) / (a.shape[0] * k) for a in agreed),
        "ce_abs": abs(got["ce"] - want["ce"]),
    }
    if None in windows:
        i = windows.index(None)
        out["full_attn_rel_median"] = median(got["attn"][i], want["attn"][i])
        out["full_attn_grad_rel_p99"] = grads_p99(i)
    if any(windows):
        i = next(i for i, w in enumerate(windows) if w)
        b, s = want["attn"][i].shape[:2]
        past = np.tile(np.arange(s) >= min(windows[i], s - 1), b)
        out["window_attn_rel_median"] = median(
            got["attn"][i], want["attn"][i], past)
        out["window_attn_grad_rel_p99"] = grads_p99(i)
    if "top_e_on_y" in want:
        out["router_same_input_min"] = min(
            float(jnp.sum(_chosen(a, n_experts) * _chosen(w, n_experts)))
            / a.size for a, w in zip(got["top_e"], want["top_e_on_y"]))
    return out


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[smallthinker] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok), flush=True)
    return all(ok.values())


def _compare(cfg, mesh, params, tokens, config, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each and returns whether all
    hold."""
    got = program_pieces(cfg, mesh, params, tokens, want["resid"],
                         want["attn_operands"])
    # the reference's router on the program's own pre-normed input: the
    # choices then differ by the router's arithmetic alone
    route = jax.jit(lambda y, lp: _ref_router(
        _f32(y).reshape(-1, y.shape[-1]), _f32(lp), config)[1])
    with jax.default_matmul_precision("highest"):
        want = dict(want, top_e_on_y=[
            route(y, {"router": lp["router"]})
            for y, lp in zip(got["y"], layers_of(params))])
    held = sum(int(np.asarray(jnp.sum(
        (e >= cfg.first_expert)
        & (e < cfg.first_expert + cfg.as_moe().n_held))))
        for e in got["top_e"])
    return _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}, window {cfg.window}; "
        f"{held} of {len(got['top_e']) * got['top_e'][0].size} pairs of the first "
        f"period chose a held expert; CE {got['ce']:.5f} / "
        f"{want['ce']:.5f})",
        readings(got, want, cfg.kinds, cfg.n_experts))


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/train_loop.py`` makes from ``seed``. By hand,
    on the chip::

        python -c "import json
        from benchmarks.families import smallthinker as f
        f.second_reading(json.load(open(
            'benchmarks/configs/smallthinker-21b-a3b-ep4-1chip.json')), 3)"
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
            f"(CE {got['ce']:.5f} / {want['ce']:.5f})",
            readings(got, want, fam.cfg.kinds, fam.cfg.n_experts))
    # (h)'s second reading: the router alone, its logits rounded to
    # bfloat16, on the inputs the float32 router read
    eps, k = float(config["rms_norm_eps"]), want["top_e"][0].shape[1]
    logits = jax.jit(lambda x, lp: _rms_norm(
        x, _f32(lp["attn_norm"]), eps).reshape(-1, x.shape[-1])
        @ _f32(lp["router"]))
    with jax.default_matmul_precision("highest"):
        rounded = [jax.lax.top_k(_round_trip(jnp.bfloat16)(logits(x, lp)), k)[1]
                   for x, lp in zip(want["resid"], layers_of(params))]
    passed["router_bfloat16"] = _report(
        f"the reference's router with its logits rounded to bfloat16 "
        f"against float32, seed {seed}",
        readings({**want, "top_e": rounded},
                 {**want, "top_e_on_y": want["top_e"]},
                 fam.cfg.kinds, fam.cfg.n_experts))
    return passed
