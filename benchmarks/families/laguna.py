"""The laguna family (poolside Laguna-XS.2), as
``dlrover_tpu.models.laguna`` computes it and as this file's plain
reference computes it again.

Layer equations, from the catalog row's ``config`` (hidden ``d`` 2048,
heads of 128 on 8 key heads, eps 1e-6, no bias anywhere; untied head);
layer ``l`` has an attention kind ``layer_types[l]``, a query-head count
``H_l = num_attention_heads_per_layer[l]`` and a feed-forward kind
``mlp_layer_types[l]``::

    y = RMSNorm(x; attn_norm)
    q = y W_q (s, H_l, 128);  k, v = y W_k, y W_v (s, 8, 128)
    full:    q, k turned on channels 0-63 of a head (partial_rotary_factor
             0.5), halves against halves, by yarn's frequencies (theta
             5e5, factor 64, original 4096, beta_fast 64, beta_slow 1),
             cos and sin times attention_factor; channels 64-127 as they are
    sliding: q, k turned on all 128 channels at theta 1e4, no scaling
    o_h = softmax(q_h k_j^T / sqrt(128) + mask) v_j,  j = h // (H_l / 8)
          mask: j <= i, and on a sliding layer 0 <= i - j < 512
    g = sigmoid(y W_g)  (s, H_l)
    x = x + concat_h(g_h o_h) W_o
    u = RMSNorm(x; mlp_norm)
    dense:  x = x + W_down (silu(W_gate u) * (W_up u))
    sparse: r = sigmoid(u W_r) in float32;  T = the 8 largest of r
            p_e = 2.5 r_e / sum_{e' in T} r_e'
            x = x + sum_{e in T, e held} p_e E_e(u) + E_shared(u)

Final RMSNorm, the head, mean next-token cross-entropy over the held
ids. This chip holds experts ``first_expert .. + num_experts - 1`` of
``published_num_experts``: a pair that chose another adds nothing. What
the row does not settle is under ``assumed`` in the configuration.

The reference is float32 at matmul precision "highest": attention by
explicit scores and mask in blocks of 256 queries (so that 16384
positions fit beside the state), the expert layer a loop over the held
experts, each on all tokens, the cross-entropy in blocks of 2048 rows.
It imports nothing of ``dlrover_tpu``; what every reference shares (the
norm, the casts, the row-wise relative error, the blocked attention core
and cross-entropy, yarn's frequencies as the paper writes them) is
``families/xing4.py``'s and ``families/smallthinker.py``'s.
"""

from __future__ import annotations

import math
import re
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.smallthinker import (
    _chosen,
    _ref_attention_core,
    _ref_ce,
    _round_trip,
)
from benchmarks.families.xing4 import (
    _f32,
    _rms_norm,
    _row_rel,
    _shifted,
    yarn_inv_freq,
)
from benchmarks.harness import laguna_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
FULL, WINDOW = "full_attention", "sliding_attention"


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import laguna
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/laguna.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    cfg = laguna.LagunaConfig.from_hf(
        config,
        n_experts=config.get("published_num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        first_expert=int(config.get("first_expert", 0)),
        init_std=std,
        out_proj_std=(float(assumed["out_proj_std"])
                      if "out_proj_std" in assumed else None),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = laguna.param_specs(cfg)
    sizes = laguna_flops.sizes_of(config)

    def reference(params, tokens):
        read, ce = compare(params, tokens, config,
                           _Program(cfg, mesh, params, tokens))
        ok = _report(
            f"program against reference on the seeded batch ({tokens.size} "
            f"tokens, pattern {cfg.pattern_string}, window {cfg.window}, "
            f"heads {cfg.heads_of('F')} / {cfg.heads_of('S')})", read)
        return ce if ok else float("nan")

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=jax.jit(
            lambda key: laguna.init_params(cfg, key),
            out_shardings=named_shardings(mesh, specs)),
        # jobs/finetune_loop.py: the optimizer the configuration states
        # (arguments of TrainConfig) and the expert layers' live rows
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(lambda p, t: laguna.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: laguna.loss_fn(p, t, cfg, mesh),
        param_count=laguna.param_count(cfg),
        flops_per_token=lambda seq: laguna_flops.flops_per_token(
            seq=seq, **sizes),
        # random weights at sigma give logits of variance dim x sigma^2
        expected_first_loss=(
            math.log(config["vocab_size"])
            + config["hidden_size"] * std * std / 2),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def kinds_of(config: dict):
    """``(layer type, query heads, dense)`` of each layer, first to last."""
    return list(zip(config["layer_types"],
                    config["num_attention_heads_per_layer"],
                    (m == "dense" for m in config["mlp_layer_types"])))


def rotary_of(config: dict, layer_type: str):
    """``(inverse frequencies, what cos and sin are multiplied by)`` of a
    layer: their count is half the channels turned."""
    rope = config["rope_parameters"][layer_type]
    turned = int(rope["partial_rotary_factor"] * config["head_dim"])
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "default":
        return theta ** (-jnp.arange(0, turned, 2, dtype=jnp.float32)
                         / turned), 1.0
    return yarn_inv_freq(
        turned, theta, float(rope["factor"]),
        rope["original_max_position_embeddings"], float(rope["beta_fast"]),
        float(rope["beta_slow"])), float(rope["attention_factor"])


def _rotary(x, inv_freq, magnitude):
    """``x (b, s, heads, d)``: the first ``2 len(inv_freq)`` channels of
    a head turned, their first half against their second, cos and sin
    times ``magnitude``; the rest as they are."""
    s, half = x.shape[1], inv_freq.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angles) * magnitude)[:, None, :]
    sin = (jnp.sin(angles) * magnitude)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _ref_qkv(y, lp, config, layer_type: str):
    """``y (b, s, d)``, already pre-normed -> q (b, s, H_l, hd), k and v
    (b, s, 8, hd), the layer's rotary applied."""
    b, s, _ = y.shape
    hd, hkv = config["head_dim"], config["num_key_value_heads"]
    q = (y @ lp["wq"]).reshape(b, s, -1, hd)
    k = (y @ lp["wk"]).reshape(b, s, hkv, hd)
    v = (y @ lp["wv"]).reshape(b, s, hkv, hd)
    turn = rotary_of(config, layer_type)
    return _rotary(q, *turn), _rotary(k, *turn), v


def _window_of(config, layer_type):
    return config["sliding_window"] if layer_type == WINDOW else None


def _ref_attention(y, lp, config, layer_type: str):
    """``y (b, s, d)`` -> ``(the attention sublayer's output, the gate
    (b, s, H_l))``."""
    b, s, _ = y.shape
    core = _ref_attention_core(
        *_ref_qkv(y, lp, config, layer_type), _window_of(config, layer_type))
    gate = jax.nn.sigmoid(y @ lp["w_g"])
    return (core * gate[..., None]).reshape(b, s, -1) @ lp["wo"], gate


def _ref_attention_grads(x, lp, config, layer_type: str, cast):
    """What holds the attention *backward* to the definition: the layer's
    q, k, v on the residual ``x``, rounded to the activation dtype (the
    operands both sides read), a seeded cotangent ``g`` of the core's
    output, and dq, dk, dv of the blocked core there, in float32
    (``cast`` rounds its operands and its results). Returns
    ``((q, k, v, g), (dq, dk, dv))``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    q, k, v = (a.astype(dt) for a in _ref_qkv(y, lp, config, layer_type))
    g = jax.random.normal(jax.random.key(0), q.shape, jnp.float32).astype(dt)
    window = _window_of(config, layer_type)
    _, vjp = jax.vjp(
        lambda q, k, v: _ref_attention_core(q, k, v, window),
        *(cast(_f32(a)) for a in (q, k, v)))
    return (q, k, v, g), tuple(cast(d) for d in vjp(cast(_f32(g))))


def _swiglu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _ref_router(ut, lp, config, scores=lambda a: a):
    """``ut (t, d)`` -> per-expert weight (t, E) over ALL the experts the
    router scores (a token's p for the experts it chose, 0 for the
    others) and the chosen experts (t, k). ``scores`` rounds the logits
    (the router's own second reading)."""
    r = jax.nn.sigmoid(scores(ut @ lp["router"]))
    top_r, top_e = jax.lax.top_k(r, config["num_experts_per_tok"])
    top_p = float(config["moe_routed_scaling_factor"]) * top_r / jnp.sum(
        top_r, axis=-1, keepdims=True)
    chose = top_e[:, :, None] == jnp.arange(r.shape[1])[None, None, :]
    return jnp.sum(jnp.where(chose, top_p[:, :, None], 0.0), axis=1), top_e


def _ref_routed(u, lp, config, first=None):
    """The held experts' part of the layer's output on ``u (b, s, d)``
    -> (that part, the chosen experts (t, k)). ``first``: the first of
    the held experts among all the router scores."""
    b, s, d = u.shape
    ut = u.reshape(b * s, d)
    weight, top_e = _ref_router(ut, lp, config)
    first = int(config.get("first_expert", 0)) if first is None else first

    def one(i, out):
        return out + weight[:, first + i, None] * _swiglu(
            ut, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])

    # a loop over the held experts (rolled, so that they trace once),
    # every one on every token; an absent expert is not in the loop
    out = jax.lax.fori_loop(0, lp["w_gate"].shape[0], one, jnp.zeros_like(ut))
    return out.reshape(b, s, d), top_e


def _ref_block(x, lp, config, layer_type: str, cast=lambda a: a) -> dict:
    """One layer -> its pieces: ``after`` the residual after it, ``attn``
    the attention sublayer's output, ``gate``, ``u`` the feed-forward's
    normed input, and ``dense`` or ``routed``, ``shared``, ``top_e``.
    ``cast`` rounds the weights and each sublayer's input and output
    (``second_reading``)."""
    eps = float(config["rms_norm_eps"])
    lp = jax.tree.map(cast, lp)
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    attn, gate = _ref_attention(y, lp, config, layer_type)
    attn = cast(attn)
    x = x + attn
    u = cast(_rms_norm(x, lp["mlp_norm"], eps))
    out = {"attn": attn, "gate": gate, "u": u}
    if "router" in lp:
        routed, out["top_e"] = _ref_routed(u, lp, config)
        out["routed"] = cast(routed)
        out["shared"] = cast(
            _swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"]))
        x = x + out["routed"] + out["shared"]
    else:
        out["dense"] = cast(
            _swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]))
        x = x + out["dense"]
    return dict(out, after=x)


def layers_of(params):
    """The layers' parameter trees, first to last: the program keeps the
    dense layers each on its own (``dense/layer<i>``), the expert layers'
    whole periods stacked a position of the period (``layers/pos<i>``:
    row ``r`` of position ``i`` is layer ``r x period + i`` of them) and
    what follows as runs of like layers, each stacked (``tail/run<i>``)."""
    def ordered(group):
        return [group[name] for name in sorted(
            group, key=lambda name: int(re.sub(r"\D", "", name)))]

    yield from ordered(params["dense"])
    slabs = ordered(params["layers"])
    for row in range(jax.tree.leaves(slabs[0])[0].shape[0] if slabs else 0):
        for slab in slabs:
            yield jax.tree.map(lambda a: a[row], slab)
    for run in ordered(params["tail"]):
        for row in range(jax.tree.leaves(run)[0].shape[0]):
            yield jax.tree.map(lambda a: a[row], run)


def plain_loss(params, tokens, config: dict):
    """The CE of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as
    it stands."""
    x = params["embed"][tokens]
    for lp, (layer_type, _, _) in zip(layers_of(params), kinds_of(config)):
        x = _ref_block(x, lp, config, layer_type)["after"]
    return _ref_ce(x, params["final_norm"], params["lm_head"],
                   _shifted(tokens, 1), float(config["rms_norm_eps"]))


def reference_loss(params, tokens, config: dict) -> float:
    return float(_highest(
        lambda p, t: plain_loss(_f32(p), t, config))(params, tokens))


def n_compared(config: dict) -> int:
    """The layers whose pieces are compared one by one: the leading
    dense ones and the first whole period of those that follow."""
    kinds = kinds_of(config)
    dense = sum(d for _, _, d in kinds)
    body = kinds[dense:]
    period = next(p for p in range(1, len(body) + 1) if all(
        body[i] == body[i % p] for i in range(len(body))))
    return min(dense + period, len(kinds))


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell
# incorrect). Except for (a), each piece is the program's layer on the
# *reference's* input to that layer (rounded to the activation dtype), so
# that a reading is one layer's error and not the chain's. The layers are
# walked one at a time and only the readings are kept: at 16384 positions
# a layer's float32 pieces are 0.6 GiB beside 6.7 GB of state.
#
# Each limit lies between two readings on the chip at the published
# widths and 16384 positions (my chip runs, PR 60; PERF.md section 2):
# the largest the bf16 program gave against the float32 reference over
# the cell's seeds ("bf16" below), and what the reference itself gives
# against float32 when its weights and each sublayer's input and output
# are rounded to float8_e4m3fn, the nearest precision below the bfloat16
# the configuration states (``second_reading``, seed 2147483012:
# "float8"; rounded to bfloat16 the same way it reads 0.00077 / 0.00062
# / 0.0033 / 0.0030 / 0.00060 / 0.0029 / 0.0029 / 0.0029 / 0.9978 /
# 0.0041 / 0.0039 / 0.00003 and passes every limit). The float8 path
# fails all but (h).
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward (the dense layer, the scan over the period, the tail's
    # scan): median over the tokens of |program - reference| /
    # |reference| along the row. bf16: 0.00656; float8: 0.26
    "hidden_rel_median": 0.03,
    # (b) the residual after each compared layer, the layer given the
    # reference's input: the largest of the layers' medians. With
    # branches that add little to their input this reads the input's own
    # rounding; the pieces below read the branches. bf16: 0.00288;
    # float8: 0.21
    "resid_rel_median": 0.008,
    # (c) the attention sublayer's output (gate and W_o included) of the
    # first full layer **over the queries past position
    # original_max_position_embeddings** (where yarn's blended
    # frequencies differ from plain ones by whole turns), and of the
    # first sliding layer **over the queries past the window** (before
    # them a window masks nothing a causal mask leaves). bf16: 0.0058 and
    # 0.0057; float8: 1 and 1 (outputs of 1e-4-sigma projections lie
    # under float8's smallest number)
    "full_attn_rel_median": 0.03,
    "window_attn_rel_median": 0.03,
    # (d) the gate sigmoid(y W_g), the largest of the compared layers'
    # medians: a number a head in (0, 1), which a dropped or misplaced
    # gate reads as 1. bf16: 0.00184; float8: 0.0154
    "gate_rel_median": 0.006,
    # (e) the dense layer's feed-forward output, the first sparse
    # layer's routed output over the tokens whose choices agree and name
    # a held expert, and its shared expert's output. bf16: 0.0039,
    # 0.0051, 0.0046; float8: 1, 1, 1
    "dense_rel_median": 0.03,
    "expert_rel_median": 0.03,
    "shared_rel_median": 0.03,
    # (f) share of (token, choice) pairs the routers agree on, the least
    # of the compared sparse layers: both route in float32 on the same
    # input, the program from a bf16 norm; near-ties flip. And the same
    # with the reference routing on the program's *own* normed input:
    # the router's arithmetic alone. bf16: 0.9968 and 1; float8: 0.949,
    # and the reference's own logits rounded to bfloat16: 0.995
    "router_agree_min": 0.98,
    "router_same_input_min": 0.999,
    # (g) the attention *backward*: dq, dk, dv of the flash kernels alone
    # (group 6 causal; group 8 under window 512, the _swa kernels' band
    # walk) against the blocked float32 reference's vjp, on the
    # reference's q, k, v of the first layer of each kind (rounded to the
    # activation dtype, so that both sides read the same operands) and
    # one seeded cotangent: the 99th percentile over the (token, head)
    # rows of |program - reference| / |reference|, the largest of the
    # three. A percentile and not the median: a band walk that drops a
    # block at one edge is wrong in a few rows of a hundred. bf16: 0.0046
    # and 0.0044; float8: 0.171 and 0.082
    "full_attn_grad_rel_p99": 0.03,
    "window_attn_grad_rel_p99": 0.03,
    # (h) the CE alone against the reference's: no precision moves it, a
    # dropped term or a wrong target does (it is the job's own loss
    # difference, held to half the job's tolerance: the limit of the
    # harness's accepted cells). bf16: 0.00003-0.00007; float8: 0.00013
    "ce_abs": 0.01,
}


def _highest(fn, **jit_args):
    """``fn`` jitted, each call traced and run at matmul precision
    "highest": the reference's own, which the program's kernels, traced
    between its calls, must not inherit."""
    jitted = jax.jit(fn, **jit_args)

    def call(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)

    return call


class _Reference:
    """The float32 reference's jitted pieces, ``cast`` applied where
    ``second_reading`` rounds."""

    def __init__(self, config: dict, cast=None, scores=lambda a: a):
        eps = float(config["rms_norm_eps"])
        cast = cast or (lambda a: a)
        self.block = _highest(
            lambda x, lp, layer_type: _ref_block(
                x, _f32(lp), config, layer_type, cast),
            static_argnums=2)
        self.grads = _highest(
            lambda x, lp, layer_type: _ref_attention_grads(
                x, _f32(lp), config, layer_type, cast),
            static_argnums=2)
        self.embed = _highest(lambda table, t: cast(_f32(table))[t])
        self.ce = _highest(lambda x, norm, w, t: _ref_ce(
            x, cast(_f32(norm)), cast(_f32(w)), t, eps))
        # the router alone; ``scores`` rounds its logits
        self.route = _highest(lambda u, lp: _ref_router(
            _f32(u).reshape(-1, u.shape[-1]), _f32(lp), config, scores)[1])


class _Program:
    """The program's side of the comparison: each compared layer on the
    reference's input, the three flash kernels alone on the reference's
    operands, and its own forward."""
    routes = True

    def __init__(self, cfg, mesh, params, tokens):
        from dlrover_tpu.models import laguna, llama, moe
        from dlrover_tpu.ops import cross_entropy_sums, rms_norm
        from dlrover_tpu.ops.attention import flash_attention

        self.cfg, self.params, self.tokens = cfg, params, tokens
        mcfg = cfg.as_moe()
        b, s = tokens.shape

        def whole(params, tokens):
            hidden = laguna.forward_layers(params, tokens, cfg, mesh)
            nll, n = cross_entropy_sums(
                rms_norm(hidden, params["final_norm"], cfg.norm_eps),
                params["lm_head"], llama._shift_targets(tokens),
                chunk_size=cfg.ce_chunk_size, mesh=mesh)
            return nll / jnp.maximum(n, 1.0), hidden

        def layer(lp, x, kind):
            x = x.astype(cfg.dtype)
            y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            attn = laguna.attention(cfg, mesh, kind, lp, y)
            u = rms_norm(x + attn, lp["mlp_norm"], cfg.norm_eps)
            out = {"after": laguna.block(cfg, mesh, kind, lp, x),
                   "attn": attn, "gate": laguna.head_gate(lp, y, cfg.dtype),
                   "u": u}
            if "router" not in lp:
                return dict(out, dense=laguna.feed_forward_half(
                    cfg, mesh, lp, jnp.zeros_like(u), u))
            routed = {n: w for n, w in lp.items() if not n.startswith("ws_")}
            return dict(
                out, routed=moe.moe_mlp(mcfg, routed, u, mesh)[0],
                shared=moe._shared_expert(lp, u),
                top_e=moe.route(mcfg, lp["router"], u.reshape(b * s, -1))[2])

        def attn_grads(q, k, v, g, window):
            # the three kernels alone, as the layer calls them
            _, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, mesh=mesh, window=window), q, k, v)
            return vjp(g)

        self._whole = jax.jit(whole)
        self._layer = jax.jit(layer, static_argnums=2)
        self._grads = jax.jit(attn_grads, static_argnums=4)

    def layer(self, i, lp, x):
        return self._layer(lp, x, self.cfg.kinds[i])

    def grads(self, i, lp, x, operands):
        name, _ = self.cfg.kinds[i]
        return self._grads(
            *operands, self.cfg.window if name == "S" else None)

    def follow(self, i, lp):
        pass

    def whole(self):
        ce, hidden = self._whole(self.params, self.tokens)
        return float(ce), hidden


class _Rounded:
    """The reference with its weights and each sublayer's input and
    output rounded by ``cast``, as a side of the comparison: each
    compared layer on the float32 reference's input, beside its own
    chain from its own rounded table."""
    routes = False

    def __init__(self, config, params, tokens, cast):
        self.ref = _Reference(config, cast)
        self.kinds = kinds_of(config)
        self.params, self.tokens = params, tokens
        self.x = self.ref.embed(params["embed"], tokens)

    def layer(self, i, lp, x):
        return self.ref.block(x, lp, self.kinds[i][0])

    def grads(self, i, lp, x, operands):
        return self.ref.grads(x, lp, self.kinds[i][0])[1]

    def follow(self, i, lp):
        self.x = self.ref.block(self.x, lp, self.kinds[i][0])["after"]

    def whole(self):
        p = self.params
        return float(self.ref.ce(self.x, p["final_norm"], p["lm_head"],
                                 _shifted(self.tokens, 1))), self.x


def _median(a, b, rows=slice(None)):
    return float(jnp.median(_row_rel(a, b)[rows]))


def _layer_readings(read: dict, got: dict, want: dict, config: dict,
                    layer_type: str, first_of_kind: bool, top_e_on_u=None):
    """One compared layer's readings into ``read``, each the worst of
    the layers so far."""
    def worst(name, value, least=False):
        read[name] = (min if least else max)(read.get(name, value), value)

    b, s = want["attn"].shape[:2]
    worst("resid_rel_median", _median(got["after"], want["after"]))
    worst("gate_rel_median", _median(got["gate"], want["gate"]))
    if first_of_kind:
        rope = config["rope_parameters"][layer_type]
        edge = (config["sliding_window"] if layer_type == WINDOW
                else rope.get("original_max_position_embeddings", 0))
        past = np.tile(np.arange(s) >= min(edge, s - 1), b)
        name = "window" if layer_type == WINDOW else "full"
        read[f"{name}_attn_rel_median"] = _median(
            got["attn"], want["attn"], past)
    if "dense" in want:
        worst("dense_rel_median", _median(got["dense"], want["dense"]))
        return
    n = config.get("published_num_experts", config["num_experts"])
    k = want["top_e"].shape[1]
    agreed = jnp.sum(
        _chosen(got["top_e"], n) * _chosen(want["top_e"], n), axis=1)
    worst("router_agree_min", float(jnp.sum(agreed)) / agreed.size / k, True)
    if top_e_on_u is not None:
        same = jnp.sum(_chosen(got["top_e"], n) * _chosen(top_e_on_u, n))
        worst("router_same_input_min", float(same) / agreed.size / k, True)
    if "expert_rel_median" not in read:
        first = int(config.get("first_expert", 0))
        held = jnp.any((want["top_e"] >= first)
                       & (want["top_e"] < first + config["num_experts"]), 1)
        read["expert_rel_median"] = _median(
            got["routed"], want["routed"], np.asarray((agreed == k) & held))
        read["shared_rel_median"] = _median(got["shared"], want["shared"])


def compare(params, tokens, config: dict, side):
    """``(readings, the reference's CE)``: ``side`` (`_Program`, or
    `_Rounded`) against the float32 reference, a layer at a time."""
    ref = _Reference(config)
    kinds = kinds_of(config)
    compared = n_compared(config)
    read, seen = {}, set()
    x = ref.embed(params["embed"], tokens)
    for i, (lp, (layer_type, _, dense)) in enumerate(
            zip(layers_of(params), kinds)):
        want = ref.block(x, lp, layer_type)
        side.follow(i, lp)
        if i < compared:
            got = side.layer(i, lp, x)
            # the reference's router on the program's own normed input:
            # the choices then differ by the router's arithmetic alone
            # (`second_reading` rounds a router's logits by themselves)
            on_u = ref.route(got["u"], {"router": lp["router"]}) if (
                side.routes and not dense) else None
            _layer_readings(read, got, want, config, layer_type,
                            layer_type not in seen, on_u)
            if layer_type not in seen:
                operands, grads = ref.grads(x, lp, layer_type)
                name = "window" if layer_type == WINDOW else "full"
                read[f"{name}_attn_grad_rel_p99"] = max(
                    float(jnp.percentile(_row_rel(a, w), 99.0))
                    for a, w in zip(side.grads(i, lp, x, operands), grads))
                del operands, grads
            seen.add(layer_type)
            del got
        x = want["after"]
        del want
    ce = float(ref.ce(x, params["final_norm"], params["lm_head"],
                      _shifted(tokens, 1)))
    got_ce, hidden = side.whole()
    read["hidden_rel_median"] = _median(hidden, x)
    read["ce_abs"] = abs(got_ce - ce)
    read["ce"] = got_ce
    read["reference_ce"] = ce
    return read, ce


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    ce = (f" (CE {read['ce']:.5f} / {read['reference_ce']:.5f})"
          if "ce" in read else "")
    print(f"[laguna] {what}{ce}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok), flush=True)
    return all(ok.values())


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/finetune_loop.py`` makes from ``seed``; and
    the router's own: its logits rounded to bfloat16. By hand, on the
    chip::

        python -c "import json
        from benchmarks.families import laguna as f
        f.second_reading(json.load(open(
            'benchmarks/configs/laguna-xs.2-ep8-1chip.json')), 3)"
    """
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam = build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    passed = {}
    for name, dtype in (("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        read, _ = compare(params, tokens, config, _Rounded(
            config, params, tokens, _round_trip(dtype)))
        passed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed}", read)
    # the router alone, its logits rounded to bfloat16, on the inputs the
    # float32 router read
    ref = _Reference(config)
    rounded = _Reference(config, scores=_round_trip(jnp.bfloat16)).route
    n = config.get("published_num_experts", config["num_experts"])
    least = 1.0
    x = ref.embed(params["embed"], tokens)
    for i, (lp, (layer_type, _, dense)) in enumerate(
            zip(layers_of(params), kinds_of(config))):
        if i >= n_compared(config):
            break
        want = ref.block(x, lp, layer_type)
        if not dense:
            got = rounded(want["u"], {"router": lp["router"]})
            least = min(least, float(jnp.sum(
                _chosen(got, n) * _chosen(want["top_e"], n))) / got.size)
        x = want["after"]
    passed["router_bfloat16"] = _report(
        "the reference's router with its logits rounded to bfloat16 against "
        f"float32, seed {seed}", {"router_same_input_min": least})
    return passed
