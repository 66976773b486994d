"""The phi4flash family (microsoft Phi-4-mini-flash-reasoning), as
``dlrover_tpu.models.phi4flash`` computes it and as this file's plain
reference computes it again.

Layer equations, from the catalog row's ``config`` and what the
configuration's ``assumed`` fixes (hidden ``d`` 2560, 40 query heads on 20
key heads of 64, feed-forward 10240, Mamba-1 with ``c`` = 5120 channels,
``n`` = 16 states, a convolution of 4 taps, ``r`` = 160; ``LN(x; g, b) =
(x - mean) / sqrt(var + eps) g + b``, eps 1e-5). The layers held are
``layer_kinds``, a letter a layer, ``(M S)^a M F (G C)^b``::

    y = LN(x; g1, b1);  x = x + Mixer_K(y)
    u = LN(x; g2, b2);  x = x + (silu(u W_gate) * (u W_up)) W_down

    M:  [xs | z] = y W_in;  t = silu(conv4(xs) + b_conv)
        [p | B | C] = t W_x;  dt = softplus(p W_dt + b_dt);  A = -exp(A_log)
        S_t[c, :] = exp(dt_t[c] A[c, :]) S_(t-1)[c, :] + dt_t[c] t_t[c] B_t
        m_t[c] = S_t[c, :] . C_t + D[c] t_t[c];   Mixer = (m silu(z)) W_out
        the last M also hands m (before the gate) to every G
    S:  [q | k | v] = y W_qkv;  o_h = softmax(q_h k_(h // 2)^T / 8 + mask)
        v_(h // 2),  mask: 0 <= i - j < 512;  Mixer = o W_o;  no position term
    F:  as S with mask j <= i;  it also hands k, v to every C
    G:  Mixer = (m* silu(y W_1)) W_2
    C:  q = y W_q;  o_h = softmax(q_h k*_(h // 2)^T / 8 + causal) v*_(h // 2)
        Mixer = o W_o

    logits = LN(x_L; g_f, b_f) E^T,  E the lookup's table; mean next-token
    cross-entropy over the held ids

The reference is float32 at matmul precision "highest": the recurrence a
token a step in rematerialised blocks of 128 tokens, attention by explicit
scores and mask in blocks of 256 queries, the cross-entropy in blocks of
2048 rows, so that 16384 positions fit beside the state. It imports
nothing of ``dlrover_tpu``; what every reference shares (the casts, the
row-wise relative error, the SwiGLU, the blocked attention core) is
``families/xing4.py``'s and ``families/smallthinker.py``'s.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.smallthinker import (
    _ref_attention_core,
    _round_trip,
)
from benchmarks.families.xing4 import _f32, _row_rel, _shifted, _swiglu
from benchmarks.harness import phi4flash_flops

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
T_BLOCK = 128      # tokens a rematerialised block of the recurrence
CE_BLOCK = 2048    # rows a block of the cross-entropy
#: ways in which a reference can be made wrong on purpose
#: (benchmarks/tests/test_phi4flash_reference.py): the memory without D's
#: term; the memory taken after the producer's gate; the C layers reading
#: the keys and values of the window layer before the F; a window of one
#: key more
MUTATIONS = ("no_d", "after_gate", "other_keys", "window_off_by_one")


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import phi4flash
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/phi4flash.py remats a block or nothing")
    cfg = phi4flash.Phi4FlashConfig.from_hf(
        config,
        mamba_chunk=int(assumed["mamba"]["chunk"]),
        init_std=float(assumed["initializer_range"]),
        out_proj_std=(float(assumed["out_proj_std"])
                      if "out_proj_std" in assumed else None),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = phi4flash.param_specs(cfg)
    sizes = phi4flash_flops.sizes_of(config)

    def reference(params, tokens):
        read, ce = compare(params, tokens, config,
                           _Program(cfg, mesh, params, tokens))
        ok = _report(
            f"program against reference on the seeded batch ({tokens.size} "
            f"tokens, pattern {cfg.pattern_string}, window {cfg.window}, "
            f"{cfg.channels} channels of {cfg.mamba_state} states)", read)
        return ce if ok else float("nan")

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=jax.jit(
            lambda key: phi4flash.init_params(cfg, key),
            out_shardings=named_shardings(mesh, specs)),
        loss_fn=lambda p, t: phi4flash.loss_fn(p, t, cfg, mesh),
        param_count=phi4flash.param_count(cfg),
        flops_per_token=lambda seq: phi4flash_flops.flops_per_token(
            seq=seq, **sizes),
        expected_first_loss=phi4flash_flops.expected_first_loss(config),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _ln(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _ref_conv(x, weight):
    """``x (b, s, c)``, ``weight (c, taps)``: ``y_t = sum_i w[:, i]
    x_(t - taps + 1 + i)``, zeros before the sequence."""
    taps, s = weight.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * weight[:, i] for i in range(taps))


def ref_scan(x, dt, A, B, C, D):
    """The recurrence as written, a token a step: ``x, dt (b, s, c)``,
    ``A (c, n)``, ``B, C (b, s, n)``, ``D (c,)`` -> ``y (b, s, c)``. The
    scan runs in rematerialised blocks of ``T_BLOCK`` tokens: a vjp keeps
    one state a block and a block's own states while it is
    differentiated."""
    b, s, c = x.shape

    def step(S, xs):
        x, dt, B, C = xs                    # (b, c), (b, c), (b, n) x 2
        S = (jnp.exp(dt[..., None] * A) * S
             + (dt * x)[..., None] * B[:, None, :])
        return S, jnp.einsum("bcn,bn->bc", S, C) + D * x

    block = T_BLOCK if s % T_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, b, -1)
               for a in (x, dt, B, C))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((b, c, B.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(s, b, c), 0, 1)


def _mamba_sizes(config: dict):
    mamba = config["assumed"]["mamba"]
    d = config["hidden_size"]
    return mamba["expand"] * d, mamba["d_state"], -(-d // 16)


def _ref_mamba_operands(y, lp, config):
    """``y (b, s, d)``, pre-normed -> the recurrence's ``(t, dt, A, B, C,
    D)`` and the gate's logits ``z (b, s, c)``."""
    c, n, r = _mamba_sizes(config)
    xz = y @ lp["w_in"]
    t = jax.nn.silu(_ref_conv(xz[..., :c], lp["conv_w"]) + lp["conv_b"])
    pbc = t @ lp["w_x"]
    dt = jax.nn.softplus(pbc[..., :r] @ lp["w_dt"] + lp["dt_bias"])
    return (t, dt, -jnp.exp(lp["a_log"]), pbc[..., r:r + n],
            pbc[..., r + n:], lp["d_skip"]), xz[..., c:]


def _ref_mamba(y, lp, config, mutate=None):
    """-> ``(the mixer's output, the scan's output m, dt, what the layer
    hands to the gated memory units)``."""
    operands, z = _ref_mamba_operands(y, lp, config)
    m = ref_scan(*operands)
    gated = m * jax.nn.silu(z)
    handed = {"no_d": m - operands[5] * operands[0],
              "after_gate": gated}.get(mutate, m)
    return gated @ lp["w_out"], m, operands[1], handed


def _ref_heads(a, hd: int):
    return a.reshape(a.shape[:2] + (-1, hd))


def _ref_qkv(y, lp, config):
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // h
    qkv = y @ lp["w_qkv"]
    return (_ref_heads(qkv[..., :h * hd], hd),
            _ref_heads(qkv[..., h * hd:(h + kvh) * hd], hd),
            _ref_heads(qkv[..., (h + kvh) * hd:], hd))


def _ref_attend(q, k, v, w_o, window):
    b, s = q.shape[:2]
    return _ref_attention_core(q, k, v, window).reshape(b, s, -1) @ w_o


def _ref_block(x, lp, config, kind: str, shared=None, cast=lambda a: a,
               mutate=None) -> dict:
    """One layer of ``kind`` -> its pieces: ``after`` the residual after
    it, ``mix`` the mixer's output, ``dense`` the feed-forward's, and by
    kind ``m``, ``dt`` and ``memory`` (M), ``k`` and ``v`` (S, F).
    ``shared``: ``(m*, k*, v*)`` for G and C. ``cast`` rounds the weights
    and each sublayer's input and output (``second_reading``)."""
    eps = float(config["layer_norm_eps"])
    lp = jax.tree.map(cast, lp)
    y = cast(_ln(x, lp["attn_norm"], lp["attn_norm_b"], eps))
    out = {}
    if kind == "M":
        mix, m, dt, memory = _ref_mamba(y, lp, config, mutate)
        out.update(m=cast(m), dt=dt, memory=cast(memory))
    elif kind in "SF":
        window = config["sliding_window"] + (mutate == "window_off_by_one")
        q, k, v = _ref_qkv(y, lp, config)
        mix = _ref_attend(q, k, v, lp["w_o"], window if kind == "S" else None)
        out.update(k=cast(k), v=cast(v))
    elif kind == "G":
        mix = (shared[0] * jax.nn.silu(y @ lp["w_1"])) @ lp["w_2"]
    else:
        q = _ref_heads(y @ lp["w_q"],
                       config["hidden_size"] // config["num_attention_heads"])
        mix = _ref_attend(q, shared[1], shared[2], lp["w_o"], None)
    mix = cast(mix)
    x = x + mix
    u = cast(_ln(x, lp["mlp_norm"], lp["mlp_norm_b"], eps))
    dense = cast(_swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return dict(out, mix=mix, dense=dense, after=x + dense)


def layers_of(params, kinds: str):
    """``(kind, the layer's parameter tree)``, first to last: the program
    keeps each decoder's periods stacked a position (``first/pos<i>``,
    ``second/pos<i>``: row ``r`` of position ``i`` is layer ``2 r + i`` of
    them) and the two producers each on its own (``memory``, ``keys``)."""
    def periods(group):
        rows = (jax.tree.leaves(group["pos0"])[0].shape[0] if group else 0)
        for row in range(rows):
            for i in range(len(group)):
                yield jax.tree.map(lambda a: a[row], group[f"pos{i}"])

    trees = (list(periods(params.get("first", {})))
             + [params["memory"], params["keys"]]
             + list(periods(params.get("second", {}))))
    return list(zip(kinds, trees))


def _ref_ce(x, params, targets, eps):
    """Mean CE of ``x (b, s, d)`` against ``targets (b, s)``, -1 = none,
    through the final LayerNorm and the table as the head; the logits a
    block of rows at a time."""
    d = x.shape[-1]
    rows = _ln(x, params["final_norm"], params["final_norm_b"], eps
               ).reshape(-1, d)
    targets = targets.reshape(-1)
    block = CE_BLOCK if rows.shape[0] % CE_BLOCK == 0 else rows.shape[0]

    def one(args):
        r, t = args
        logp = jax.nn.log_softmax(r @ params["embed"].T, axis=-1)
        gold = jnp.take_along_axis(
            logp, jnp.maximum(t, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(t >= 0, gold, 0.0))

    sums = jax.lax.map(
        one, (rows.reshape(-1, block, d), targets.reshape(-1, block)))
    return -jnp.sum(sums) / jnp.sum(targets >= 0)


def _handed(shared, kind, last_m: bool, pieces, mutate=None):
    """``shared = (m*, k*, v*)`` after a layer of ``kind`` gave
    ``pieces``."""
    m, k, v = shared
    if kind == "M" and last_m:
        m = pieces["memory"]
    if kind == ("S" if mutate == "other_keys" else "F"):
        k, v = pieces["k"], pieces["v"]
    return m, k, v


def plain_loss(params, tokens, config: dict, mutate=None):
    """The CE of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as
    it stands."""
    kinds = config["layer_kinds"]
    x = params["embed"][tokens]
    shared = (None, None, None)
    for i, (kind, lp) in enumerate(layers_of(params, kinds)):
        pieces = _ref_block(x, lp, config, kind, shared, mutate=mutate)
        shared = _handed(shared, kind, i == kinds.rindex("M"), pieces, mutate)
        x = pieces["after"]
    return _ref_ce(x, params, _shifted(tokens, 1),
                   float(config["layer_norm_eps"]))


def reference_loss(params, tokens, config: dict) -> float:
    return float(_highest(
        lambda p, t: plain_loss(_f32(p), t, config))(params, tokens))


# ---------------------------------------------------------------------------
# What a loss cannot show. At this init the CE is the tied term (``LN(e) .
# e``, harness/phi4flash_flops.py) whatever the body computes, so the loss
# check alone would pass a wrong layer: the program's pieces against the
# reference's on the seeded batch (logged outside the timed window; one
# failure makes the cell incorrect). **Each mixer is compared on its own
# output**, the program's layer given the *reference's* input to that
# layer and the reference's shared tensors (rounded to the activation
# dtype), so that a reading is one layer's error and not the chain's, nor
# a residual's of which the mixer is a ten-thousandth. The layers are
# walked one at a time and only the readings are kept.
#
# Each limit lies between two readings on the chip at the published
# widths and 16384 positions (my chip runs, PR 63; PERF.md section 2): the
# largest the bf16 program gave against the float32 reference over seven
# seeds of the cell ("bf16" below; 0, 1, 2, 2147463101, 2147463201-03), and
# what the reference itself gives against float32 when its weights and
# each sublayer's input and output are rounded to float8_e4m3fn, the
# nearest precision below the bfloat16 the configuration states
# (``second_reading``, seed 2147463301: "float8"; it fails all nineteen;
# rounded to bfloat16 the same way it reads 0.00025 / 0.0023 / 0.00019 /
# 0.0022 / 0.0033 / 0.0028 / 0.0029 / 0.0024 / 0.0023 / 0.0033 / 0.0033 /
# 0.0029 / 0.0017 / 0.0026 / 0.0041 / 0.0021 / 0.0021 / 0.0013 / 0 in the
# order below and passes every limit).
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward (both scans over periods, the producers in line): median
    # over the tokens of |program - reference| / |reference| along the
    # row. With branches of 1e-5-sigma projections this reads the table's
    # row; the pieces below read the branches. bf16: 0.00577 (0.00677
    # at depth 10); float8: 0.0925
    "hidden_rel_median": 0.02,
    # (b) the first M layer's scan output m and its step dt **over the
    # positions past 4096** (the state has forgotten its start or it has
    # not: both must agree), and the producer's m over all positions (the
    # memory every G reads). bf16: 0.00329, 0.00041, 0.00368; float8:
    # 0.0544, 0.0745, 0.0538
    "mamba_m_rel_median": 0.02,
    "mamba_dt_rel_median": 0.01,
    "memory_rel_median": 0.02,
    # (c) the attention sublayers' outputs (W_o included): the first S
    # layer's **over the queries past the window** (before them a window
    # masks nothing a causal mask leaves) and the F layer's; the first M
    # layer's mixer output (gate and W_out included). bf16: 0.00563,
    # 0.00478, 0.00481; float8: 1, 1, 1 (outputs of 1e-5-sigma
    # projections lie under float8's smallest number)
    "window_attn_rel_median": 0.03,
    "full_attn_rel_median": 0.03,
    "mamba_rel_median": 0.03,
    # (d) the layers that read another layer's tensors: the first G's
    # output on the reference's memory, the first C's on the reference's
    # k* and v*. bf16: 0.00392, 0.00423; float8: 1, 1
    "gmu_rel_median": 0.03,
    "cross_rel_median": 0.03,
    # and the same two layers' outputs **inside the side's own forward**
    # (`forward_taps`: its own wiring of memory and keys, its own chain of
    # inputs) against the reference's chain: the only readings a forward
    # that hands a reader another layer's tensors fails. bf16: 0.00684,
    # 0.00749 (0.0084, 0.0091 at depth 10); float8: 1, 1
    "wired_gmu_rel_median": 0.03,
    "wired_cross_rel_median": 0.03,
    # (e) the SwiGLU's output (the largest of the compared layers'
    # medians) and the final LayerNorm's on the reference's last residual.
    # bf16: 0.00516, 0.00166; float8: 1, 0.0265
    "dense_rel_median": 0.03,
    "final_norm_rel_median": 0.01,
    # (f) the scan's *backward*: the kernels' dx, ddt, dA, dB, dC, dD
    # against the reference recurrence's vjp, on the first M layer's
    # operands from the reference (x, B, C rounded to the activation
    # dtype, so that both sides read the same) and one seeded cotangent:
    # |program - reference| / |reference| over each whole gradient, the
    # largest of the six. bf16: 0.00167; float8: 0.159
    "sscan_grad_rel_max": 0.03,
    # (g) the shared tensors' cotangents, **the sums over their readers**:
    # dm*, dk*, dv* of the program's second decoder (its scan's constants)
    # against the vjp of the reference's layers composed in line, from
    # the reference's residual before the second decoder and one seeded
    # cotangent of the residual after it: the largest of the three
    # row-wise medians. bf16: 0.00777; float8: 1
    "shared_grad_rel_median": 0.03,
    # (h) the attention *backward*: dq, dk, dv of the flash kernels alone
    # at 40 heads of 64 on 20 (group 2), causal and under window 512,
    # against the blocked float32 reference's vjp on the reference's q,
    # k, v (rounded to the activation dtype) and one seeded cotangent: the
    # 99th percentile over the (token, head) rows, the largest of the
    # three. bf16: 0.00305, 0.00322; float8: 0.355, 0.100
    "full_attn_grad_rel_p99": 0.03,
    "window_attn_grad_rel_p99": 0.03,
    # (i) the table's gradient, **the sum of the lookup's and the
    # head's**: d CE / dE of LN(E[tokens] + r) E^T with r the rest of the
    # reference's last residual, the program's lookup, norm and fused
    # cross-entropy against the reference's, over the whole gradient.
    # bf16: 0.00325; float8: 0.545
    "table_grad_rel": 0.03,
    # (j) the CE alone against the reference's: no precision moves it, a
    # dropped term or a wrong target does (the job's own loss difference,
    # held to half the job's tolerance). bf16: 0.00083-0.00122; float8:
    # 0.174
    "ce_abs": 0.01,
}
PAST = 4096   # (b): positions from here on


def _highest(fn, **jit_args):
    """``fn`` jitted, each call traced and run at matmul precision
    "highest": the reference's own, which the program's kernels, traced
    between its calls, must not inherit."""
    jitted = jax.jit(fn, **jit_args)

    def call(*args):
        with jax.default_matmul_precision("highest"):
            return jitted(*args)

    return call


def _seeded(shape, dtype, salt: int = 0):
    return jax.random.normal(jax.random.key(salt), shape, jnp.float32
                             ).astype(dtype)


def _attention_grads(q, k, v, g, window, cast):
    _, vjp = jax.vjp(lambda q, k, v: _ref_attention_core(q, k, v, window),
                     *(cast(_f32(a)) for a in (q, k, v)))
    return tuple(cast(d) for d in vjp(cast(_f32(g))))


def _scan_grads(operands, g, cast):
    _, vjp = jax.vjp(ref_scan, *(cast(_f32(a)) for a in operands))
    return tuple(cast(d) for d in vjp(cast(_f32(g))))


def _table_grad(table, rest, params, tokens, eps, cast):
    def ce(table):
        return _ref_ce(cast(table[tokens] + rest),
                       dict(params, embed=table), _shifted(tokens, 1), eps)

    return jax.grad(ce)(cast(_f32(table)))


class _Reference:
    """The float32 reference's jitted pieces, ``cast`` applied where
    ``second_reading`` rounds, ``mutate`` where a test makes it wrong."""

    def __init__(self, config: dict, cast=None, mutate=None):
        eps = float(config["layer_norm_eps"])
        cast = cast or (lambda a: a)
        dt = _DTYPES[config["assumed"]["activation_dtype"]]
        self.config, self.mutate = config, mutate
        self.block = _highest(
            lambda x, lp, kind, shared: _ref_block(
                x, _f32(lp), config, kind, shared, cast, mutate),
            static_argnums=2)
        self.embed = _highest(lambda table, t: cast(_f32(table))[t])
        self.final_norm = _highest(lambda x, p: cast(_ln(
            x, *(cast(_f32(p[n])) for n in ("final_norm", "final_norm_b")),
            eps)))
        self.ce = _highest(lambda x, p, t: _ref_ce(
            x, jax.tree.map(lambda a: cast(_f32(a)), p), t, eps))

        def scan_operands(x, lp):
            lp = _f32(lp)
            y = _ln(x, lp["attn_norm"], lp["attn_norm_b"], eps)
            (t, step, A, B, C, D), _ = _ref_mamba_operands(y, lp, config)
            return (t.astype(dt), step, A, B.astype(dt), C.astype(dt), D)

        def qkv(x, lp):
            lp = _f32(lp)
            y = _ln(x, lp["attn_norm"], lp["attn_norm_b"], eps)
            return tuple(a.astype(dt) for a in _ref_qkv(y, lp, config))

        self.scan_operands = _highest(scan_operands)
        self.scan_grads = _highest(lambda ops, g: _scan_grads(ops, g, cast))
        self.qkv = _highest(qkv)
        self.attn_grads = _highest(
            lambda q, k, v, g, window: _attention_grads(
                q, k, v, g, window, cast), static_argnums=4)
        self.table_grad = _highest(
            lambda table, rest, p, t: _table_grad(
                table, rest, _f32(p), t, eps, cast))

        def second(x, shared, lps, g):
            """d of <g, the residual after the second decoder> in the
            shared tensors, the layers composed in line."""
            def run(m, k, v):
                h = x
                for i, lp in enumerate(lps):
                    h = jax.checkpoint(
                        lambda h, lp, m, k, v, kind="GC"[i % 2]: _ref_block(
                            h, _f32(lp), config, kind, (m, k, v), cast
                        )["after"])(h, lp, m, k, v)
                return jnp.sum(h * _f32(g))

            return tuple(cast(d) for d in jax.grad(run, argnums=(0, 1, 2))(
                *(cast(_f32(a)) for a in shared)))

        self.second_grads = _highest(second)

    def window_of(self, kind):
        return self.config["sliding_window"] if kind == "S" else None


class _Program:
    """The program's side of the comparison: each compared layer on the
    reference's input and shared tensors, its kernels alone on the
    reference's operands, its second decoder's vjp, and its own forward."""

    def __init__(self, cfg, mesh, params, tokens):
        from dlrover_tpu.models import llama, phi4flash, stack
        from dlrover_tpu.ops import embed_lookup, selective_scan
        from dlrover_tpu.ops.attention import flash_attention

        self.cfg, self.params, self.tokens = cfg, params, tokens
        dt = cfg.dtype

        def whole(params, tokens):
            hidden, mixes = phi4flash.forward_taps(params, tokens, cfg, mesh)
            return stack.next_token_loss(
                phi4flash.head_input(cfg, params, hidden),
                params["embed"].T, tokens, cfg.ce_chunk_size, mesh
            ), hidden, mixes

        def layer(lp, x, kind, shared):
            """What `compare` reads of a layer: its mixer's output, the
            feed-forward's, and of an M layer ``m``, ``dt`` and what it
            hands on."""
            x = x.astype(dt)
            shared = tuple(None if a is None else a.astype(dt)
                           for a in shared)
            y = phi4flash.norm(x, lp, "attn_norm", cfg.norm_eps)
            mix, handed = phi4flash.mixer(cfg, mesh, kind, lp, y, shared)
            u = phi4flash.norm(x + mix, lp, "mlp_norm", cfg.norm_eps)
            out = {"mix": mix, "dense": llama.swiglu(
                u, lp["w_gate"], lp["w_up"], lp["w_down"], dt)}
            if kind == "M":
                out.update(m=handed, memory=handed,
                           dt=phi4flash.mamba_operands(cfg, lp, y)[0][1])
            return out

        def scan_grads(operands, g):
            _, vjp = jax.vjp(lambda *a: selective_scan.selective_scan(
                *a, chunk=cfg.mamba_chunk, mesh=mesh), *operands)
            return vjp(g)

        def attn_grads(q, k, v, g, window):
            _, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, mesh=mesh, window=window), q, k, v)
            return vjp(g)

        def second_grads(params, x, shared, g):
            _, vjp = jax.vjp(
                lambda m, k, v: phi4flash.second_decoder(
                    cfg, mesh, params, x.astype(dt), m, k, v)[0],
                *(a.astype(dt) for a in shared))
            return vjp(g.astype(dt))

        def table_grad(table, rest, params, tokens):
            def ce(table):
                x = embed_lookup(table, tokens, mesh, dt) + rest.astype(dt)
                return stack.next_token_loss(
                    phi4flash.head_input(cfg, params, x), table.T, tokens,
                    cfg.ce_chunk_size, mesh)

            return jax.grad(ce)(table)

        self._whole = jax.jit(whole)
        self.layer = jax.jit(layer, static_argnums=2)
        self.scan_grads = jax.jit(scan_grads)
        self.attn_grads = jax.jit(attn_grads, static_argnums=4)
        self._second_grads = jax.jit(second_grads)
        self._table_grad = jax.jit(table_grad)
        self.final_norm = jax.jit(
            lambda x, p: phi4flash.head_input(cfg, p, x.astype(dt)))

    def second_grads(self, x, shared, lps, g):
        return self._second_grads(self.params, x, shared, g)

    def table_grad(self, rest):
        return self._table_grad(self.params["embed"], rest, self.params,
                                self.tokens)

    def follow(self, kind, last_m, lp):
        pass

    def whole(self):
        """``(CE, the last residual, {layer: its mixer's output})`` of the
        program's own forward: its own wiring of the shared tensors."""
        ce, hidden, mixes = self._whole(self.params, self.tokens)
        return float(ce), hidden, mixes


class _Rounded:
    """The reference with its weights and each sublayer's input and
    output rounded by ``cast`` (or made wrong by ``mutate``), as a side
    of the comparison: each compared layer on the float32 reference's
    input, beside its own chain from its own table."""

    def __init__(self, config, params, tokens, cast=None, mutate=None):
        self.ref = _Reference(config, cast, mutate)
        self.params, self.tokens = params, tokens
        self.x = self.ref.embed(params["embed"], tokens)
        self.shared = (None, None, None)
        self.mixes = []
        self.final_norm = self.ref.final_norm
        self.scan_grads = self.ref.scan_grads
        self.attn_grads = self.ref.attn_grads
        self.second_grads = self.ref.second_grads

    def layer(self, lp, x, kind, shared):
        return self.ref.block(x, lp, kind, shared)

    def table_grad(self, rest):
        return self.ref.table_grad(self.params["embed"], rest, self.params,
                                   self.tokens)

    def follow(self, kind, last_m, lp):
        pieces = self.ref.block(self.x, lp, kind, self.shared)
        self.shared = _handed(self.shared, kind, last_m, pieces,
                              self.ref.mutate)
        self.x = pieces["after"]
        # the readers' alone are read (`compare`): the others' go
        self.mixes.append(pieces["mix"] if kind in "GC" else None)

    def whole(self):
        return float(self.ref.ce(self.x, self.params, _shifted(
            self.tokens, 1))), self.x, self.mixes


def _median(a, b, rows=slice(None)):
    return float(jnp.median(_row_rel(a, b)[rows]))


def _whole_rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-30))


def compare(params, tokens, config: dict, side):
    """``(readings, the reference's CE)``: ``side`` (`_Program`, or
    `_Rounded`) against the float32 reference, a layer at a time."""
    ref = _Reference(config)
    kinds = config["layer_kinds"]
    layers = layers_of(params, kinds)
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    b, s = tokens.shape
    read, seen, wired = {}, set(), {}

    def worst(name, value):
        read[name] = max(read.get(name, value), value)

    def past(edge):
        return np.tile(np.arange(s) >= min(edge, s - 1), b)

    x = rows = ref.embed(params["embed"], tokens)
    shared = (None, None, None)
    for i, (kind, lp) in enumerate(layers):
        last_m = i == kinds.rindex("M")
        want = ref.block(x, lp, kind, shared)
        side.follow(kind, last_m, lp)
        if kind not in seen or last_m:
            got = side.layer(lp, x, kind, shared)
            worst("dense_rel_median", _median(got["dense"], want["dense"]))
            if last_m:
                read["memory_rel_median"] = _median(
                    got["memory"], want["memory"])
            if kind == "M" and kind not in seen:
                read["mamba_m_rel_median"] = _median(
                    got["m"], want["m"], past(PAST))
                read["mamba_dt_rel_median"] = _median(
                    got["dt"], want["dt"], past(PAST))
                read["mamba_rel_median"] = _median(got["mix"], want["mix"])
                operands = ref.scan_operands(x, lp)
                g = _seeded(operands[0].shape, dt)
                read["sscan_grad_rel_max"] = max(
                    _whole_rel(a, w) for a, w in zip(
                        side.scan_grads(operands, g),
                        ref.scan_grads(operands, g)))
                del operands, g
            elif kind in "SF" and kind not in seen:
                name = "window" if kind == "S" else "full"
                read[f"{name}_attn_rel_median"] = _median(
                    got["mix"], want["mix"],
                    past(config["sliding_window"] if kind == "S" else 0))
                q, k, v = ref.qkv(x, lp)
                g = _seeded(q.shape, dt)
                window = ref.window_of(kind)
                read[f"{name}_attn_grad_rel_p99"] = max(
                    float(jnp.percentile(_row_rel(a, w), 99.0))
                    for a, w in zip(side.attn_grads(q, k, v, g, window),
                                    ref.attn_grads(q, k, v, g, window)))
                del q, k, v, g
            elif kind == "G" and kind not in seen:
                read["gmu_rel_median"] = _median(got["mix"], want["mix"])
                # the second decoder begins here: the shared tensors'
                # cotangents, summed over their readers
                lps = [lp for _, lp in layers[i:]]
                g = _seeded(x.shape, dt, 1)
                read["shared_grad_rel_median"] = max(
                    _median(a, w) for a, w in zip(
                        side.second_grads(x, shared, lps, g),
                        ref.second_grads(x, shared, lps, g)))
                del lps, g
            elif kind == "C" and kind not in seen:
                read["cross_rel_median"] = _median(got["mix"], want["mix"])
            if kind in "GC" and kind not in seen:
                wired[i] = want["mix"]
            seen.add(kind)
            del got
        shared = _handed(shared, kind, last_m, want)
        x = want["after"]
        del want
    read["final_norm_rel_median"] = _median(
        side.final_norm(x, params), ref.final_norm(x, params))
    rest = x - rows
    read["table_grad_rel"] = _whole_rel(
        side.table_grad(rest),
        ref.table_grad(params["embed"], rest, params, tokens))
    ce = float(ref.ce(x, params, _shifted(tokens, 1)))
    got_ce, hidden, mixes = side.whole()
    read["hidden_rel_median"] = _median(hidden, x)
    for i, want_mix in wired.items():
        name = "gmu" if kinds[i] == "G" else "cross"
        read[f"wired_{name}_rel_median"] = _median(mixes[i], want_mix)
    read["ce_abs"] = abs(got_ce - ce)
    read["ce"] = got_ce
    read["reference_ce"] = ce
    return read, ce


def _report(what: str, read: dict) -> bool:
    ok = {name: read[name] <= limit
          for name, limit in LIMITS.items() if name in read}
    ce = (f" (CE {read['ce']:.5f} / {read['reference_ce']:.5f})"
          if "ce" in read else "")
    print(f"[phi4flash] {what}{ce}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok), flush=True)
    return all(ok.values())


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/train_loop.py`` makes from ``seed``. By hand,
    on the chip::

        python -c "import json
        from benchmarks.families import phi4flash as f
        f.second_reading(json.load(open(
            'benchmarks/configs/phi-4-mini-flash-1chip.json')), 3)"
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
    return passed
