"""The ``xing4`` family's stream mixing as four Pallas passes.

A sublayer ``F`` of that family sits between a pre-mix of the ``n``
residual streams ``X (n, b, s, d)`` to one and a post + res-mix back to
``n`` (``models/xing4.py``'s docstring has the equations). Each of the
four places where a sublayer touches the streams is one pass here that
reads every ``(b, s, d)`` slab once and writes every result once,
float32 from the load to the one store:

1. ``hc_pre_fwd`` reads X (n slabs) and writes ``y`` (1): one read gives
   a token's sum of squares, its product with ``phi`` (the MXU, the
   operands as stored, float32 accumulation), ``H_pre`` and ``y``. It
   also writes ``raw = (vec(X) phi) / rms`` and ``1 / rms`` a token.
2. ``hc_post_fwd`` reads X and ``z`` (n + 1) and writes X' (n).
3. ``hc_post_bwd`` reads dX', X and ``z`` (2n + 1), writes ``dz`` (1)
   and a token's ``n (n + 1)`` row-dot-products ``<dX'[i], X[j]>``,
   ``<dX'[i], z>``.
4. ``hc_pre_bwd`` reads ``dy``, X and dX' (2n + 1) and writes dX (n):
   the res-mix's transpose, the pre-mix's, the ``phi`` product's and the
   rms's in one store, and ``phi``'s gradient summed over the row blocks.

That is the schedule ``benchmarks/harness/xing4_flops.py
hc_mix_bytes_per_step`` counts: 3n + 2 slabs forward, 5n + 3 backward.
What is a few floats a token stays XLA's, on tokens-minor ``(k, b, s)``
arrays (``coefficients``: sigmoid, clamp, exp, the Sinkhorn rounds, and
their autodiff).

**Small arrays.** Every per-token array of a pass is ``(b, s, WIDTH)``
float32, tokens on the sublanes, with one column layout: ``[0, n)`` pre,
``[n, 2n)`` post, ``[2n, 2n + n n)`` res (row ``i`` then column ``j``),
column ``n (n + 2)`` the token's ``1 / rms`` where the array is ``raw``.

**Two ``custom_vjp``s a sublayer, one private convention between them.**
``_pre`` (passes 1 and 4, the coefficients between) also hands the
streams on as a result, and ``_post`` (passes 2 and 3) takes them from
there: ``_post``'s backward returns dX' *as it came* for them, and
``_pre``'s backward, their only producer, applies the res-mix's
transpose while it writes dX. So no pass writes a partial dX that
another would read again and add. Neither is differentiable alone;
``sublayer`` is what callers get. The residuals are the passes' own
inputs (X, ``z``) and ``raw``.

A block of ``ROWS`` tokens by the whole width is a grid step (the rms
and the ``phi`` product need a token's whole ``vec(X)``); inside, the
vector work walks ``GROUP`` rows at a time across the lanes, so a
token's coefficients are broadcast once and stay in registers while its
channels stream by; the MXU products take the block's rows at once.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.kda import (
    _NN, _NT, _TN, _dot, _iota, _lane_sum, _like, _sigmoid)
from dlrover_tpu.parallel.mesh import BATCH_AXES

_F32 = jnp.float32

WIDTH = 128    # columns of a per-token array: a whole register's lanes
GROUP = 16     # rows of a kernel's inner step: one bf16 tile
LANES = 128    # lanes of it
# tokens a grid step; pass 4 holds a float32 (n, rows, d) product besides
ROWS = {"hc_pre_fwd": 256, "hc_post_fwd": 128, "hc_post_bwd": 128,
        "hc_pre_bwd": 64}
CHUNKS = 4     # chunks of LANES lanes an iteration of a kernel's lane loop
_SUM_ROWS = 32  # rows of phi's gradient a stream: n (n + 2), whole tiles
_VMEM_LIMIT = 64 * 2**20


class Static(NamedTuple):
    """What a sublayer's passes are built for, besides shapes."""
    norm_eps: float               # the rms's
    clamp: Tuple[float, float]    # on the res logits, before exp
    iters: int                    # Sinkhorn rounds
    eps: float                    # in each of their denominators
    interpret: bool


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused(interpret: bool, d: int) -> bool:
    """Whether the passes run: on the TPU where a stream's channels are
    whole lanes (or in interpret mode, for the CPU's numerics tests)."""
    on = interpret or (_on_tpu() and d % LANES == 0)
    # under two names: a log's `gauges:` line prints by prefix, the
    # xing4 cell's `hc.` (its job is `train_loop`; `mtp_ms`'s reader
    # prints the line), `finetune_loop`'s `layers.`
    trace.gauge("layers.hc_fused", int(on))
    trace.gauge("hc.fused", int(on))
    return on


# ---------------------------------------------------------------------------
# What stays XLA's: a token's coefficients from its ``raw``
# ---------------------------------------------------------------------------

def sinkhorn(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """``m (n, n, ...)`` positive -> doubly stochastic over its first two
    axes: ``iters`` times, columns to sum one, then rows."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def coefficients(raw, alpha, bias, n: int, clamp, iters: int, eps: float):
    """``raw (n (n + 2), b, s)`` float32, tokens minor -> ``H_pre (n, b,
    s)``, ``H_post (n, b, s)``, ``H_res (n, n, b, s)``."""
    lo, hi = clamp
    alpha = alpha.astype(_F32)
    bias = bias.astype(_F32)[:, None, None]
    pre = alpha[0] * raw[:n] + bias[:n]
    post = alpha[1] * raw[n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * raw[2 * n:] + bias[2 * n:]).reshape(
        (n, n) + raw.shape[1:])
    h_res = sinkhorn(jnp.exp(jnp.clip(res, lo, hi)), iters, eps)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def _post_res(raw, alpha, bias, n, hp):
    """The passes' ``(b, s, WIDTH)`` ``raw`` -> ``H`` in the same layout,
    its post and res columns filled (the passes form ``H_pre``
    themselves, from the same ``raw``). The tokens are laid out as whole
    registers, ``(k, tokens / 128, 128)``, where they divide: the
    Sinkhorn's sums over ``n`` are then adds of whole registers, where
    a ``(k, 2, 4096)`` array pads its 2 rows to 8 and is copied into
    another layout between a sum over rows and one over columns."""
    k = n * (n + 2)
    b, s, _ = raw.shape
    rows = jnp.moveaxis(raw[..., :k], -1, 0)
    if (b * s) % LANES == 0:
        rows = rows.reshape(k, -1, LANES)
    _, h_post, h_res = coefficients(
        rows, alpha, bias, n, hp.clamp, hp.iters, hp.eps)
    cols = jnp.concatenate(
        [h_post, h_res.reshape((n * n,) + h_post.shape[1:])]).reshape(
            k - n, b, s)
    return jnp.pad(jnp.moveaxis(cols, 0, -1),
                   ((0, 0), (0, 0), (n, WIDTH - k)))


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _group_rows(g):
    return pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)


def _column(h, k):
    """Column ``k`` of ``h (GROUP, WIDTH)`` on every lane."""
    return jnp.broadcast_to(h[:, k:k + 1], (GROUP, LANES))


def _into_columns(sums):
    """``{k: (GROUP, 1)}`` -> ``(GROUP, WIDTH)``, zero elsewhere."""
    lane = _iota((GROUP, WIDTH), 1)
    out = jnp.zeros((GROUP, WIDTH), _F32)
    for k, value in sums.items():
        out = jnp.where(lane == k, value, out)
    return out


def _h_pre(raw, aff_ref):
    """``sigmoid(alpha_0 raw + b)`` in the pre columns of ``raw (GROUP,
    WIDTH)``; ``aff_ref (2, WIDTH)``: ``alpha_0`` there and zero
    elsewhere, the bias likewise."""
    return _sigmoid(aff_ref[0:1, :] * raw + aff_ref[1:2, :])


def _over_lanes(d, body, carry=0):
    """``body(lanes, carry) -> carry`` over a row's chunks of ``LANES``
    lanes, in order. A loop of four chunks an iteration, not 28 chunks
    written out: a kernel's body is traced once a place it is called
    from (twelve times a step build), and written out the four bodies
    cost the build 7 s. (Mosaic unrolls a loop wholly or not at all, so
    the four are written out here.)"""
    count = d // LANES
    per = math.gcd(count, CHUNKS)

    def chunks(c, carry):
        for i in range(per):
            at = pl.multiple_of((c * per + i) * LANES, LANES)
            carry = body(pl.ds(at, LANES), carry)
        return carry

    return lax.fori_loop(0, count // per, chunks, carry)


def _f32(ref, *at):
    return ref[at].astype(_F32)


def _zeros(keys):
    return {k: jnp.zeros((GROUP, LANES), _F32) for k in keys}


def _pre_fwd_kernel(x_ref, phit_ref, aff_ref, y_ref, raw_ref, *, n, eps):
    rows, d = y_ref.shape
    k = n * (n + 2)
    # the product with phi, the block's rows at once; staged in the result
    p = _dot(x_ref[0], phit_ref[0], _NT)
    for j in range(1, n):
        p = p + _dot(x_ref[j], phit_ref[j], _NT)
    raw_ref[...] = p

    def group(g, carry):
        at = _group_rows(g)

        def squares(lanes, sq):
            for j in range(n):
                x = _f32(x_ref, j, at, lanes)
                sq = sq + x * x
            return sq

        sq = _over_lanes(d, squares, jnp.zeros((GROUP, LANES), _F32))
        inv = lax.rsqrt(_lane_sum(sq) * (1.0 / (n * d)) + eps)
        raw = raw_ref[at, :] * inv
        raw_ref[at, :] = jnp.where(_iota((GROUP, WIDTH), 1) == k, inv, raw)
        h = _h_pre(raw, aff_ref)
        pre = [_column(h, j) for j in range(n)]

        def mix(lanes, carry):
            y = pre[0] * _f32(x_ref, 0, at, lanes)
            for j in range(1, n):
                y = y + pre[j] * _f32(x_ref, j, at, lanes)
            y_ref[at, lanes] = y.astype(y_ref.dtype)
            return carry

        _over_lanes(d, mix)
        return carry

    lax.fori_loop(0, rows // GROUP, group, 0)


def _post_fwd_kernel(h_ref, x_ref, z_ref, out_ref, *, n):
    rows, d = z_ref.shape

    def group(g, carry):
        at = _group_rows(g)
        h = h_ref[at, :]
        post = [_column(h, n + i) for i in range(n)]
        res = [[_column(h, 2 * n + i * n + j) for j in range(n)]
               for i in range(n)]

        def mix(lanes, carry):
            x = [_f32(x_ref, j, at, lanes) for j in range(n)]
            z = _f32(z_ref, at, lanes)
            for i in range(n):
                acc = res[i][0] * x[0]
                for j in range(1, n):
                    acc = acc + res[i][j] * x[j]
                out_ref[i, at, lanes] = (acc + post[i] * z).astype(
                    out_ref.dtype)
            return carry

        _over_lanes(d, mix)
        return carry

    lax.fori_loop(0, rows // GROUP, group, 0)


def _post_bwd_kernel(dxp_ref, x_ref, z_ref, h_ref, dz_ref, dh_ref, *, n):
    rows, d = z_ref.shape

    def group(g, carry):
        at = _group_rows(g)
        h = h_ref[at, :]
        post = [_column(h, n + i) for i in range(n)]

        def back(lanes, sums):
            dxp = [_f32(dxp_ref, i, at, lanes) for i in range(n)]
            x = [_f32(x_ref, j, at, lanes) for j in range(n)]
            z = _f32(z_ref, at, lanes)
            dz = post[0] * dxp[0]
            for i in range(1, n):
                dz = dz + post[i] * dxp[i]
            dz_ref[at, lanes] = dz.astype(dz_ref.dtype)
            sums = dict(sums)
            for i in range(n):
                sums[n + i] = sums[n + i] + dxp[i] * z
                for j in range(n):
                    k = 2 * n + i * n + j
                    sums[k] = sums[k] + dxp[i] * x[j]
            return sums

        sums = _over_lanes(d, back, _zeros(range(n, n * (n + 2))))
        dh_ref[at, :] = _into_columns(
            {k: _lane_sum(v) for k, v in sums.items()})
        return carry

    lax.fori_loop(0, rows // GROUP, group, 0)


def _pre_bwd_kernel(dy_ref, x_ref, dxp_ref, h_ref, raw_ref, draw_ref,
                    phit_ref, aff_ref, dx_ref, dpre_ref, dphit_ref,
                    dp_ref, c_ref, g_ref, *, n):
    """``h_ref``: the post and res columns XLA formed; ``raw_ref``: pass
    1's; ``draw_ref``: the cotangent of ``raw``'s post and res columns
    from XLA's small backward. ``dpre_ref``: the cotangent of the pre
    columns' ``alpha_0 raw + b`` (``alpha_0``'s and the bias's gradients
    are its sums, outside). Scratch: ``dp_ref (rows, WIDTH)`` the
    cotangent of ``vec(X) phi``, ``c_ref (rows, LANES)`` the rms's
    factor on a token's own values, ``g_ref (n, rows, d)`` the ``phi``
    product's transpose."""
    rows, d = dy_ref.shape
    k = n * (n + 2)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)

    def coefficients_back(g, carry):
        at = _group_rows(g)

        def dots(lanes, sums):
            dy = _f32(dy_ref, at, lanes)
            return {j: sums[j] + dy * _f32(x_ref, j, at, lanes)
                    for j in range(n)}

        sums = _over_lanes(d, dots, _zeros(range(n)))
        raw = raw_ref[at, :]
        inv = _lane_sum(jnp.where(_iota((GROUP, WIDTH), 1) == k, raw, 0.0))
        h = _h_pre(raw, aff_ref)
        # zero outside the pre columns, as the dot-products are
        dpre = _into_columns(
            {j: _lane_sum(v) for j, v in sums.items()}) * h * (1.0 - h)
        dpre_ref[at, :] = dpre
        d_raw = draw_ref[at, :] + aff_ref[0:1, :] * dpre
        dp_ref[at, :] = d_raw * inv
        # raw = p inv, inv = (mean x^2 + eps)^-1/2: d inv / d x = -inv^3 x / (n d)
        c_ref[at, :] = jnp.broadcast_to(
            _lane_sum(d_raw * raw) * (inv * inv) * (-1.0 / (n * d)),
            (GROUP, LANES))
        return carry

    lax.fori_loop(0, rows // GROUP, coefficients_back, 0)

    dp = dp_ref[...].astype(x_ref.dtype)
    for j in range(n):
        g_ref[j] = _dot(dp, phit_ref[j], _NN)
        dphit_ref[j] += _dot(dp[:, :dphit_ref.shape[1]], x_ref[j], _TN)

    def streams_back(g, carry):
        at = _group_rows(g)
        h = h_ref[at, :]
        h_pre = _h_pre(raw_ref[at, :], aff_ref)
        pre = [_column(h_pre, j) for j in range(n)]
        res = [[_column(h, 2 * n + i * n + j) for j in range(n)]
               for i in range(n)]
        own = c_ref[at, :]

        def back(lanes, carry):
            dxp = [_f32(dxp_ref, i, at, lanes) for i in range(n)]
            dy = _f32(dy_ref, at, lanes)
            for j in range(n):
                acc = res[0][j] * dxp[0]
                for i in range(1, n):
                    acc = acc + res[i][j] * dxp[i]
                acc = (acc + pre[j] * dy + g_ref[j, at, lanes]
                       + own * _f32(x_ref, j, at, lanes))
                dx_ref[j, at, lanes] = acc.astype(dx_ref.dtype)
            return carry

        _over_lanes(d, back)
        return carry

    lax.fori_loop(0, rows // GROUP, streams_back, 0)


# ---------------------------------------------------------------------------
# The calls
# ---------------------------------------------------------------------------

def _call(kernel, name, ins, outs, scratch=(), *, rows, interpret):
    """A pass over whole row blocks. ``ins`` and ``outs``: ``(kind,
    array or its shape)``; ``kind`` is the block a grid step takes:
    "streams", ``rows`` tokens of ``(n, b, s, d)``; "rows", the same of
    a ``(b, s, width)`` array; "whole", all of a small array, which a
    kernel may add to block by block where it is a result (so both grid
    axes are ``arbitrary``). Grid (batch, row blocks), in order."""
    n, b, s, d = next(x.shape for kind, x in ins if kind == "streams")

    def spec(kind, x):
        if kind == "streams":
            return pl.BlockSpec((n, None, rows, d),
                                lambda bi, ti: (0, bi, ti, 0))
        if kind == "rows":
            return pl.BlockSpec((None, rows, x.shape[-1]),
                                lambda bi, ti: (bi, ti, 0))
        return pl.BlockSpec(x.shape, lambda bi, ti: (0,) * len(x.shape))

    return pl.pallas_call(
        kernel,
        grid=(b, s // rows),
        in_specs=[spec(*x) for x in ins],
        out_specs=[spec(*x) for x in outs],
        out_shape=[x for _, x in outs],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*(x for _, x in ins))


def _blocks(name: str, s: int) -> Tuple[int, int]:
    """``(rows a grid step, the tokens to pad the sequence by)``."""
    rows = min(ROWS[name], -(-s // GROUP) * GROUP)
    return rows, -s % rows


def _pad(x, by: int):
    """Whole row blocks: zeros after the sequence (its axis is the last
    but one), which mix to zeros and add nothing to a sum."""
    if not by:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, by), (0, 0)))


def _phi_rows(phi, dtype):
    """``(n, d, k)`` -> ``(n, WIDTH, d)`` in the streams' dtype: a
    coefficient is a row of lanes."""
    return jnp.pad(jnp.swapaxes(phi.astype(dtype), 1, 2),
                   ((0, 0), (0, WIDTH - phi.shape[-1]), (0, 0)))


def _affine(alpha, bias, n):
    """``(2, WIDTH)`` float32: ``alpha_0`` in the pre columns and zero
    elsewhere; the bias likewise."""
    scale = jnp.broadcast_to(alpha.astype(_F32)[0], (n,))
    return jnp.pad(jnp.stack([scale, bias.astype(_F32)[:n]]),
                   ((0, 0), (0, WIDTH - n)))


@functools.partial(jax.jit, static_argnums=(4,), inline=True)
def _pre_forward(X, phi, alpha, bias, hp):
    n, b, s, d = X.shape
    with trace.scope("hc_mix"):
        rows, by = _blocks("hc_pre_fwd", s)
        Xp = _pad(X, by)
        y, raw = _call(
            functools.partial(_pre_fwd_kernel, n=n, eps=hp.norm_eps),
            "hc_pre_fwd",
            [("streams", Xp), ("whole", _phi_rows(phi, X.dtype)),
             ("whole", _affine(alpha, bias, n))],
            [("rows", jax.ShapeDtypeStruct((b, s + by, d), X.dtype)),
             ("rows", jax.ShapeDtypeStruct((b, s + by, WIDTH), _F32))],
            rows=rows, interpret=hp.interpret)
        y, raw = y[:, :s], raw[:, :s]
    with trace.scope("hc_coeff"):
        return y, raw, _post_res(raw, alpha, bias, n, hp)


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _pre_backward(hp, res, cts):
    X, phi, alpha, bias, raw = res
    dy, dH, dXp = cts
    n, b, s, d = X.shape
    k = n * (n + 2)
    # a custom_vjp's backward is traced outside the caller's scopes
    with trace.scope("hc_coeff"):
        H, small_back = jax.vjp(
            lambda raw, alpha, bias: _post_res(raw, alpha, bias, n, hp),
            raw, alpha, bias)
        d_raw, dalpha, dbias = small_back(dH)
    with trace.scope("hc_mix"):
        rows, by = _blocks("hc_pre_bwd", s)
        Xp = _pad(X, by)
        phit = _phi_rows(phi, X.dtype)
        dX, dpre, dphit = _call(
            functools.partial(_pre_bwd_kernel, n=n), "hc_pre_bwd",
            [("rows", _pad(dy, by)), ("streams", Xp),
             ("streams", _pad(dXp, by)), ("rows", _pad(H, by)),
             ("rows", _pad(raw, by)), ("rows", _pad(d_raw, by)),
             ("whole", phit), ("whole", _affine(alpha, bias, n))],
            [("streams", _like(Xp)),
             ("rows", jax.ShapeDtypeStruct((b, s + by, WIDTH), _F32)),
             ("whole", jax.ShapeDtypeStruct((n, _SUM_ROWS, d), _F32))],
            [pltpu.VMEM((rows, WIDTH), _F32), pltpu.VMEM((rows, LANES), _F32),
             pltpu.VMEM((n, rows, d), _F32)],
            rows=rows, interpret=hp.interpret)
        dX = dX[:, :, :s]
    with trace.scope("hc_coeff"):
        dpre = dpre[:, :s, :n]
        dalpha = dalpha.at[0].add(
            jnp.sum(dpre * raw[..., :n]).astype(dalpha.dtype))
        dbias = dbias.at[:n].add(
            jnp.sum(dpre, axis=(0, 1)).astype(dbias.dtype))
        dphi = jnp.swapaxes(dphit[:, :k], 1, 2)
    return dX, dphi.astype(phi.dtype), dalpha, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(X, phi, alpha, bias, hp):
    """-> ``y (b, s, d)``, ``H (b, s, WIDTH)`` (post and res columns),
    and the streams again, for ``_post`` alone (the module docstring's
    convention)."""
    y, _, H = _pre_forward(X, phi, alpha, bias, hp)
    return y, H, X


def _pre_fwd(X, phi, alpha, bias, hp):
    y, raw, H = _pre_forward(X, phi, alpha, bias, hp)
    return (y, H, X), (X, phi, alpha, bias, raw)


_pre.defvjp(_pre_fwd, _pre_backward)


@functools.partial(jax.jit, static_argnums=(3,), inline=True)
def _post_forward(H, X, z, interpret):
    n, b, s, d = X.shape
    with trace.scope("hc_mix"):
        rows, by = _blocks("hc_post_fwd", s)
        Xp = _pad(X, by)
        out, = _call(
            functools.partial(_post_fwd_kernel, n=n), "hc_post_fwd",
            [("rows", _pad(H, by)), ("streams", Xp), ("rows", _pad(z, by))],
            [("streams", _like(Xp))], rows=rows, interpret=interpret)
        return out[:, :, :s]


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _post_backward(interpret, res, dXp):
    H, X, z = res
    n, b, s, d = X.shape
    with trace.scope("hc_mix"):
        rows, by = _blocks("hc_post_bwd", s)
        zp, Hp = _pad(z, by), _pad(H, by)
        dz, dH = _call(
            functools.partial(_post_bwd_kernel, n=n), "hc_post_bwd",
            [("streams", _pad(dXp, by)), ("streams", _pad(X, by)),
             ("rows", zp), ("rows", Hp)],
            [("rows", _like(zp)), ("rows", _like(Hp))],
            rows=rows, interpret=interpret)
    # the streams' cotangent as it came: `_pre`'s backward mixes it
    return dH[:, :s], dXp, dz[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(H, X, z, interpret):
    return _post_forward(H, X, z, interpret)


def _post_fwd(H, X, z, interpret):
    return _post_forward(H, X, z, interpret), (H, X, z)


_post.defvjp(_post_fwd, _post_backward)


def _over_batch_rows(fn, mesh, in_specs, out_specs):
    """``fn``; over a mesh of more than one device under ``shard_map``
    on each device's batch rows (the compiler does not partition a
    Mosaic kernel), what has the spec ``P()`` whole on each."""
    if mesh is None or mesh.size == 1:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def sublayer(X, phi, alpha, bias, fn, *, norm_eps: float,
             clamp: Tuple[float, float], iters: int, eps: float,
             interpret: bool = False, mesh: Optional[Mesh] = None):
    """One sublayer ``fn`` (b, s, d) -> (b, s, d) between its pre-mix and
    its post + res-mix, through the four passes: ``X (n, b, s, d)``,
    ``phi (n, d, n (n + 2))``, ``alpha (3,)``, ``bias (n (n + 2),)`` ->
    X' in X's dtype. ``norm_eps``: the rms's; ``clamp``, ``iters``,
    ``eps``: the Sinkhorn's. ``mesh``: the mesh the caller's jit
    partitions over (dp, fsdp and ep: batch rows); ``phi``, ``alpha``
    and ``bias`` are whole on every device and their gradients summed
    over them. Call it where ``fused`` says so."""
    hp = Static(norm_eps, tuple(clamp), iters, eps, interpret)
    streams = P(None, BATCH_AXES, None, None)
    wide = P(BATCH_AXES, None, None)
    y, H, Xr = _over_batch_rows(
        lambda X, phi, alpha, bias: _pre(X, phi, alpha, bias, hp), mesh,
        (streams, P(), P(), P()), (wide, wide, streams))(X, phi, alpha, bias)
    return _over_batch_rows(
        lambda H, Xr, z: _post(H, Xr, z, interpret), mesh,
        (wide, streams, wide), streams)(H, Xr, fn(y))
