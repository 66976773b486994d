"""The state-space scan of Mamba-2 (SSD) with **one group**: a head's state
is ``p x n`` (64 x 128 at granite-4.0-h's sizes), its decay and its step
are functions of the token, and one ``B`` and one ``C`` serve every head::

    S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t B_t^T        S_0 = 0
    y_t = S_t C_t + D_h x_t

``x (b, s, h, p)``, ``dt (b, s, h)`` float32 and positive (the softplus is
the caller's), ``A (h,)`` float32 and negative, ``B, C (b, s, n)``, ``D
(h,)``. Chunked, with ``L`` tokens a chunk, ``a = dt A``, ``G`` its
cumulative sum inside the chunk (inclusive) and ``S`` the state before the
chunk::

    y_i = sum_(j <= i) exp(G_i - G_j) (C_i . B_j) dt_j x_j        within
        + exp(G_i) S C_i + D x_i                                  across
    S'  = exp(G_L) S + sum_j exp(G_L - G_j) dt_j x_j B_j^T

Every exponent is a difference ``G_i - G_j`` with ``i >= j`` or ``G``
itself, so every factor is at most one: the form is exact for any ``dt A
<= 0`` and needs no bound on it. Neither ``ops/lightning.py`` (its decay
tables are made from static slopes, once a call) nor ``ops/kda.py``'s
per-head delta rule (a per-token ``g``, but inside the rule's solve)
computes this; what they share with it is the way a kernel walks chunks
with the state in VMEM (docs/design/kernels.md).

- `ssd`: on the TPU (and under ``interpret``) two Pallas kernels under one
  ``custom_vjp``, ``ssd_fwd`` and ``ssd_bwd``. A grid step is one chunk of
  up to 64 heads, read where it lies in the ``(b, s, h p)`` arrays. ``C
  B^T (L, L)`` is formed once a grid step for all its heads; a head then
  costs the mask ``exp(G_i - G_j)`` (its ``L^2`` exponentials) and the
  product with its ``dt x``. Heads of 64 go through the kernel two at a
  time, so that loads, stores, the products with the state and the
  state's update are 128 lanes wide. The states, float32, are VMEM
  scratch of ``(heads p, n)`` carried along the grid's last axis (from
  the end in the backward, which carries the state's cotangent and reads
  the forward's states a chunk). ``G`` is a cumulative sum XLA makes in
  float32 before the call, handed over token-major and head-major.
- Off the TPU the same chunked equations in XLA's ops (`_chunked_xla`),
  differentiated by JAX: the kernels' oracle. `recurrence` is the
  definition, a token a step (tests).

The forward names its output and the chunks' starting states (`KEPT`;
both forms do), all the backward reads beside its operands: a block whose
checkpoint keeps them never runs ``ssd_fwd`` twice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.kda import _NN, _NT, _TN, _dot, _iota, _over_batch_rows
from dlrover_tpu.parallel.mesh import BATCH_AXES

_F32 = jnp.float32
LANES = 128
#: heads a grid step at most (their blocks and states must fit VMEM)
HEADS_A_STEP = 64
_VMEM_LIMIT = 96 * 1024 * 1024

#: the forward's output and the states its chunks started from, by name
KEPT = ("ssd_out", "ssd_states")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def report_kept(name: str):
    """A ``recompute(kept=)`` callback: ``ssm.state_kept`` reads 1 once a
    block's checkpoint has met the forward's states and kept them."""
    if name == KEPT[1]:
        trace.gauge("ssm.state_kept", 1)


def chunk_sums(a, chunk: int, reverse: bool = False):
    """``a (b, s, h)``: the cumulative sum along ``s`` inside each chunk,
    inclusive (``reverse``: from the chunk's end)."""
    b, s, h = a.shape
    return lax.cumsum(a.reshape(b, s // chunk, chunk, h), axis=2,
                      reverse=reverse).reshape(b, s, h)


def _chunked_xla(x, dt, A, B, C, D, chunk: int):
    """The chunked equations as they stand; every chunk's state exists at
    once (``(s / L, b, h, p, n)`` float32) and so does every head's mask."""
    b, s, h, p = x.shape
    n, nc, dtp = B.shape[-1], s // chunk, x.dtype
    G = chunk_sums(dt * A, chunk).reshape(b, nc, chunk, h)
    x32 = x.astype(_F32).reshape(b, nc, chunk, h, p)
    xd32 = x32 * dt.reshape(b, nc, chunk, h, 1)
    Bc, Cc = (a.reshape(b, nc, chunk, n) for a in (B, C))
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc, preferred_element_type=_F32)
    pos = jnp.arange(chunk)
    under = (pos[:, None] >= pos[None, :])[None, None, :, :, None]
    mask = jnp.where(under, jnp.exp(jnp.minimum(
        G[:, :, :, None, :] - G[:, :, None, :, :], 0.0)), 0.0)
    y = jnp.einsum("bcijh,bcjhp->bcihp", (cb[..., None] * mask).astype(dtp),
                   xd32.astype(dtp), preferred_element_type=_F32)
    last = G[:, :, -1]                                     # (b, nc, h)
    xk = (xd32 * jnp.exp(last[:, :, None] - G)[..., None]).astype(dtp)
    added = jnp.einsum("bcjhp,bcjn->cbhpn", xk, Bc,
                       preferred_element_type=_F32)
    whole = jnp.exp(jnp.moveaxis(last, 1, 0))[..., None, None]

    def step(S, xs):
        whole, add = xs
        return whole * S + add, S

    _, states = lax.scan(step, jnp.zeros_like(added[0]), (whole, added))
    states = checkpoint_name(states, KEPT[1])
    across = jnp.einsum("bcin,cbhpn->bcihp", Cc, states.astype(dtp),
                        preferred_element_type=_F32)
    y = y + across * jnp.exp(G)[..., None] + D[:, None] * x32
    return checkpoint_name(y.reshape(b, s, h, p).astype(dtp), KEPT[0])


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _heads_a_step(h: int, p: int) -> int:
    """The most heads, at most ``HEADS_A_STEP``, that divide ``h`` and
    fill whole lane tiles."""
    per = max(1, LANES // p)
    return next((hb for hb in range(min(h, HEADS_A_STEP), 0, -1)
                 if h % hb == 0 and hb % per == 0), h)


def _column(ref, head):
    """``ref (1, L, hb)``: column ``head`` as ``(L, 1)``."""
    blk = ref[0]
    return jnp.sum(jnp.where(_iota(blk.shape, 1) == head, blk, 0.0),
                   axis=1, keepdims=True)


def _put_column(ref, head, col):
    """Column ``head`` of ``ref (1, L, hb)`` becomes ``col (L, 1)``."""
    blk = ref[0]
    ref[0] = jnp.where(_iota(blk.shape, 1) == head, col, blk)


def _a_head(shape, dim: int, p: int, values):
    """``shape`` float32: index ``i`` along ``dim`` reads ``values[i //
    p]`` (each broadcastable to ``shape``)."""
    out = jnp.broadcast_to(values[-1], shape)
    at = _iota(shape, dim)
    for j in range(len(values) - 2, -1, -1):
        out = jnp.where(at < (j + 1) * p, values[j], out)
    return out


def _of_head(shape, dim: int, p: int, j: int):
    """Where the index along ``dim`` is one of head ``j``'s ``p``."""
    at = _iota(shape, dim)
    return (at >= j * p) & (at < (j + 1) * p)


def _lane_tile(width: int, p: int):
    """``(heads, lanes)`` of a lane tile: as many heads of ``p`` as fill
    128 lanes where the block's width is whole tiles, else a head."""
    per = max(1, LANES // p) if width % LANES == 0 else 1
    return per, per * p


def _under(chunk: int):
    return _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)


def _head_parts(G_ref, GT_ref, head, under):
    """A head's decays inside the chunk: the mask ``exp(G_i - G_j)`` under
    the diagonal ``(L, L)``, ``exp(G)`` and ``exp(G_L - G) (L, 1)``,
    ``exp(G_L) (1, 1)``."""
    G = _column(G_ref, head)
    G_row = GT_ref[0, pl.ds(head, 1), :]                       # (1, L)
    mask = jnp.where(under, jnp.exp(jnp.minimum(G - G_row, 0.0)), 0.0)
    last = G[-1:]
    return mask, jnp.exp(G), jnp.exp(last - G), jnp.exp(last)


def _fwd_kernel(x_ref, dt_ref, G_ref, GT_ref, B_ref, C_ref, D_ref, y_ref,
                *rest, p: int, states: bool):
    st_ref, S = rest if states else (None,) + rest
    chunk, width = x_ref.shape[1:]
    per, lanes = _lane_tile(width, p)
    under = _under(chunk)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        S[...] = jnp.zeros_like(S)

    if states:
        st_ref[0, 0] = S[...]
    Bm, Cm = B_ref[0], C_ref[0]
    dtp = Bm.dtype
    cb = _dot(Cm, Bm, _NT)                                     # (L, L)

    def group(g, carry):
        at = pl.multiple_of(g * lanes, lanes)
        x32 = x_ref[0, :, pl.ds(at, lanes)].astype(_F32)       # (L, lanes)
        shape = x32.shape
        parts = [_head_parts(G_ref, GT_ref, g * per + j, under)
                 for j in range(per)]
        step = _a_head(shape, 1, p, [
            _column(dt_ref, g * per + j) for j in range(per)])
        xd32 = x32 * step
        before = S[pl.ds(at, lanes), :]                        # (lanes, n)
        y = _dot(Cm, before.astype(dtp), _NT) * _a_head(
            shape, 1, p, [part[1] for part in parts])
        y = y + D_ref[:, pl.ds(at, lanes)] * x32
        for j, (mask, _, _, _) in enumerate(parts):
            mine = xd32 if per == 1 else jnp.where(
                _of_head(shape, 1, p, j), xd32, 0.0)
            y = y + _dot((cb * mask).astype(dtp), mine.astype(dtp), _NN)
        y_ref[0, :, pl.ds(at, lanes)] = y.astype(y_ref.dtype)
        xk = (xd32 * _a_head(shape, 1, p, [part[2] for part in parts])
              ).astype(dtp)
        S[pl.ds(at, lanes), :] = before * _a_head(
            before.shape, 0, p, [part[3] for part in parts]
        ) + _dot(xk, Bm, _TN)
        return carry

    lax.fori_loop(0, width // lanes, group, 0)


def _bwd_kernel(x_ref, dt_ref, G_ref, GT_ref, B_ref, C_ref, D_ref, dy_ref,
                st_ref, dx_ref, ddt_ref, dG_ref, dGT_ref, dB_ref, dC_ref,
                dS, dcb, dB_acc, dC_acc, *, p: int):
    chunk, width = x_ref.shape[1:]
    per, lanes = _lane_tile(width, p)
    under = _under(chunk)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dS[...] = jnp.zeros_like(dS)

    Bm, Cm = B_ref[0], C_ref[0]
    dtp = Bm.dtype
    cb = _dot(Cm, Bm, _NT)
    dcb[...] = jnp.zeros_like(dcb)
    dB_acc[...] = jnp.zeros_like(dB_acc)
    dC_acc[...] = jnp.zeros_like(dC_acc)
    last_row = _iota((chunk, 1), 0) == chunk - 1

    def group(g, carry):
        at = pl.multiple_of(g * lanes, lanes)
        x32 = x_ref[0, :, pl.ds(at, lanes)].astype(_F32)
        dy = dy_ref[0, :, pl.ds(at, lanes)]
        dy32 = dy.astype(_F32)
        shape = x32.shape
        heads = [g * per + j for j in range(per)]
        parts = [_head_parts(G_ref, GT_ref, head, under) for head in heads]
        mine = [_of_head(shape, 1, p, j) for j in range(per)]
        step = _a_head(shape, 1, p, [_column(dt_ref, head) for head in heads])
        xd32 = x32 * step
        xd = xd32.astype(dtp)
        before = st_ref[0, 0, pl.ds(at, lanes), :]             # (lanes, n)
        after = dS[pl.ds(at, lanes), :]        # d of the state it wrote
        before_d, after_d = before.astype(dtp), after.astype(dtp)
        to_end = _a_head(shape, 1, p, [part[2] for part in parts])
        xk32 = xd32 * to_end
        # what the state after the chunk hands back to dt x
        z = _dot(Bm, after_d, _NT)                             # (L, lanes)
        dxd = z * to_end
        # the chunk's read of the state before it
        q = _dot(Cm, before_d, _NT)                            # (L, lanes)
        dye32 = dy32 * _a_head(shape, 1, p, [part[1] for part in parts])
        dye = dye32.astype(dtp)
        dC_acc[...] += _dot(dye, before_d, _NN)
        dB_acc[...] += _dot(xk32.astype(dtp), after_d, _NN)
        read, wrote = dye32 * q, xk32 * z
        for j, (mask, _, _, whole) in enumerate(parts):
            dy_j = dy if per == 1 else jnp.where(mine[j], dy,
                                                 jnp.zeros_like(dy))
            w32 = cb * mask
            dw = _dot(dy_j, xd, _NT)                           # (L, L)
            dcb[...] += dw * mask
            pairs = dw * w32
            dxd = dxd + _dot(w32.astype(dtp), dy_j, _TN)
            kept = jnp.sum(jnp.where(mine[j], wrote, 0.0), axis=1,
                           keepdims=True)
            at_end = jnp.sum(kept) + whole * jnp.sum(jnp.where(
                _of_head(before.shape, 0, p, j), after * before, 0.0))
            dG = (jnp.sum(pairs, axis=1, keepdims=True) - kept
                  + jnp.sum(jnp.where(mine[j], read, 0.0), axis=1,
                            keepdims=True)
                  + jnp.where(last_row, at_end, 0.0))
            _put_column(dG_ref, heads[j], dG)
            dGT_ref[0, pl.ds(heads[j], 1), :] = -jnp.sum(
                pairs, axis=0, keepdims=True)
        for j, head in enumerate(heads):
            _put_column(ddt_ref, head, jnp.sum(jnp.where(
                mine[j], dxd * x32, 0.0), axis=1, keepdims=True))
        dx_ref[0, :, pl.ds(at, lanes)] = (
            dxd * step + D_ref[:, pl.ds(at, lanes)] * dy32
        ).astype(dx_ref.dtype)
        dS[pl.ds(at, lanes), :] = after * _a_head(
            after.shape, 0, p, [part[3] for part in parts]
        ) + _dot(dye, Cm, _TN)
        return carry

    lax.fori_loop(0, width // lanes, group, 0)
    total = dcb[...].astype(dtp)
    dC_ref[0, 0] = dC_acc[...] + _dot(total, Bm, _NN)
    dB_ref[0, 0] = dB_acc[...] + _dot(total, Cm, _TN)


def _operands(x, dt, A, B, C, D, chunk: int):
    """What both kernels read: ``x`` wide, ``dt``, the cumulative decay
    token-major and head-major, ``B``, ``C``, ``D`` a lane (``dt``, ``A`` and ``D`` are float32: `ssd`
    widened them)."""
    b, s, h, p = x.shape
    G = chunk_sums(dt * A, chunk)
    return (x.reshape(b, s, h * p), dt, G, jnp.swapaxes(G, 1, 2), B, C,
            jnp.repeat(D, p)[None, :])


def _call(kernel, name, operands, extra, extra_specs, out, scratch, *,
          h, p, chunk, backwards, interpret):
    """The grid is (batch, head groups, chunks), the last axis in order
    (from the end where ``backwards``): it carries the state. ``extra_specs``
    and ``out`` name kinds of block (``specs`` below), ``out`` with a dtype
    a result; ``scratch(width, n)`` gives the float32 scratch shapes."""
    b, s, width = operands[0].shape
    n, nc = operands[4].shape[-1], s // chunk
    hb = _heads_a_step(h, p)
    wb = hb * p

    def at(ci):
        return nc - 1 - ci if backwards else ci

    specs = {
        "wide": pl.BlockSpec((1, chunk, wb), lambda bi, gi, ci: (bi, at(ci), gi)),
        "heads": pl.BlockSpec((1, chunk, hb), lambda bi, gi, ci: (bi, at(ci), gi)),
        "heads_t": pl.BlockSpec((1, hb, chunk), lambda bi, gi, ci: (bi, gi, at(ci))),
        "state": pl.BlockSpec((1, chunk, n), lambda bi, gi, ci: (bi, at(ci), 0)),
        "lane": pl.BlockSpec((1, wb), lambda bi, gi, ci: (0, gi)),
        "states": pl.BlockSpec((1, 1, wb, n),
                               lambda bi, gi, ci: (bi, at(ci), gi, 0)),
        "sum": pl.BlockSpec((1, 1, chunk, n),
                            lambda bi, gi, ci: (bi, gi, at(ci), 0)),
    }
    shapes = {
        "wide": (b, s, width), "heads": (b, s, h), "heads_t": (b, h, s),
        "states": (b, nc, width, n), "sum": (b, h // hb, s, n),
    }
    in_specs = [specs[k] for k in ("wide", "heads", "heads", "heads_t",
                                   "state", "state", "lane")]
    return pl.pallas_call(
        functools.partial(kernel, p=p),
        grid=(b, h // hb, nc),
        in_specs=in_specs + [specs[k] for k in extra_specs],
        out_specs=[specs[k] for k, _ in out],
        out_shape=[jax.ShapeDtypeStruct(shapes[k], dtype) for k, dtype in out],
        scratch_shapes=[pltpu.VMEM(shape, _F32) for shape in scratch(wb, n)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*operands, *extra)


@functools.partial(jax.jit, static_argnums=(6, 7, 8), inline=True)
def _forward(x, dt, A, B, C, D, chunk, interpret, states: bool):
    b, s, h, p = x.shape
    out = _call(
        functools.partial(_fwd_kernel, states=states), "ssd_fwd",
        _operands(x, dt, A, B, C, D, chunk), (), (),
        [("wide", x.dtype)] + [("states", _F32)] * states,
        lambda wb, n: [(wb, n)], h=h, p=p, chunk=chunk, backwards=False,
        interpret=interpret)
    return (out[0].reshape(b, s, h, p),) + tuple(out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernels(x, dt, A, B, C, D, chunk, interpret):
    return _forward(x, dt, A, B, C, D, chunk, interpret, False)[0]


def _kernels_fwd(x, dt, A, B, C, D, chunk, interpret):
    y, states = _forward(x, dt, A, B, C, D, chunk, interpret, True)
    y = checkpoint_name(y, KEPT[0])
    return y, (x, dt, A, B, C, D, checkpoint_name(states, KEPT[1]))


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _kernels_bwd(chunk, interpret, res, dy):
    x, dt, A, B, C, D, states = res
    b, s, h, p = x.shape
    n = B.shape[-1]
    # a custom_vjp's backward is traced outside the caller's scopes: the
    # device metrics find the op by this one
    with trace.scope("ssm_chunk"):
        dy = dy.astype(x.dtype)
        dx, ddt, dG, dGT, dB, dC = _call(
            _bwd_kernel, "ssd_bwd", _operands(x, dt, A, B, C, D, chunk),
            (dy.reshape(b, s, h * p), states), ("wide", "states"),
            [("wide", x.dtype), ("heads", _F32), ("heads", _F32),
             ("heads_t", _F32), ("sum", _F32), ("sum", _F32)],
            lambda wb, n: [(wb, n), (chunk, chunk), (chunk, n), (chunk, n)],
            h=h, p=p, chunk=chunk, backwards=True, interpret=interpret)
        da = chunk_sums(dG + jnp.swapaxes(dGT, 1, 2), chunk, reverse=True)
        dD = jnp.einsum("bshp,bshp->h", dy, x, preferred_element_type=_F32)
    return (dx.reshape(b, s, h, p), ddt + da * A,
            jnp.sum(da * dt, axis=(0, 1)),
            jnp.sum(dB, axis=1).astype(B.dtype),
            jnp.sum(dC, axis=1).astype(C.dtype), dD)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kernels_fit(h: int, p: int, n: int, chunk: int) -> bool:
    """Whether the kernels take these sizes on the chip: whole lane tiles
    of heads, of the state's width and of the chunk."""
    return ((_heads_a_step(h, p) * p) % LANES == 0 and n % LANES == 0
            and chunk % LANES == 0 and (LANES % p == 0 or p % LANES == 0))


def ssd(x, dt, A, B, C, D, *, chunk: int = 256, interpret: bool = False,
        mesh: Optional[Mesh] = None):
    """``x (b, s, h, p)``, ``dt (b, s, h)`` float32 (the step, already
    positive), ``A (h,)`` float32 (negative), ``B, C (b, s, n)`` in ``x``'s
    dtype, ``D (h,)`` -> ``y (b, s, h, p)`` in ``x``'s dtype,
    differentiable in all six. A sequence that ``chunk`` does not divide
    is padded with tokens whose step is zero, which leave the state as it
    is. ``mesh``: over more than one device the kernels run under
    ``shard_map`` on each device's batch rows. The gauges ``ssm.kernel``
    and ``ssm.chunk`` say which form the traced step took."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, -(-s // 8) * 8)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    dt, A, D = (a.astype(_F32) for a in (dt, A, D))
    trace.gauge("ssm.chunk", chunk)
    if interpret or (_on_tpu() and kernels_fit(h, p, n, chunk)):
        trace.gauge("ssm.kernel", 1)
        y = _over_batch_rows(
            lambda x, dt, B, C, A, D: _kernels(x, dt, A, B, C, D, chunk,
                                               interpret),
            mesh, (x, dt, B, C), (A, D), P(BATCH_AXES, None, None, None))
    else:
        trace.gauge("ssm.kernel", 0)
        y = _chunked_xla(x, dt, A, B, C, D, chunk)
    return y[:, :s] if pad else y


def recurrence(x, dt, A, B, C, D):
    """The definition, a token a step (tests): float32."""
    A, D = A.astype(_F32), D.astype(_F32)

    def step(S, inp):
        x, dt, B, C = inp                  # (b, h, p), (b, h), (b, n) x 2
        S = (jnp.exp(dt * A)[..., None, None] * S
             + (dt[..., None] * x)[..., None] * B[:, None, None, :])
        return S, jnp.einsum("bhpn,bn->bhp", S, C) + D[:, None] * x

    b, _, h, p = x.shape
    _, y = lax.scan(step, jnp.zeros((b, h, p, B.shape[-1]), _F32), tuple(
        jnp.moveaxis(a.astype(_F32), 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)
