"""The selective scan of Mamba-1: a channel's state is ``n`` numbers (16 at
the published sizes), and its decay is a function of the token, **of the
channel and of the state**::

    S_t[c, :] = exp(dt_t[c] A[c, :]) * S_(t-1)[c, :] + dt_t[c] x_t[c] B_t[:]
    y_t[c]    = S_t[c, :] . C_t + D[c] x_t[c]                      S_0 = 0

``x (b, s, c)``, ``dt (b, s, c)`` float32 and positive (the softplus is the
caller's), ``A (c, n)`` float32 and negative, ``B, C (b, s, n)``, ``D
(c,)``. ``ops/ssd.py`` (Mamba-2) has one decay a head, so its within-chunk
sum factors into ``(C_i . B_j) x mask(i, j)`` and runs on the MXU; here the
decay between two tokens is ``exp(A[c, n] (G_i[c] - G_j[c]))``, another
number for every ``(c, n)``, and nothing factors: the recurrence is walked
a token at a time, on the vector and transcendental units, and the kernels
exist to keep the ``(c, n)`` state out of HBM (``(s, c, n)`` in float32 is
5.4 GB a layer at 16384 tokens of 5120 channels).

- `selective_scan`: on the TPU (and under ``interpret``) two Pallas kernels
  under one ``custom_vjp``, ``sscan_fwd`` and ``sscan_bwd``. A grid step is
  one chunk of ``L`` tokens of up to 512 channels. **The state lies ``(n,
  128)``: states on sublanes, channels on lanes**, two float32 registers a
  lane tile, carried in registers through the chunk's tokens and in VMEM
  scratch along the grid's last axis. A token's ``dt`` and ``dt x`` are
  rows broadcast over the sublanes; its ``B`` and ``C`` are wanted down
  the sublanes and alike in every lane, which no cheap in-kernel move
  gives, so XLA hands them over as ``(b, s, n, 128)`` (a fortieth of ``(s,
  c, n)`` at 5120 channels, in the activations' dtype). ``y``'s sum over
  the states is a sublane reduction. The backward walks the chunks from
  the end: it recomputes a chunk's ``L`` states forward from the chunk's
  starting state (kept by the forward) into VMEM, then walks the tokens
  back with the state's cotangent in registers. ``dB`` and ``dC`` sum
  over channels (lanes): a token's products are summed over the step's
  lane tiles into VMEM, and reduced over the lanes once a chunk.
- Off the TPU the same chunks in XLA's ops (`_chunked_xla`: an
  associative scan inside a chunk, a scan over the chunks), differentiated
  by JAX: the kernels' oracle. `recurrence` is the definition (tests).

Every factor is ``exp`` of ``dt A <= 0`` or a product of such: exact for
any ``dt A <= 0``, no rescaling by ``exp(-A T)`` that could overflow. The
forward names its output and the chunks' starting states (`KEPT`), all the
backward reads beside its operands: a block whose checkpoint keeps them
never runs ``sscan_fwd`` twice.
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
from dlrover_tpu.ops.kda import _iota, _over_batch_rows
from dlrover_tpu.parallel.mesh import BATCH_AXES

_F32 = jnp.float32
LANES = 128
SUBLANES = 8
#: lane tiles a grid step at most: their states, the states' cotangents
#: and a token's rows must stay in registers
TILES_A_STEP = 4
_VMEM_LIMIT = 96 * 1024 * 1024

#: the forward's output and the states its chunks started from, by name
KEPT = ("sscan_out", "sscan_states")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def report_kept(name: str):
    """A ``recompute(kept=)`` callback: ``mamba.state_kept`` reads 1 once
    a block's checkpoint has met the forward's states and kept them."""
    if name == KEPT[1]:
        trace.gauge("mamba.state_kept", 1)


def _chunked_xla(x, dt, A, B, C, D, chunk: int):
    """Chunk by chunk: inside a chunk an associative scan over its tokens'
    ``(decay, input)`` pairs; a scan over the chunks carries the state and
    leaves each chunk's starting state, from which a second walk makes
    ``y``. A chunk's ``(b, L, c, n)`` exists at once, the sequence's never."""
    b, s, c = x.shape
    n, nc = B.shape[-1], s // chunk
    x32 = x.astype(_F32)

    def chunks(a):
        return jnp.moveaxis(a.astype(_F32).reshape(b, nc, chunk, -1), 1, 0)

    def combine(first, then):
        return first[0] * then[0], then[0] * first[1] + then[1]

    def within(S, dt, u, B):
        """The states after each of a chunk's tokens: ``(b, L, c, n)``."""
        decay = jnp.exp(dt[..., None] * A)
        mult, add = lax.associative_scan(
            combine, (decay, u[..., None] * B[:, :, None, :]), axis=1)
        return mult * S[:, None] + add

    dt_c, u_c, B_c, C_c = chunks(dt), chunks(x32 * dt), chunks(B), chunks(C)
    _, states = lax.scan(
        lambda S, xs: (within(S, *xs)[:, -1], S),
        jnp.zeros((b, c, n), _F32), (dt_c, u_c, B_c))
    states = checkpoint_name(states, KEPT[1])
    y = lax.map(lambda xs: jnp.einsum(
        "blcn,bln->blc", within(*xs[:4]), xs[4]),
        (states, dt_c, u_c, B_c, C_c))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, c) + D * x32
    return checkpoint_name(y.astype(x.dtype), KEPT[0])


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _channels_a_step(c: int) -> int:
    """The most whole lane tiles, at most ``TILES_A_STEP``, that divide
    ``c`` channels."""
    return next(k * LANES for k in range(TILES_A_STEP, 0, -1)
                if c % (k * LANES) == 0)


def _tile(k: int):
    return pl.ds(k * LANES, LANES)


def _put_row(tile, j: int, row):
    """``tile (8, 128)`` with its row ``j`` become ``row (1, 128)``."""
    return jnp.where(_iota(tile.shape, 0) == j, row, tile)


def _over_states(a):
    """``a (n, 128)`` summed over the states: ``(1, 128)``."""
    return jnp.sum(a, axis=0, keepdims=True)


def _walk_forward(dt_ref, u_s, At_ref, Bb_ref, starts, begin, visit, end):
    """The chunk's tokens first to last, eight a trip; returns the states
    after the chunk. ``starts``: a ``(n, 128)`` state a lane tile.
    ``begin(i)`` opens trip ``i``'s context, ``visit(t, j, k, S, ctx)``
    takes the state after token ``t = 8 i + j`` of lane tile ``k`` and
    returns the context, ``end(rows, ctx)`` closes the trip."""
    chunk, width = u_s.shape
    tiles = width // LANES
    At = [At_ref[:, _tile(k)] for k in range(tiles)]

    def trip(i, S):
        S = list(S)
        r0 = pl.multiple_of(i * SUBLANES, SUBLANES)
        rows = pl.ds(r0, SUBLANES)
        dt8 = [dt_ref[0, rows, _tile(k)] for k in range(tiles)]
        u8 = [u_s[rows, _tile(k)] for k in range(tiles)]
        ctx = begin(i)
        for j in range(SUBLANES):
            Bt = Bb_ref[0, r0 + j].astype(_F32)                # (n, 128)
            for k in range(tiles):
                decay = jnp.exp(dt8[k][j:j + 1, :] * At[k])
                S[k] = decay * S[k] + u8[k][j:j + 1, :] * Bt
                ctx = visit(r0 + j, j, k, S[k], ctx)
        end(rows, ctx)
        return tuple(S)

    return lax.fori_loop(0, chunk // SUBLANES, trip, tuple(starts))


def _fwd_kernel(x_ref, dt_ref, At_ref, Bb_ref, Cb_ref, D_ref, y_ref, *rest,
                states: bool):
    st_ref, S, u_s, y_s = rest if states else (None,) + rest
    width = u_s.shape[1]
    tiles = width // LANES

    @pl.when(pl.program_id(2) == 0)
    def _start():
        S[...] = jnp.zeros_like(S)

    if states:
        st_ref[0, 0] = S[...]
    u_s[...] = x_ref[0].astype(_F32) * dt_ref[0]

    def begin(i):
        return [jnp.zeros((SUBLANES, LANES), _F32)] * tiles, None

    def visit(t, j, k, S_k, ctx):
        y8, Ct = ctx
        if k == 0:
            Ct = Cb_ref[0, t].astype(_F32)
        y8 = list(y8)
        y8[k] = _put_row(y8[k], j, _over_states(S_k * Ct))
        return y8, Ct

    def end(rows, ctx):
        for k, tile in enumerate(ctx[0]):
            y_s[rows, _tile(k)] = tile

    after = _walk_forward(dt_ref, u_s, At_ref, Bb_ref,
                          [S[:, _tile(k)] for k in range(tiles)],
                          begin, visit, end)
    for k in range(tiles):
        S[:, _tile(k)] = after[k]
    y_ref[0] = (y_s[...] + D_ref[...] * x_ref[0].astype(_F32)
                ).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, At_ref, Bb_ref, Cb_ref, D_ref, dy_ref, st_ref,
                dx_ref, ddt_ref, dAt_ref, dB_ref, dC_ref,
                dS, u_s, dy_s, r1_s, r2_s, S_s, accB, accC):
    chunk, width = u_s.shape
    tiles = width // LANES
    n = At_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dS[...] = jnp.zeros_like(dS)
        dAt_ref[...] = jnp.zeros_like(dAt_ref)

    u_s[...] = x_ref[0].astype(_F32) * dt_ref[0]
    dy_s[...] = dy_ref[0].astype(_F32)

    def state_rows(t):
        """Where ``S_s`` holds the state *before* token ``t``."""
        return pl.ds(pl.multiple_of(t * n, n), n)

    # the chunk's states again, from the state it started with
    S_s[pl.ds(0, n), :] = st_ref[0, 0]

    def visit(t, j, k, S_k, ctx):
        S_s[state_rows(t + 1), _tile(k)] = S_k
        return ctx

    _walk_forward(dt_ref, u_s, At_ref, Bb_ref,
                  [st_ref[0, 0, :, _tile(k)] for k in range(tiles)],
                  lambda i: None, visit, lambda rows, ctx: None)

    # the tokens back, the state's cotangent in registers
    At = [At_ref[:, _tile(k)] for k in range(tiles)]

    def trip(ii, carry):
        dSs, dAs = (list(a) for a in carry)
        r0 = pl.multiple_of((chunk // SUBLANES - 1 - ii) * SUBLANES, SUBLANES)
        rows = pl.ds(r0, SUBLANES)
        dt8 = [dt_ref[0, rows, _tile(k)] for k in range(tiles)]
        u8 = [u_s[rows, _tile(k)] for k in range(tiles)]
        dy8 = [dy_s[rows, _tile(k)] for k in range(tiles)]
        zero = jnp.zeros((SUBLANES, LANES), _F32)
        r1, r2 = [zero] * tiles, [zero] * tiles
        for j in range(SUBLANES - 1, -1, -1):
            t = r0 + j
            Bt = Bb_ref[0, t].astype(_F32)
            Ct = Cb_ref[0, t].astype(_F32)
            toB = toC = None
            for k in range(tiles):
                step, dy = dt8[k][j:j + 1, :], dy8[k][j:j + 1, :]
                before = S_s[state_rows(t), _tile(k)]
                after = S_s[state_rows(t + 1), _tile(k)]
                decay = jnp.exp(step * At[k])
                d = dSs[k] + dy * Ct          # d of the state after token t
                mine = after * dy
                toC = mine if toC is None else toC + mine
                mine = d * u8[k][j:j + 1, :]
                toB = mine if toB is None else toB + mine
                r1[k] = _put_row(r1[k], j, _over_states(d * Bt))
                through = d * before * decay  # d of log(decay)
                r2[k] = _put_row(r2[k], j, _over_states(through * At[k]))
                dAs[k] = dAs[k] + through * step
                dSs[k] = d * decay
            accB[state_rows(t), :] = toB
            accC[state_rows(t), :] = toC
        for k in range(tiles):
            r1_s[rows, _tile(k)] = r1[k]
            r2_s[rows, _tile(k)] = r2[k]
        return tuple(dSs), tuple(dAs)

    dSs, dAs = lax.fori_loop(
        0, chunk // SUBLANES, trip,
        (tuple(dS[:, _tile(k)] for k in range(tiles)),
         tuple(jnp.zeros((n, LANES), _F32) for _ in range(tiles))))
    for k in range(tiles):
        dS[:, _tile(k)] = dSs[k]
        dAt_ref[0, :, _tile(k)] += dAs[k]
    x32 = x_ref[0].astype(_F32)
    ddt_ref[0] = r2_s[...] + r1_s[...] * x32
    dx_ref[0] = (r1_s[...] * dt_ref[0] + D_ref[...] * dy_s[...]
                 ).astype(dx_ref.dtype)
    # over the lanes, once a chunk: state ``m`` of every token is a row
    # in ``n`` of the accumulators
    lane = _iota((chunk, LANES), 1)
    for acc, out_ref in ((accB, dB_ref), (accC, dC_ref)):
        out = jnp.zeros((chunk, LANES), _F32)
        for m in range(n):
            of_state = acc[pl.ds(m, chunk, stride=n), :]       # (L, 128)
            out = jnp.where(lane == m, jnp.sum(
                of_state, axis=1, keepdims=True), out)
        out_ref[0, 0] = out


def _operands(x, dt, A, B, C, D):
    """What both kernels read: ``A`` with the states on sublanes, ``B``
    and ``C`` down the sublanes and alike in every lane, ``D`` a row."""
    def lanes(a):
        return jnp.broadcast_to(a.astype(x.dtype)[..., None],
                                a.shape + (LANES,))

    return x, dt, A.T, lanes(B), lanes(C), D[None, :]


def _call(kernel, name, operands, extra, extra_specs, out, scratch, *,
          chunk, backwards, interpret):
    """The grid is (batch, channel blocks, chunks), the last axis in order
    (from the end where ``backwards``): it carries the state."""
    b, s, c = operands[0].shape
    n, nc = operands[2].shape[0], s // chunk
    cb = _channels_a_step(c)

    def at(ci):
        return nc - 1 - ci if backwards else ci

    specs = {
        "wide": pl.BlockSpec((1, chunk, cb), lambda bi, gi, ci: (bi, at(ci), gi)),
        "A": pl.BlockSpec((n, cb), lambda bi, gi, ci: (0, gi)),
        "state": pl.BlockSpec((1, chunk, n, LANES),
                              lambda bi, gi, ci: (bi, at(ci), 0, 0)),
        "lane": pl.BlockSpec((1, cb), lambda bi, gi, ci: (0, gi)),
        "states": pl.BlockSpec((1, 1, n, cb),
                               lambda bi, gi, ci: (bi, at(ci), 0, gi)),
        "dA": pl.BlockSpec((1, n, cb), lambda bi, gi, ci: (bi, 0, gi)),
        "sum": pl.BlockSpec((1, 1, chunk, LANES),
                            lambda bi, gi, ci: (bi, gi, at(ci), 0)),
    }
    shapes = {
        "wide": (b, s, c), "states": (b, nc, n, c), "dA": (b, n, c),
        "sum": (b, c // cb, s, LANES),
    }
    in_specs = [specs[k] for k in ("wide", "wide", "A", "state", "state",
                                   "lane")]
    return pl.pallas_call(
        kernel,
        grid=(b, c // cb, nc),
        in_specs=in_specs + [specs[k] for k in extra_specs],
        out_specs=[specs[k] for k, _ in out],
        out_shape=[jax.ShapeDtypeStruct(shapes[k], dtype) for k, dtype in out],
        scratch_shapes=[pltpu.VMEM(shape, _F32)
                        for shape in scratch(chunk, cb, n)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(*operands, *extra)


@functools.partial(jax.jit, static_argnums=(6, 7, 8), inline=True)
def _forward(x, dt, A, B, C, D, chunk, interpret, states: bool):
    return _call(
        functools.partial(_fwd_kernel, states=states), "sscan_fwd",
        _operands(x, dt, A, B, C, D), (), (),
        [("wide", x.dtype)] + [("states", _F32)] * states,
        lambda L, cb, n: [(n, cb), (L, cb), (L, cb)],
        chunk=chunk, backwards=False, interpret=interpret)


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
    n = B.shape[-1]
    # a custom_vjp's backward is traced outside the caller's scopes: the
    # device metrics find the op by this one
    with trace.scope("mamba_scan"):
        dy = dy.astype(x.dtype)
        dx, ddt, dAt, dB, dC = _call(
            _bwd_kernel, "sscan_bwd", _operands(x, dt, A, B, C, D),
            (dy, states), ("wide", "states"),
            [("wide", x.dtype), ("wide", _F32), ("dA", _F32),
             ("sum", _F32), ("sum", _F32)],
            lambda L, cb, n: [(n, cb)] + [(L, cb)] * 4 + [
                ((L + 1) * n, cb), (L * n, LANES), (L * n, LANES)],
            chunk=chunk, backwards=True, interpret=interpret)
        dD = jnp.einsum("bsc,bsc->c", dy, x, preferred_element_type=_F32)
    return (dx, ddt, jnp.sum(dAt, axis=0).T,
            jnp.sum(dB, axis=1)[..., :n].astype(B.dtype),
            jnp.sum(dC, axis=1)[..., :n].astype(C.dtype), dD)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kernels_fit(c: int, n: int, chunk: int) -> bool:
    """Whether the kernels take these sizes: whole lane tiles of channels,
    whole sublane tiles of states and of a chunk's tokens, the states of
    a token within the lanes that ``dB`` and ``dC`` come back on."""
    return (c % LANES == 0 and n % SUBLANES == 0 and n <= LANES
            and chunk % SUBLANES == 0)


def selective_scan(x, dt, A, B, C, D, *, chunk: int = 256,
                   interpret: bool = False, mesh: Optional[Mesh] = None):
    """``x (b, s, c)``, ``dt (b, s, c)`` float32 (the step, already
    positive), ``A (c, n)`` float32 (negative), ``B, C (b, s, n)`` in
    ``x``'s dtype, ``D (c,)`` -> ``y (b, s, c)`` in ``x``'s dtype,
    differentiable in all six. A sequence that ``chunk`` does not divide
    is padded with tokens whose step is zero, which leave the state as it
    is. ``mesh``: over more than one device the kernels run under
    ``shard_map`` on each device's batch rows. The gauges ``mamba.kernel``
    and ``mamba.chunk`` say which form the traced step took."""
    b, s, c = x.shape
    n = B.shape[-1]
    chunk = min(chunk, -(-s // SUBLANES) * SUBLANES)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                       for a in (x, dt, B, C))
    dt, A, D = (a.astype(_F32) for a in (dt, A, D))
    B, C = B.astype(x.dtype), C.astype(x.dtype)
    trace.gauge("mamba.chunk", chunk)
    if (interpret or _on_tpu()) and kernels_fit(c, n, chunk):
        trace.gauge("mamba.kernel", 1)
        y = _over_batch_rows(
            lambda x, dt, B, C, A, D: _kernels(x, dt, A, B, C, D, chunk,
                                               interpret),
            mesh, (x, dt, B, C), (A, D), P(BATCH_AXES, None, None))
    else:
        trace.gauge("mamba.kernel", 0)
        y = _chunked_xla(x, dt, A, B, C, D, chunk)
    return y[:, :s] if pad else y


def recurrence(x, dt, A, B, C, D):
    """The definition, a token a step (tests): float32."""
    A, D = A.astype(_F32), D.astype(_F32)

    def step(S, inp):
        x, dt, B, C = inp                  # (b, c), (b, c), (b, n) x 2
        S = (jnp.exp(dt[..., None] * A) * S
             + (dt * x)[..., None] * B[:, None, :])
        return S, jnp.einsum("bcn,bn->bc", S, C) + D * x

    b, _, c = x.shape
    _, y = lax.scan(step, jnp.zeros((b, c, B.shape[-1]), _F32), tuple(
        jnp.moveaxis(a.astype(_F32), 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)
