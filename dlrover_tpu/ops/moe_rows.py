"""The expert layer's row movements, bound by the live count: Pallas TPU
kernels for ``models/moe.py``'s ``dispatch_rows`` and ``combine_rows``
(forward and backward) and the pass between the grouped products,
``act(gate) x up`` (`gated_rows`), forward and backward.

``models/moe.py`` sorts the ``n = t * k`` (token, choice) pairs by expert
and keeps every array at its worst-case ``(n, d)``; a chip that holds
some of the experts its router scores reads only the first ``live =
sum(group_sizes)`` sorted rows. XLA's gathers move all ``n`` (and sum a
token's ``k`` through a ``(t, k, d)`` layout that costs more than the
gather). The kernels here move the rows below ``live`` and no others;
``live`` stays on the device and reaches them as a scalar-prefetch
operand, so shapes stay static and nothing is dropped.

- **token order** (`token_sums`: combine's forward, and dispatch's
  backward with weights of one): the grid walks blocks of tokens; a
  block fetches the rows of its live pairs alone and adds a token's in
  float32 in choice order, one store in the rows' dtype; a token with no
  live pair stores zeros. The live pairs of a block reach the kernel as
  a compacted list (`_live_pairs`: a ``cumsum`` of the live mask inside
  the block and one fused compare-and-reduce, no scatter and no second
  sort), so the scalar loop is as long as the live count.
- **sorted order** (`sorted_cotangents`: combine's backward): the grid
  walks blocks of sorted rows. A block whose first row is past ``live``
  is not visited: its ``index_map``s stay on the last visited block, so
  nothing is fetched for it and nothing stored, and its rows of the
  outputs keep whatever the buffer held. The block that holds row
  ``live`` is written whole, zeros from ``live`` on (so is block 0 when
  ``live`` is 0): a tile the grouped matmul's backward fetches holds no
  unwritten row (it masks them by position, so it would not need it).
  One fetch of the cotangent's row gives ``d_rows = g_row x weight`` and
  ``d_weights = <rows, g_row>`` in float32.
- **the pass between the products** (`gated_rows`,
  `gated_rows_cotangents`: PR 42): blocks of sorted rows of the ``(n,
  f)`` arrays up to the one that holds row ``live``, on a grid as long
  (a traced grid dimension), float32 from the load to the one store,
  zeros from ``live`` to the block's end; ``silu`` and ``relu``. The
  backward writes its two results over two of its operands.
- **a row fetch.** Mosaic slices a tiled HBM operand by whole tiles of 8
  rows, so a row comes with the 7 beside it: one contiguous DMA of ``(8,
  d)`` from the operand left in ``pl.ANY``, ``_WINDOW`` of them in
  flight. The row is then read from VMEM as float32: a bf16 row through
  the 32-bit view of its row pair (even rows in the low half), a float32
  row as it is. Indices and weights come in by block through SMEM. That
  is 50-85 ns a row on the v5e by width (the 32-56 KB of a fetch at
  about 690 GB/s), slower than XLA's gather of whole rows (8-13 ns) and
  a little faster than its weighted sums (70-90 ns over every row):
  the kernels win by the rows they skip.
- **dispatch's forward** (`gathered_rows`: PR 53): blocks of sorted
  rows up to the one that holds row ``live``, on a grid as long; a row
  is fetched as above and stored as it is (exact). XLA's gather of
  whole rows runs at the HBM's rate while its operand can be staged in
  VMEM, so the kernel wins by the rows it skips and only past a share
  of dead rows that `gather_pays` reads off the shapes
  (docs/design/kernels.md 1c).

Off the TPU, for a dtype or shape the kernels do not take, and as their
oracle in tests/test_moe_rows.py, ``models/moe.py`` keeps XLA's ops.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.grouped_matmul import _divisors

#: Rows a DMA moves: the tile of a 2-d operand in HBM (module docstring).
_GROUP = 8
#: Row fetches in flight. Both are powers of two: a row's index is masked
#: and shifted where `//` and `%` would each trace to a dozen scalar
#: operations (signs of a floor division no index here can have), a
#: fifth of a call's time and most of the body's equations.
_WINDOW = 16
#: Most sorted rows a grid step of `sorted_cotangents` and most tokens a grid
#: step of `token_sums`.
_MAX_SORTED_BLOCK = 256
_MAX_TOKEN_BLOCK = 128
_VMEM_LIMIT = 64 * 2**20

_F32 = jnp.float32


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def row_blocks(t: int, k: int, d: int, dtype, *, interpret: bool = False
               ) -> Optional[Tuple[int, int]]:
    """``(sorted rows, tokens)`` a grid step for ``t`` tokens of ``k``
    choices and width ``d``, or None where XLA's gathers run: off the
    TPU (unless ``interpret``), for another dtype than bfloat16 or
    float32, and for shapes no block divides (256 and 128 at the
    cells' shapes; the sorted block is chosen as ``ops/
    grouped_matmul.py`` chooses its row tile)."""
    if not (interpret or _on_tpu()):
        return None
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return None
    itemsize = jnp.dtype(dtype).itemsize
    rows = _divisors(t * k, _MAX_SORTED_BLOCK, 8 * 4 // itemsize)
    tokens = _divisors(t, _MAX_TOKEN_BLOCK, _GROUP)
    if d % 128 or not rows or not tokens:
        return None
    return rows[0], tokens[0]


#: What `gather_pays` weighs, GB/s on the v5e (docs/design/kernels.md
#: 1c): the row fetches' rate over the 8 rows a fetch moves, and XLA's
#: whole gather while its operand can be staged in VMEM and from the
#: size on where it cannot (128 MiB gathers from HBM; 80 is staged).
_FETCH_RATE, _STAGED_RATE, _UNSTAGED_RATE = 630.0, 650.0, 130.0
_UNSTAGED_BYTES = 128 * 2**20


def gather_pays(t: int, d: int, dtype, share: float) -> bool:
    """Whether `gathered_rows` over the held ``share`` of the sorted rows
    of ``t`` tokens of width ``d`` is faster than XLA's gather of every
    row: three calls a layer (the rows are not kept for gate's and up's
    d-rhs: ``models/moe.py`` `_experts`) of 8-row fetches against two at
    the rate the tokens' size allows."""
    staged = t * d * jnp.dtype(dtype).itemsize < _UNSTAGED_BYTES
    whole = _STAGED_RATE if staged else _UNSTAGED_RATE
    return 3 * share * _GROUP / _FETCH_RATE < 2 / whole


def _row_f32(stage, slot, sub):
    """Row ``sub`` of the ``(8, d)`` group in ``stage[slot]`` as ``(1,
    d)`` float32 (exact: a bf16 is the high half of its float32)."""
    if stage.dtype == _F32:
        return stage[slot, pl.ds(sub, 1), :]
    word = stage.bitcast(jnp.uint32)[slot, pl.ds(sub >> 1, 1), :]
    bits = lax.select(jnp.broadcast_to(sub & 1 == 1, word.shape),
                      word & jnp.uint32(0xFFFF0000), word << 16)
    return pltpu.bitcast(bits, _F32)


def _fetch(src_ref, stage, sems, row, i):
    """The DMA of the group that holds ``src[row]`` into slot ``i %
    _WINDOW``."""
    start = pl.multiple_of(row & -_GROUP, _GROUP)
    slot = i & (_WINDOW - 1)
    return pltpu.make_async_copy(
        src_ref.at[pl.ds(start, _GROUP)], stage.at[slot], sems.at[slot])


def _walk(count, row_of, src_ref, stage, sems, use):
    """``use(i, row (1, d) float32)`` for ``i < count``, the fetch of
    ``src[row_of(i)]`` running ``_WINDOW`` ahead."""
    def start(i, _):
        _fetch(src_ref, stage, sems, row_of(i), i).start()
        return 0

    lax.fori_loop(0, jnp.minimum(count, _WINDOW), start, 0)

    def body(i, _):
        at = row_of(i)
        _fetch(src_ref, stage, sems, at, i).wait()
        row = _row_f32(stage, i & (_WINDOW - 1), at & (_GROUP - 1))

        @pl.when(i + _WINDOW < count)
        def _():
            _fetch(src_ref, stage, sems, row_of(i + _WINDOW),
                   i + _WINDOW).start()

        use(i, row)
        return 0

    lax.fori_loop(0, count, body, 0)


# ---------------------------------------------------------------------------
# sorted order: combine's two cotangents over the live blocks
# ---------------------------------------------------------------------------

def _cotangents_kernel(live_ref, tok_ref, w_ref, g_ref, rows_ref,
                       d_rows_ref, dw_ref, stage, picked, w_col, sems, *,
                       block: int):
    live = live_ref[0]
    first = pl.program_id(0) * block

    @pl.when(first <= live)
    def _visit():
        def use(i, row):
            picked[pl.ds(i, 1), :] = row
            w_col[pl.ds(i, 1), :] = jnp.full((1, 128), w_ref[0, i])

        _walk(jnp.clip(live - first, 0, block), lambda i: tok_ref[0, i],
              g_ref, stage, sems, use)
        below = first + lax.broadcasted_iota(
            jnp.int32, (block, 128), 0) < live
        w = w_col[...]
        zeros = jnp.zeros((block, 128), _F32)

        # a lane block at a time: the weights' tile serves every block,
        # and the rows' products add on the VPU before one lane sum
        def lane_block(c, dots):
            lanes = pl.ds(pl.multiple_of(c * 128, 128), 128)
            g = picked[:, lanes]
            d_rows_ref[:, lanes] = lax.select(below, g * w, zeros).astype(
                d_rows_ref.dtype)
            return dots + lax.select(
                below, rows_ref[:, lanes].astype(_F32) * g, zeros)

        dots = lax.fori_loop(0, picked.shape[1] // 128, lane_block, zeros)
        # (block, 1) sums to the (1, block) row the output keeps
        sums = jnp.sum(dots, axis=1, keepdims=True)
        dw_ref[...] = jnp.broadcast_to(sums, (block, 128)).T[0:1, :]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sorted_cotangents(g, tok, rows, weights, live, *, block: int,
                      interpret: bool = False):
    """Combine's backward in sorted order. ``g (t, d)`` (the output's
    cotangent), ``tok (n,)`` int32 (the token of each sorted row),
    ``rows (n, d)``, ``weights (n,)`` float32 (in sorted order), ``live
    ()`` int32 -> ``(d_rows (n, d), d_weights (n,) float32)``: for ``r <
    live``, ``d_rows[r] = g[tok[r]] x weights[r]`` (float32, rounded
    once) and ``d_weights[r] = <rows[r], g[tok[r]]>`` (float32; 128
    partial sums a row, then one sum over them). Both are zero from
    ``live`` to the end of the block that holds row ``live``; later
    blocks are neither read nor written."""
    n, d = rows.shape
    blocks = n // block
    live = live.reshape(1).astype(jnp.int32)

    def at(b, live):
        return jnp.minimum(b, jnp.minimum(live[0] // block, blocks - 1))

    tile = pl.BlockSpec((block, d), lambda b, live: (at(b, live), 0))
    line = lambda space: pl.BlockSpec(
        (None, 1, block), lambda b, live: (at(b, live), 0, 0),
        memory_space=space)
    d_rows, d_weights = pl.pallas_call(
        functools.partial(_cotangents_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[line(pltpu.SMEM), line(pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY), tile],
            out_specs=[tile, line(pltpu.VMEM)],
            scratch_shapes=[pltpu.VMEM((_WINDOW, _GROUP, d), g.dtype),
                            pltpu.VMEM((block, d), _F32),
                            pltpu.VMEM((block, 128), _F32),
                            pltpu.SemaphoreType.DMA((_WINDOW,))]),
        out_shape=[jax.ShapeDtypeStruct((n, d), rows.dtype),
                   jax.ShapeDtypeStruct((blocks, 1, block), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_cotangents",
    )(live, tok.reshape(blocks, 1, block),
      weights.reshape(blocks, 1, block), g, rows)
    return d_rows, d_weights.reshape(n)


# ---------------------------------------------------------------------------
# sorted order: dispatch's forward, the live blocks' rows and no others
# ---------------------------------------------------------------------------

def _gathered_kernel(live_ref, tok_ref, src_ref, out_ref, stage, picked,
                     sems):
    def use(i, row):
        picked[pl.ds(i, 1), :] = row

    _walk(out_ref.shape[0], lambda i: tok_ref[0, i], src_ref, stage, sems,
          use)
    out_ref[...] = picked[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gathered_rows(yt, tok, live, *, block: int, interpret: bool = False):
    """Dispatch's forward in sorted order. ``yt (t, d)``, ``tok (n,)``
    int32 (the token of each sorted row), ``live ()`` int32 -> ``(n,
    d)``: row ``r`` is ``yt[tok[r]]``, exactly, for every ``r`` up to the
    end of the block that holds row ``live`` (so a tile the grouped
    products straddle there holds real rows, which they mask by
    position); later blocks are neither read nor written. The grid's
    length is counted on the device."""
    n, d = tok.shape[0], yt.shape[1]
    blocks = n // block
    live = live.reshape(1).astype(jnp.int32)
    return pl.pallas_call(
        _gathered_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.minimum(live[0] // block + 1, blocks),),
            in_specs=[pl.BlockSpec((None, 1, block),
                                   lambda b, live: (b, 0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda b, live: (b, 0)),
            scratch_shapes=[pltpu.VMEM((_WINDOW, _GROUP, d), yt.dtype),
                            pltpu.VMEM((block, d), _F32),
                            pltpu.SemaphoreType.DMA((_WINDOW,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), yt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_gathered",
    )(live, tok.reshape(blocks, 1, block), yt)


# ---------------------------------------------------------------------------
# between the products: act(gate) x up and its backward over the live blocks
# ---------------------------------------------------------------------------

def _act(name: str, g):
    """``(act(g), act'(g))`` of a float32 tile: ``silu`` or ``relu``
    (the sigmoid through tanh, as ``ops/kda.py``'s passes form it)."""
    if name == "relu":
        return jnp.maximum(g, 0.0), (g > 0.0).astype(_F32)
    sig = 0.5 * jnp.tanh(0.5 * g) + 0.5
    return g * sig, sig * (1.0 + g * (1.0 - sig))


def _below(live_ref, shape):
    """Which rows of this grid step's block are below the live count."""
    rows = pl.program_id(0) * shape[0] + lax.broadcasted_iota(
        jnp.int32, shape, 0)
    return rows < live_ref[0]


def _gated_kernel(live_ref, gate_ref, up_ref, out_ref, *, act: str):
    hidden, _ = _act(act, gate_ref[...].astype(_F32))
    out_ref[...] = jnp.where(
        _below(live_ref, out_ref.shape), hidden * up_ref[...].astype(_F32),
        0.0).astype(out_ref.dtype)


def _gated_bwd_kernel(live_ref, gate_ref, up_ref, g_ref, d_gate_ref,
                      d_up_ref, *, act: str):
    below = _below(live_ref, g_ref.shape)
    hidden, slope = _act(act, gate_ref[...].astype(_F32))
    g = g_ref[...].astype(_F32)
    d_gate_ref[...] = jnp.where(
        below, g * up_ref[...].astype(_F32) * slope, 0.0).astype(
            d_gate_ref.dtype)
    d_up_ref[...] = jnp.where(below, g * hidden, 0.0).astype(d_up_ref.dtype)


def _live_blocks_call(kernel, name, live, operands, n_out, *, block: int,
                      interpret: bool, in_place: bool = False):
    """``kernel`` over the blocks of ``block`` rows of the ``(n, f)``
    ``operands`` up to the one that holds row ``live``: the grid's length
    is counted on the device, so a later block is neither read nor
    written. ``in_place``: the results are written over the last
    ``n_out`` operands (``input_output_aliases``; a block is read whole
    before it is stored)."""
    n, f = operands[0].shape
    live = live.reshape(1).astype(jnp.int32)
    tile = pl.BlockSpec((block, f), lambda b, live: (b, 0))
    out = jax.ShapeDtypeStruct((n, f), operands[0].dtype)
    first = 1 + len(operands) - n_out    # the count is operand 0
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.minimum(live[0] // block + 1, n // block),),
            in_specs=[tile] * len(operands),
            out_specs=[tile] * n_out),
        out_shape=[out] * n_out,
        input_output_aliases={first + i: i for i in range(n_out)}
        if in_place else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(live, *operands)


@functools.partial(jax.jit, static_argnames=("act", "block", "interpret"))
def gated_rows(gate, up, live, *, act: str, block: int,
               interpret: bool = False):
    """``act(gate) x up`` of two ``(n, f)`` arrays in sorted order, for
    the rows below ``live ()`` int32: float32 from the load to the one
    store. Zero from ``live`` to the end of the block that holds row
    ``live``; later blocks are neither read nor written."""
    return _live_blocks_call(
        functools.partial(_gated_kernel, act=act), "moe_rows_gated", live,
        (gate, up), 1, block=block, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("act", "block", "interpret"))
def gated_rows_cotangents(gate, up, g, live, *, act: str, block: int,
                          interpret: bool = False):
    """`gated_rows`' backward: ``(d_gate, d_up) = (g x up x act'(gate),
    g x act(gate))`` below ``live``, written as `gated_rows` writes, and
    over ``up`` and ``g``, as XLA's fusion wrote in place: no ``(n, f)``
    buffer beside the operands'."""
    return _live_blocks_call(
        functools.partial(_gated_bwd_kernel, act=act), "moe_rows_gated_bwd",
        live, (gate, up, g), 2, block=block, interpret=interpret,
        in_place=True)


# ---------------------------------------------------------------------------
# token order: out[i] = sum over token i's live pairs of weight x row
# ---------------------------------------------------------------------------

def _live_pairs(inverse, live, pairs: int):
    """The live pairs of each block of ``pairs`` pairs, compacted:
    ``(which (blocks, pairs) int32, count (blocks,) int32)``. Block
    ``b``'s first ``count[b]`` entries of ``which[b]`` are the offsets
    inside the block of its pairs whose sorted row is below ``live``,
    ascending; later entries are 0."""
    mask = (inverse < live).reshape(-1, pairs)
    place = jnp.cumsum(mask, axis=1, dtype=jnp.int32)
    slot = jnp.where(mask, place - 1, pairs)
    offsets = jnp.arange(pairs, dtype=jnp.int32)
    # which[b, s] = the offset whose slot is s: one compare-and-reduce
    # the compiler fuses, where a scatter would run an element at a time
    which = jnp.sum(
        jnp.where(slot[:, :, None] == offsets[None, None, :],
                  offsets[None, :, None], 0), axis=1, dtype=jnp.int32)
    return which, place[:, -1]


def _token_kernel(count_ref, which_ref, pos_ref, *refs, k: int,
                  weighted: bool):
    if weighted:
        w_ref, rows_ref, out_ref, stage, acc, sems = refs
    else:
        rows_ref, out_ref, stage, acc, sems = refs
    acc[...] = jnp.zeros_like(acc)

    def use(i, row):
        pair = which_ref[0, i]
        token = pl.ds(lax.div(pair, jnp.int32(k)), 1)
        if weighted:
            row = row * w_ref[0, pair]
        acc[token, :] = acc[token, :] + row

    _walk(count_ref[pl.program_id(0)],
          lambda i: pos_ref[0, which_ref[0, i]], rows_ref, stage, sems, use)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def token_sums(rows, inverse, live, k: int, *, block: int, weights=None,
               interpret: bool = False):
    """``rows (n, d)`` in sorted order, ``inverse (n,)`` int32 (the
    sorted row of each pair), ``live ()`` int32 -> ``(n / k, d)``: token
    ``i``'s row is the sum over its pairs ``p`` with ``inverse[p] <
    live`` of ``weights[p] x rows[inverse[p]]`` (``weights (n,)``
    float32; ones where None), added in float32 in choice order and
    rounded once. No row at or past ``live`` is read."""
    n, d = rows.shape
    pairs = block * k
    blocks = n // pairs
    weighted = weights is not None
    which, count = _live_pairs(inverse, live, pairs)
    scalars = pl.BlockSpec((None, 1, pairs), lambda b, count: (b, 0, 0),
                           memory_space=pltpu.SMEM)
    operands = [which, inverse] + ([weights] if weighted else [])
    return pl.pallas_call(
        functools.partial(_token_kernel, k=k, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[scalars] * len(operands) + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda b, count: (b, 0)),
            scratch_shapes=[pltpu.VMEM((_WINDOW, _GROUP, d), rows.dtype),
                            pltpu.VMEM((block, d), _F32),
                            pltpu.SemaphoreType.DMA((_WINDOW,))]),
        out_shape=jax.ShapeDtypeStruct((n // k, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_summed",
    )(count, *(a.reshape(blocks, 1, pairs) for a in operands), rows)
