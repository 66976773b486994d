"""Grouped (ragged) matmul: Pallas TPU kernels for the expert layer.

``grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,)) -> (m, n)``:
the rows of ``lhs`` come sorted by group, group ``i`` has
``group_sizes[i]`` of them, and each row is multiplied by its group's
matrix. Rows past ``live = sum(group_sizes)`` belong to no group
(models/moe.py sorts there the pairs that chose an expert this rank
does not hold, (ep - 1) / ep of all pairs). **By default they come out
zero**, in the forward and in d-lhs: XLA's gathers in ``combine_rows``
and ``dispatch_rows``' backward read those zeros with non-zero weights
wherever ``ops/moe_rows.py``'s kernels do not run (off the TPU, under a
mesh, where a shape falls back). **On the caller's word that nothing
reads them (``tail_unread``) they are not visited at all** and stay
unwritten memory: ``models/moe.py`` gives it where its row kernels and
its ``act(gate) x up`` pass stop at the same count, so that no producer
walks further than its consumers read. An operand's rows past the tile
that holds row ``live`` are never fetched, so they too may be unwritten.
`grouped_matmuls` is a ``jax.custom_vjp`` over the products of one
``lhs`` with several matrices of one shape (gate and up):

- **forward**  ``out[rows_i] = lhs[rows_i] @ rhs[i]``, a walk a product;
- **d-lhs**    ``d_lhs[rows_i] = sum_p d_out_p[rows_i] @ rhs_p[i]^T``:
  the same kernel contracting the matrices' last dim, so no transposed
  copy of the expert matrices is ever made, a walk a product, **each
  after the first adding onto the one before** (``onto``: that result
  comes in as one more tile a visit and is the buffer written,
  ``input_output_aliases``; the sum is formed in f32 before the store),
  where autodiff would add the ``(m, k)`` results over every row, tail
  and all;
- **d-rhs**    ``d_rhs[i] = lhs[rows_i]^T @ d_out[rows_i]``: the
  transposed grouped product, rows accumulating into one ``(k, n)``
  block a group, a walk a product.

How the kernels walk the rows (the shape of JAX's own
``pallas.ops.tpu.megablox``; the kernels are this file's):

- **row tiles and visits.** Rows are cut into tiles of ``block_m``. A
  tile that holds rows of several groups is *visited* once for each, and
  a group's visit computes the whole tile against its matrix and keeps
  only that group's rows. The visits (tile, group), in row order, are
  worked out from ``group_sizes`` with a few ``jnp`` operations on
  ``g``-long vectors and handed to the kernel as scalar-prefetch
  operands, so the ``index_map``s pick the row tile and the group's
  matrix for each grid step. Their number depends on the data, and **the
  grid is as long as their number**: Pallas takes a traced scalar as a
  grid dimension on the TPU (`_grid_steps`), so no step runs past the
  last visit (``m / block_m + g + 1``, the static bound, is only the
  length of the lists). A visit to a tile that straddles groups is work
  done twice, so ``block_m`` is what decides the kernel's share of the
  MXU at ~1000 rows a group: 63 of 191 visits are repeats at 512 rows a
  tile (what the compiler's own ``ragged_dot`` kernel walks: 44-50 % of
  the v5e's peak, PERF.md section 6, PR 27), 63 of 319 at 256.
- **the tail costs its zeros, or nothing.** By default the forward and
  d-lhs walks count the rows of no group as one more group, so that
  their tiles are visited; such a visit forms no product and asks for no
  operand block (``lhs``'s ``index_map`` stays on the row tile the last
  real visit fetched, ``rhs``'s on the last group's panel): it stores
  zeros in its rows of the output tile, and the pipeline moves that tile
  out and nothing in: 0.75-0.93 us against a live visit's 10.2 where 112
  of a call's 135 visits are the tail's (v5e, docs/design/kernels.md
  1c). Under ``tail_unread`` the walk is d-rhs's, which never visited
  the tail: `_visits(tail=False)` names no tile past the one that holds
  row ``live - 1``. One kernel either way: it tells a tail visit by its
  group id, visit by visit, and a walk without one never takes the
  branch.
- **the tile that holds row ``live``** is then written by its groups'
  visits alone, each through a mask, so its rows from ``live`` on are
  whatever the VMEM buffer held (and with no live row nothing is written
  at all). Nobody multiplies them: the forward and d-lhs keep a visit's
  own rows; **d-rhs masks both operands** of a visit that does not fill
  its tile (a row of no group times a masked zero would be a NaN in a
  weight's gradient), and multiplies a whole tile unmasked.
- **no k loop.** A visit multiplies a ``(block_m, k)`` tile of rows by a
  whole ``(k, block_n)`` panel of the group's matrix (``k`` is 2048 or
  1024 here): one MXU pass sequence, f32 accumulation, one store. The
  panel is fetched when the group changes, every fourth visit or so.
- **tiles** are chosen from the shapes and the dtype (`choose_tiles`):
  the largest that divide the dims, are whole (8, 128) registers, and
  fit ``_VMEM_BUDGET``. No knob. A shape nothing divides goes to
  ``lax.ragged_dot``, as everything does off the TPU (the CPU tests'
  path; ``interpret=True`` runs the kernels in interpreter mode).
- **operands** go to the MXU in the dtype they arrive in (bf16 under
  ``activation_dtype: bfloat16``) and accumulate in f32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.observability import trace

#: Scoped VMEM the kernels ask the compiler for, and what `choose_tiles`
#: lets its own count of their blocks fill (as ops/attention.py).
_VMEM_LIMIT = 64 * 2**20
_VMEM_BUDGET = 40 * 2**20

#: Largest tiles the chooser offers. Rows: a visit to a tile that
#: straddles groups is done once a group, so tall tiles waste the MXU
#: (module docstring); 256 rows keep a visit at 1 GFLOP or more at these
#: widths, against some 0.35 us a grid step costs. Columns: 1024 makes a
#: visit's panel the whole width of an OLMoE expert.
_MAX_BLOCK_M = 256
_MAX_BLOCK_N = 1024

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _divisors(n: int, cap: int, align: int):
    return [t for t in range(min(cap, n) // align * align, 0, -align)
            if n % t == 0]


def choose_tiles(m: int, k: int, n: int, dtype
                 ) -> Optional[Tuple[int, int, int]]:
    """``(block_m, block_n, block_k)`` for the three products over ``m``
    rows between widths ``k`` and ``n``: forward walks ``(block_m, k)``
    row tiles against ``(k, block_n)`` panels, d-lhs ``(block_m, n)``
    against ``(block_k, n)`` (and may add onto an earlier result, one
    more ``(block_m, block_k)`` tile), d-rhs holds a ``(k, block_n)``
    block. None where the shapes do not tile: ``m`` needs a divisor that
    is a multiple of the dtype's sublane packing, ``k`` and ``n`` must
    be multiples of 128."""
    itemsize = jnp.dtype(dtype).itemsize
    if k % 128 or n % 128:
        return None

    def panel(bm, contract, out, onto=0):
        # double-buffered row tile, panel and output tile (and the tile
        # added onto), the f32 product; for d-rhs the (contract, b)
        # accumulator and its output
        for b in _divisors(out, _MAX_BLOCK_N, 128):
            walk = 2 * (bm * contract + contract * b
                        + (1 + onto) * bm * b) * itemsize + bm * b * 4
            if walk + contract * b * 4 <= _VMEM_BUDGET:
                return b
        return None

    for bm in _divisors(m, _MAX_BLOCK_M, 8 * 4 // itemsize):
        bn, bk = panel(bm, k, n), panel(bm, n, k, onto=1)
        if bn and bk:
            return bm, bn, bk
    return None


def _visits(group_sizes, m: int, block_m: int, *, tail: bool,
            empty: bool):
    """The row-ordered (tile, group) visits of a walk over ``m`` rows.

    ``tail``: the rows past ``sum(group_sizes)`` count as one more group
    (id ``g``), so that their tiles are visited (and zeroed); without
    it no visit names a tile past the one that holds row
    ``sum(group_sizes) - 1``. ``empty``: a group without rows still gets
    one visit (which writes its zero block). Returns ``offsets (g +
    2,)`` (row range of group ``i`` is ``offsets[i] : offsets[i + 1]``;
    the tail's too), ``group_ids`` and ``tile_ids`` of static length
    ``m / block_m + g + 1``, and ``num_visits (1,)``, which may be 0
    (no tail, no row in any group); entries past ``num_visits`` repeat
    the last real visit (visit 0 of a walk without one is tile 0, group
    0), so they ask for no new block."""
    g = group_sizes.shape[0]
    tiles = m // block_m
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), ends, jnp.full((1,), m, jnp.int32)])
    starts, stops = offsets[:-1], offsets[1:]          # g + 1 groups
    sizes = stops - starts
    first = jnp.minimum(starts // block_m, tiles - 1)
    last = jnp.where(sizes > 0, (stops - 1) // block_m, first)
    count = jnp.where(sizes > 0, last - first + 1, 1 if empty else 0)
    if not tail:
        count = count.at[g].set(0)
    bound = tiles + g + 1
    group_ids = jnp.repeat(
        jnp.arange(g + 1, dtype=jnp.int32), count, total_repeat_length=bound)
    before = jnp.cumsum(count) - count                 # visits before a group
    num_visits = jnp.sum(count)
    step = jnp.minimum(jnp.arange(bound, dtype=jnp.int32),
                       jnp.maximum(num_visits - 1, 0))
    group_ids = jnp.where(num_visits > 0, group_ids[step], 0)
    tile_ids = first[group_ids] + step - before[group_ids]
    return offsets, group_ids, tile_ids, num_visits.reshape(1)


def _fills_tile(offsets_ref, group, tile, block_m: int):
    """Whether every row of ``tile`` is ``group``'s."""
    return ((offsets_ref[group] <= tile * block_m)
            & (offsets_ref[group + 1] >= (tile + 1) * block_m))


def _row_mask(offsets_ref, group, tile, block_m: int, width: int):
    rows = tile * block_m + lax.broadcasted_iota(
        jnp.int32, (block_m, width), 0)
    return (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])


# ---------------------------------------------------------------------------
# forward and d-lhs: walk the row tiles, one group's panel a visit
# ---------------------------------------------------------------------------

def _lhs_tile(offsets, tile, n_groups: int, block_m: int):
    """The row tile of ``lhs`` a visit to ``tile`` asks for: its own,
    but the tail's visits stay on the last tile that holds a row of a
    group (the block the last real visit fetched), so the pipeline moves
    nothing in for them. ``offsets[n_groups]`` is ``sum(group_sizes)``."""
    live = offsets[n_groups]
    return jnp.minimum(tile, jnp.maximum(live - 1, 0) // block_m)


def _grid_steps(meta):
    """The length of a walk's grid dimension: its visits, counted on the
    device (Pallas takes a traced scalar as a grid dimension on the
    TPU), so no step runs past the last one. A walk without a visit
    takes one step, which does nothing."""
    return jnp.maximum(meta[3][0], 1)


def _gmm_kernel(offsets_ref, group_ref, tile_ref, visits_ref,
                lhs_ref, rhs_ref, *refs, block_m: int, n_groups: int, dims):
    *onto, out_ref = refs                # the result added onto, if any
    v = pl.program_id(1)
    group, tile = group_ref[v], tile_ref[v]

    @pl.when(v < visits_ref[0])
    def _visit():
        whole = _fills_tile(offsets_ref, group, tile, block_m)
        in_group = group < n_groups      # else the tail: rows of no group

        def store(value):
            # a tile other groups share keeps what their visits wrote
            # (the block stays in VMEM between visits to one tile)
            @pl.when(whole)
            def _():
                out_ref[...] = value

            @pl.when(jnp.logical_not(whole))
            def _():
                mask = _row_mask(offsets_ref, group, tile, block_m,
                                 out_ref.shape[1])
                out_ref[...] = jnp.where(mask, value, out_ref[...])

        @pl.when(in_group)
        def _():
            acc = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            for ref in onto:
                acc += ref[...].astype(jnp.float32)
            store(acc.astype(out_ref.dtype))

        @pl.when(jnp.logical_not(in_group))
        def _():
            # rows of no group cost their zeros: no product, and no
            # operand block is read (the index_maps stay where they were)
            store(jnp.zeros(out_ref.shape, out_ref.dtype))


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool, tiles, tail: bool,
         interpret, onto=None):
    """``lhs (m, k)`` x ``rhs (g, k, n)`` (``(g, n, k)`` under
    ``transpose_rhs``) over the ragged groups. ``tail``: the walk visits
    the rows of no group and zeroes them; without it those rows of the
    result are never written. ``onto (m, n)``: an earlier walk's result
    over the same groups, which this one adds its product to and writes
    over (its tail is what that walk left: zeros, or unwritten)."""
    m, k = lhs.shape
    g = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    block_m, block_n = tiles[0], tiles[2 if transpose_rhs else 1]
    meta = _visits(group_sizes, m, block_m, tail=tail, empty=False)
    last = g - 1   # the tail's visits stay on the last group's panel

    def fetched(off, til, v):   # a tail visit, which stores zeros, fetches none
        return _lhs_tile(off, til[v], g, block_m)

    in_specs = [pl.BlockSpec(
        (block_m, k),
        lambda ni, v, off, grp, til, nv: (fetched(off, til, v), 0))]
    if transpose_rhs:
        in_specs.append(pl.BlockSpec(
            (None, block_n, k),
            lambda ni, v, off, grp, til, nv: (jnp.minimum(grp[v], last),
                                              ni, 0)))
    else:
        in_specs.append(pl.BlockSpec(
            (None, k, block_n),
            lambda ni, v, off, grp, til, nv: (jnp.minimum(grp[v], last),
                                              0, ni)))
    operands = [lhs, rhs]
    if onto is not None:
        in_specs.append(pl.BlockSpec(
            (block_m, block_n),
            lambda ni, v, off, grp, til, nv: (fetched(off, til, v), ni)))
        operands.append(onto)
    return pl.pallas_call(
        functools.partial(
            _gmm_kernel, block_m=block_m, n_groups=g,
            dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // block_n, _grid_steps(meta)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (block_m, block_n),
                lambda ni, v, off, grp, til, nv: (til[v], ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # ``onto`` is operand 6, after the visits' four lists, lhs and rhs
        input_output_aliases={} if onto is None else {6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_dlhs" if transpose_rhs else "grouped_matmul",
    )(*meta, *operands)


# ---------------------------------------------------------------------------
# d-rhs: the transposed product, a group's rows accumulate into its block
# ---------------------------------------------------------------------------

def _tgmm_kernel(offsets_ref, group_ref, tile_ref, visits_ref,
                 lhs_ref, rhs_ref, out_ref, acc_ref, *, block_m: int):
    v = pl.program_id(1)
    num = visits_ref[0]
    group, tile = group_ref[v], tile_ref[v]
    first = (v == 0) | (group != group_ref[jnp.maximum(v - 1, 0)])
    last = (v == num - 1) | (group != group_ref[jnp.minimum(v + 1, num - 1)])

    @pl.when(v < num)
    def _visit():
        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        whole = _fills_tile(offsets_ref, group, tile, block_m)

        def add(lhs, rhs):
            acc_ref[...] += lax.dot_general(
                lhs, rhs, _TN, preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            add(lhs_ref[...], rhs_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _():
            # both operands: a row of another group, or of none (which
            # may be unwritten memory), times a masked zero could be NaN
            add(*(jnp.where(
                _row_mask(offsets_ref, group, tile, block_m, ref.shape[1]),
                ref[...], jnp.zeros_like(ref)) for ref in (lhs_ref, rhs_ref)))

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, rhs, group_sizes, *, tiles, interpret):
    """``lhs (m, k)``, ``rhs (m, n)`` -> ``(g, k, n)``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    g = group_sizes.shape[0]
    block_m, block_n = tiles[:2]
    meta = _visits(group_sizes, m, block_m, tail=False, empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // block_n, _grid_steps(meta)),
            in_specs=[
                pl.BlockSpec((block_m, k),
                             lambda ni, v, off, grp, til, nv: (til[v], 0)),
                pl.BlockSpec((block_m, block_n),
                             lambda ni, v, off, grp, til, nv: (til[v], ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, k, block_n),
                lambda ni, v, off, grp, til, nv: (grp[v], 0, ni)),
            scratch_shapes=[pltpu.VMEM((k, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_drhs",
    )(*meta, lhs, rhs)


# ---------------------------------------------------------------------------
# custom_vjp surface
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_matmuls(lhs, rhs, group_sizes, tiles, tail, interpret):
    return tuple(
        _gmm(lhs, w, group_sizes, transpose_rhs=False, tiles=tiles,
             tail=tail, interpret=interpret) for w in rhs)


def _grouped_matmuls_fwd(lhs, rhs, group_sizes, tiles, tail, interpret):
    out = _grouped_matmuls.fun(lhs, rhs, group_sizes, tiles, tail, interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmuls_bwd(tiles, tail, interpret, res, g):
    lhs, rhs, group_sizes = res
    d_lhs = None
    for gi, w in zip(g, rhs):    # each product's adds onto the one before
        d_lhs = _gmm(gi, w, group_sizes, transpose_rhs=True, tiles=tiles,
                     tail=tail, interpret=interpret, onto=d_lhs)
    d_rhs = tuple(
        _tgmm(lhs, gi, group_sizes, tiles=tiles,
              interpret=interpret).astype(w.dtype) for gi, w in zip(g, rhs))
    return (d_lhs, d_rhs, np.zeros(group_sizes.shape, jax.dtypes.float0))


_grouped_matmuls.defvjp(_grouped_matmuls_fwd, _grouped_matmuls_bwd)


def grouped_matmuls(lhs, rhs, group_sizes, *, tail_unread: bool = False,
                    interpret: bool = False):
    """``lhs (m, k)`` x each ``(g, k, n)`` of the tuple ``rhs`` (one
    shape) -> a tuple of ``(m, n)`` over the ragged groups ``group_sizes
    (g,)`` (module docstring). The matrices are cast to ``lhs``'s dtype.
    On the TPU (or under ``interpret``) the Pallas kernels run wherever
    the shapes tile, and the backward forms ``d_lhs``, the sum over the
    products, by each product's walk adding onto the one before;
    anywhere else, and off the TPU, ``lax.ragged_dot``, which has the
    same contract.

    ``tail_unread``: the caller's word that nothing reads a row at or
    past ``sum(group_sizes)`` of a result or of ``d_lhs`` and that every
    product of its layer tiles (``choose_tiles``); the kernels then
    visit no tile of the tail and leave those rows unwritten."""
    rhs = tuple(w.astype(lhs.dtype) for w in rhs)
    tiles = None
    if interpret or _on_tpu():
        tiles = choose_tiles(*lhs.shape, rhs[0].shape[2], lhs.dtype)
    trace.gauge("moe.block_m", tiles[0] if tiles else 0)
    trace.gauge("moe.block_n", tiles[1] if tiles else 0)
    trace.gauge("moe.tail_skipped", int(bool(tiles) and tail_unread))
    sizes = group_sizes.astype(jnp.int32)
    if tiles is None:
        return tuple(
            lax.ragged_dot(lhs, w, sizes, preferred_element_type=lhs.dtype)
            for w in rhs)
    with trace.scope("grouped_matmul"):
        return _grouped_matmuls(lhs, rhs, sizes, tiles, not tail_unread,
                                bool(interpret))


def grouped_matmul(lhs, rhs, group_sizes, **kwargs):
    """One product: `grouped_matmuls` of ``rhs (g, k, n)`` alone."""
    return grouped_matmuls(lhs, (rhs,), group_sizes, **kwargs)[0]
