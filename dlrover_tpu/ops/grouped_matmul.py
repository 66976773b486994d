"""Grouped (ragged) matmul: Pallas TPU kernels for the expert layer.

``grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,)) -> (m, n)``:
the rows of ``lhs`` come sorted by group, group ``i`` has
``group_sizes[i]`` of them, and each row is multiplied by its group's
matrix. Rows past ``sum(group_sizes)`` belong to no group and come out
zero, in the forward and in d-lhs (models/moe.py sorts there the pairs
that chose an expert this rank does not hold, (ep - 1) / ep of all
pairs). Who reads those zeros: XLA's gathers in ``combine_rows`` and
``dispatch_rows``' backward, with non-zero weights, wherever
``ops/moe_rows.py``'s kernels do not run (off the TPU, under a mesh,
where a shape falls back); where they do run, only the elementwise
``act(gate) * up`` between the products, and dropping the stores is
the next step (``ROADMAP.md`` Queue 1 item 4). An operand's rows past
the tile that holds row ``sum(group_sizes)`` are never fetched, so
they may be unwritten memory. It is a ``jax.custom_vjp`` over three
products of the same FLOPs:

- **forward**  ``out[rows_i] = lhs[rows_i] @ rhs[i]``;
- **d-lhs**    ``d_lhs[rows_i] = d_out[rows_i] @ rhs[i]^T``: the same
  kernel contracting ``rhs``'s last dim, so no transposed copy of the
  expert matrices is ever made;
- **d-rhs**    ``d_rhs[i] = lhs[rows_i]^T @ d_out[rows_i]``: the
  transposed grouped product, rows accumulating into one ``(k, n)``
  block a group.

How the kernels walk the rows (the shape of JAX's own
``pallas.ops.tpu.megablox``; the kernels are this file's):

- **row tiles and visits.** Rows are cut into tiles of ``block_m``. A
  tile that holds rows of several groups is *visited* once for each, and
  a group's visit computes the whole tile against its matrix and keeps
  only that group's rows. The visits (tile, group), in row order, are
  worked out from ``group_sizes`` with a few ``jnp`` operations on
  ``g``-long vectors and handed to the kernel as scalar-prefetch
  operands, so the ``index_map``s pick the row tile and the group's
  matrix for each grid step. Their number depends on the data; its
  static bound, ``m / block_m + g + 1``, is the grid, and steps past the
  last real visit do nothing. A visit to a tile that straddles groups is
  work done twice, so ``block_m`` is what decides the kernel's share of
  the MXU at ~1000 rows a group: 63 of 191 visits are repeats at 512
  rows a tile (what the compiler's own ``ragged_dot`` kernel walks: 44-50
  % of the v5e's peak, PERF.md section 6, PR 27), 63 of 319 at 256.
- **the tail costs its zeros.** The forward and d-lhs walks count the
  rows of no group as one more group, so that their tiles are visited;
  such a visit forms no product and asks for no operand block (``lhs``'s
  ``index_map`` stays on the row tile the last real visit fetched,
  ``rhs``'s on the last group's panel): it stores zeros in its rows of
  the output tile, and the pipeline moves that tile out and nothing in:
  0.75-0.93 us against a live visit's 10.2 where 112 of a call's 135
  visits are the tail's (v5e, docs/design/kernels.md 1c). d-rhs never
  visits the tail. The kernel tells a tail visit by its group id, visit
  by visit: a call without a tail never takes the branch.
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
    against ``(block_k, n)``, d-rhs holds a ``(k, block_n)`` block. None
    where the shapes do not tile: ``m`` needs a divisor that is a
    multiple of the dtype's sublane packing, ``k`` and ``n`` must be
    multiples of 128."""
    itemsize = jnp.dtype(dtype).itemsize
    if k % 128 or n % 128:
        return None

    def panel(bm, contract, out):
        # double-buffered row tile, panel and output tile, the f32
        # product; for d-rhs the (contract, b) accumulator and its output
        for b in _divisors(out, _MAX_BLOCK_N, 128):
            walk = 2 * (bm * contract + contract * b + bm * b) * itemsize \
                + bm * b * 4
            if walk + contract * b * 4 <= _VMEM_BUDGET:
                return b
        return None

    for bm in _divisors(m, _MAX_BLOCK_M, 8 * 4 // itemsize):
        bn, bk = panel(bm, k, n), panel(bm, n, k)
        if bn and bk:
            return bm, bn, bk
    return None


def _visits(group_sizes, m: int, block_m: int, *, tail: bool,
            empty: bool):
    """The row-ordered (tile, group) visits of a walk over ``m`` rows.

    ``tail``: the rows past ``sum(group_sizes)`` count as one more group
    (id ``g``), so that their tiles are visited (and zeroed). ``empty``:
    a group without rows still gets one visit (which writes its zero
    block). Returns ``offsets (g + 2,)`` (row range of group ``i`` is
    ``offsets[i] : offsets[i + 1]``; the tail's too), ``group_ids`` and
    ``tile_ids`` of static length ``m / block_m + g + 1``, and
    ``num_visits (1,)``; entries past ``num_visits`` repeat the last
    real visit, so they ask for no new block."""
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
    step = jnp.minimum(jnp.arange(bound, dtype=jnp.int32), num_visits - 1)
    group_ids = group_ids[step]
    tile_ids = first[group_ids] + step - before[group_ids]
    return offsets, group_ids, tile_ids, num_visits.reshape(1)


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


def _gmm_kernel(offsets_ref, group_ref, tile_ref, visits_ref,
                lhs_ref, rhs_ref, out_ref, *, block_m: int, n_groups: int,
                dims):
    v = pl.program_id(1)
    group, tile = group_ref[v], tile_ref[v]

    @pl.when(v < visits_ref[0])
    def _visit():
        start, stop = offsets_ref[group], offsets_ref[group + 1]
        whole = (start <= tile * block_m) & (stop >= (tile + 1) * block_m)
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
            store(acc.astype(out_ref.dtype))

        @pl.when(jnp.logical_not(in_group))
        def _():
            # rows of no group cost their zeros: no product, and no
            # operand block is read (the index_maps stay where they were)
            store(jnp.zeros(out_ref.shape, out_ref.dtype))


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool, tiles, interpret):
    m, k = lhs.shape
    g = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    block_m, block_n = tiles[0], tiles[2 if transpose_rhs else 1]
    meta = _visits(group_sizes, m, block_m, tail=True, empty=False)
    last = g - 1   # the tail's visits stay on the last group's panel
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, block_n, k),
            lambda ni, v, off, grp, til, nv: (jnp.minimum(grp[v], last),
                                              ni, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, block_n),
            lambda ni, v, off, grp, til, nv: (jnp.minimum(grp[v], last),
                                              0, ni))
    return pl.pallas_call(
        functools.partial(
            _gmm_kernel, block_m=block_m, n_groups=g,
            dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // block_n, meta[1].shape[0]),
            in_specs=[
                pl.BlockSpec(
                    (block_m, k),
                    lambda ni, v, off, grp, til, nv: (
                        _lhs_tile(off, til[v], g, block_m), 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n),
                lambda ni, v, off, grp, til, nv: (til[v], ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_dlhs" if transpose_rhs else "grouped_matmul",
    )(*meta, lhs, rhs)


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

        mask = _row_mask(offsets_ref, group, tile, block_m, rhs_ref.shape[1])
        rhs = jnp.where(mask, rhs_ref[...], jnp.zeros_like(rhs_ref))
        acc_ref[...] += lax.dot_general(
            lhs_ref[...], rhs, _TN, preferred_element_type=jnp.float32)

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
            grid=(n // block_n, meta[1].shape[0]),
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

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, group_sizes, tiles, interpret):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False, tiles=tiles,
                interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, tiles, interpret):
    out = _gmm(lhs, rhs, group_sizes, transpose_rhs=False, tiles=tiles,
               interpret=interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(tiles, interpret, res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True, tiles=tiles,
                 interpret=interpret)
    d_rhs = _tgmm(lhs, g, group_sizes, tiles=tiles, interpret=interpret)
    return (d_lhs, d_rhs.astype(rhs.dtype),
            np.zeros(group_sizes.shape, jax.dtypes.float0))


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool = False):
    """``lhs (m, k)`` x ``rhs (g, k, n)`` -> ``(m, n)`` over the ragged
    groups ``group_sizes (g,)`` (module docstring). ``rhs`` is cast to
    ``lhs``'s dtype. On the TPU (or under ``interpret``) the Pallas
    kernels run wherever the shapes tile; anywhere else, and off the TPU,
    ``lax.ragged_dot``, which has the same contract."""
    rhs = rhs.astype(lhs.dtype)
    tiles = None
    if interpret or _on_tpu():
        tiles = choose_tiles(*lhs.shape, rhs.shape[2], lhs.dtype)
    trace.gauge("moe.block_m", tiles[0] if tiles else 0)
    trace.gauge("moe.block_n", tiles[1] if tiles else 0)
    if tiles is None:
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=lhs.dtype)
    with trace.scope("grouped_matmul"):
        return _grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32),
                               tiles, bool(interpret))
