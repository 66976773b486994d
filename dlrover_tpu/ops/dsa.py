"""Learned sparse attention's own pieces: the indexer's scores, the exact
top-k threshold of a causal row, the selection as a mask, and the
head-summed attention probabilities the indexer learns from.

A layer with an indexer (``models/dots3.py``'s full-attention layers)
lets each query attend to the ``topk`` keys at or before it that a small
scorer ranks highest::

    I[t, s] = sum_j w[t, j] * relu(q_j[t] . k[s])      j over index heads
    S_t     = the topk keys s <= t of largest I[t, s]  (all while t < topk)

and trains the scorer towards the main attention's own distribution,
``KL(p^_t || softmax_{s in S_t} I[t, s])`` with ``p`` the attention
probabilities summed over heads. What this file computes:

- `index_scores`: ``I (b, s, s)`` float32. On the TPU two Pallas
  kernels under one ``custom_vjp`` (``dsa_index_fwd``; ``dsa_index_bwd``
  for d``q``, d``w`` and d``k``, the one index key's gradient held in
  VMEM for a whole batch row): a grid step is a (query block, key block)
  tile, the index heads a loop inside it, so the ``(s, s, heads)``
  products live in VMEM a head at a time and HBM holds ``I`` alone.
  Blocks wholly above the diagonal are zeros, neither computed nor
  fetched. bf16 operands, float32 products and sums.
- `select_threshold`: per row the ``topk``-th largest of the causal
  entries, exactly, and where its ties are cut: **bisection on the
  float's bits** (32 counting passes over an order-preserving uint32
  key build the threshold bit by bit, ``log2 s`` more find the position
  of the last tie kept). Ties are broken to the lower ``s``, as a
  stable descending sort and ``lax.top_k`` break them. XLA ops, every
  pass one fused compare-and-count over ``(s, s)`` in HBM: the
  selection's form off the TPU, the kernel's oracle, and what
  ``ops/blocksel.py pick_blocks`` calls on its ``s / block`` blocks.
  ``lax.top_k`` at k = 2048 and a full sort are the slow paths on a TPU.
- `selection_mask`: the int8 ``(b, s, s)`` mask the flash kernels read
  (``ops/attention.py`` ``select=``), causal included. On the TPU one
  Pallas kernel, ``dsa_select``: a grid step holds a block of whole
  query rows (`_select_rows`: 128 at 16384 positions, 256 at 8192) as
  int32 keys in VMEM, runs the same passes there over the key tiles at
  or under the block's diagonal, and writes the rows' mask; HBM sees
  the scores once and the mask once, and no ``(s, s)`` array of bits
  exists. The ``cut`` passes run only in a block one of whose rows
  holds more keys at its threshold than it needs. Off the TPU
  `select_threshold`'s passes as XLA ops; the same mask bit for bit.
- `head_summed_probs`: ``p[t, s] = sum_h exp(scale q_h[t] . k_h[s] -
  lse[t, h])`` over the selected pairs, from q, k, the flash forward's
  ``lse`` and the mask; a Pallas kernel on the TPU (``dsa_probs``: a
  grid step is a (query block, key block) tile as the index kernels'
  is, every head's q block resident for a row of tiles, the heads a
  loop inside it several a trip, their sum masked and written once a
  tile), not differentiated.
- `indexer_loss`: the KL, summed over the rows (XLA ops over ``(s, s)``);
  its forward also forms the gradient with respect to the scores, the
  one array its backward reads (a ``custom_vjp``).

Off the TPU the XLA forms (the scores and the probabilities blocked
over query rows), which are the kernels' oracles; ``interpret=True``
runs the kernels on the CPU. No ``(s, s, heads)`` array exists in HBM
on the TPU path. A gather-by-index
form (a query's ``topk`` keys gathered into a dense block) is not here:
at 8192 positions the masked causal walk does less work a pair.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.attention import (
    _LSE_LANES as _LANES,   # a per-row scalar's broadcast minor dim
    _NN,
    _NT,
    _STAT_LANES,
    _VMEM_BUDGET,
    _VMEM_LIMIT,
    _dot,
    _last_k_block as _last_k,
    _round_up,
    flash_attention,
)
from dlrover_tpu.ops.kda import _over_batch_rows
from dlrover_tpu.parallel.mesh import BATCH_AXES

#: largest (block_q, block_k) of the three kernels: the forward and the
#: probabilities keep one float32 tile, the backward also a float32
#: accumulator an index head (its tiles and trips tried on the v5e:
#: PERF.md section 6, PR 57)
_MAX_TILE = {"fwd": (256, 512), "dq": (256, 512), "probs": (512, 512)}

#: index heads a trip of the backward's head loop (Mosaic unrolls a loop
#: wholly or not at all): a head's chain of product, vector passes and
#: two products leaves the units idle in turn, and four give the
#: scheduler independent work to lay between (a fifth faster than one)
_HEADS_A_TRIP = 4


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _side(s: int, cap: int) -> int:
    """The largest divisor of ``s`` up to ``cap`` that is a multiple of
    128; a sequence 128 does not divide goes as one block."""
    if s % 128:
        return s
    return next(t for t in range(min(cap, s) // 128 * 128, 0, -128)
                if s % t == 0)


def _tiles(kernel: str, s: int) -> Tuple[int, int]:
    bq, bk = _MAX_TILE[kernel]
    return _side(s, bq), _side(s, bk)


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _over_batch(fn, mesh: Optional[Mesh], *arrays):
    """``fn`` on each device's batch rows (a Mosaic kernel is not
    partitioned by the compiler); every operand leads with the batch and
    the one result is ``(b, s, s)``."""
    return _over_batch_rows(fn, mesh, arrays, (), P(BATCH_AXES, None, None))


# ---------------------------------------------------------------------------
# The indexer's scores
# ---------------------------------------------------------------------------

def _index_scores_xla(q, k, w):
    """The definition, a block of query rows at a time (the block's
    ``(rows, heads, s)`` products are all that exists at once; a block
    is recomputed in a backward pass). Above the diagonal too."""
    b, s, h, d = q.shape
    block = 128 if s % 128 == 0 else s

    @jax.checkpoint
    def one(args):
        qb, wb = args                                   # (b, block, h, d)
        dots = jnp.einsum("bqhd,bkd->bqhk", qb, k,
                          preferred_element_type=jnp.float32)
        return jnp.sum(wb[..., None] * jnp.maximum(dots, 0.0), axis=2)

    out = lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0),
        jnp.moveaxis(w.reshape(b, s // block, block, h), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, bq: int, bk: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        k = k_ref[0]                                         # (bk, d)

        def head(j, acc):
            dots = _dot(q_ref[0, j], k, _NT)                 # (bq, bk) f32
            return acc + w_ref[0, j][:, :1] * jnp.maximum(dots, 0.0)

        o_ref[0] = lax.fori_loop(
            0, heads, head, jnp.zeros((bq, bk), jnp.float32))

    @pl.when(ki * bk > qi * bq + bq - 1)
    def _above():
        o_ref[0] = jnp.zeros((bq, bk), jnp.float32)


def _lanes(x):
    """``(b, h, s)`` -> ``(b, h, s, 8)``: a row's scalar with a minor dim
    a TPU block can tile."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LANES,))


def _index_fwd_pallas(q, k, w, interpret: bool):
    b, s, h, d = q.shape
    bq, bk = _tiles("fwd", s)
    n_q, n_k = s // bq, s // bk
    return pl.pallas_call(
        functools.partial(_index_fwd_kernel, bq=bq, bk=bk),
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, h, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bi, qi, ki: (
                bi, jnp.minimum(ki, _last_k(qi, bq, bk, n_k)), 0)),
            pl.BlockSpec((1, h, bq, _LANES),
                         lambda bi, qi, ki: (bi, 0, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="dsa_index_fwd",
    )(q.transpose(0, 2, 1, 3), k, _lanes(w.transpose(0, 2, 1)))


def _index_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dw_ref, dkt_ref,
                      dq_acc, dw_acc, dkt_acc, qt_ref, wl_ref, *, bq: int,
                      bk: int, n_q: int, n_k: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]

    def of_head(j):
        return lax.broadcasted_iota(jnp.int32, (bq, heads), 1) == j

    @pl.when((qi == 0) & (ki == 0))
    def _init_row():
        dkt_acc[...] = jnp.zeros_like(dkt_acc)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

        # once a q block, so that no tile's product transposes anything
        # and a head's weights lie a row's on every lane
        def turn(j, carry):
            qt_ref[j] = q_ref[0, j].T
            wl_ref[j] = jnp.broadcast_to(jnp.sum(
                jnp.where(of_head(j), w_ref[0], 0.0), axis=1, keepdims=True),
                wl_ref.shape[1:])
            return carry

        lax.fori_loop(0, heads, turn, 0)

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        k = k_ref[0]
        g = g_ref[0]                                         # (bq, bk) f32
        keys = pl.ds(pl.multiple_of(ki * bk, bk), bk)

        def head(j):
            dots = _dot(q_ref[0, j], k, _NT)
            dw_acc[...] = dw_acc[...] + jnp.where(of_head(j), jnp.sum(
                g * jnp.maximum(dots, 0.0), axis=1, keepdims=True), 0.0)
            gw = jnp.where(
                dots > 0.0, g * wl_ref[j][:, :1], 0.0).astype(k.dtype)
            dq_acc[j] = dq_acc[j] + _dot(gw, k, _NN)
            dkt_acc[:, keys] = dkt_acc[:, keys] + _dot(qt_ref[j], gw, _NN)

        a_trip = math.gcd(heads, _HEADS_A_TRIP)

        def trip(i, carry):
            for u in range(a_trip):
                head(i * a_trip + u)
            return carry

        lax.fori_loop(0, heads // a_trip, trip, 0)

    @pl.when(ki == _last_k(qi, bq, bk, n_k))
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_acc[...]

    @pl.when((qi == n_q - 1) & (ki == n_k - 1))
    def _finalize_row():
        dkt_ref[0] = dkt_acc[...].astype(dkt_ref.dtype)


def _index_bwd_pallas(q, k, w, g, interpret: bool):
    """d``q``, d``k``, d``w`` from one kernel: a tile's scores are formed
    once a head and feed all three. The flash backward is two kernels
    because dk and dv a head fit no VMEM; the indexer's one key a
    position has a gradient of ``(d, s)`` float32, which stays in VMEM
    while a batch row's q blocks walk by. Kept transposed, ``dk^T +=
    q_j^T gw`` on a q block turned once: the other way round every head
    and tile would turn ``gw``."""
    b, s, h, d = q.shape
    bq, bk = _tiles("dq", s)
    n_q, n_k = s // bq, s // bk
    # whatever holds the float32 (s, s) scores in HBM (s under 64 k on a
    # 16 GiB chip) holds this: 32 MiB at 64 k x 128
    assert s * d * 4 <= _VMEM_BUDGET, (s, d)
    heads_q_rows = pl.BlockSpec(
        (1, h, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0))
    q_rows = pl.BlockSpec((1, bq, h), lambda bi, qi, ki: (bi, qi, 0))

    def k_index(bi, qi, ki):
        return jnp.minimum(ki, _last_k(qi, bq, bk, n_k))

    dq, dw, dkt = pl.pallas_call(
        functools.partial(_index_bwd_kernel, bq=bq, bk=bk, n_q=n_q, n_k=n_k),
        grid=(b, n_q, n_k),
        in_specs=[
            heads_q_rows,
            pl.BlockSpec((1, bk, d),
                         lambda bi, qi, ki: (bi, k_index(bi, qi, ki), 0)),
            q_rows,
            pl.BlockSpec((1, bq, bk),
                         lambda bi, qi, ki: (bi, qi, k_index(bi, qi, ki))),
        ],
        out_specs=[heads_q_rows, q_rows,
                   pl.BlockSpec((1, d, s), lambda bi, qi, ki: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h), jnp.float32),
                   jax.ShapeDtypeStruct((b, d, s), k.dtype)],
        scratch_shapes=[pltpu.VMEM((h, bq, d), jnp.float32),
                        pltpu.VMEM((bq, h), jnp.float32),
                        pltpu.VMEM((d, s), jnp.float32),
                        pltpu.VMEM((h, d, bq), q.dtype),
                        pltpu.VMEM((h, bq, _STAT_LANES), jnp.float32)],
        # dk sums over the q blocks too
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="dsa_index_bwd",
    )(q.transpose(0, 2, 1, 3), k, w, g)
    return dq.transpose(0, 2, 1, 3), dkt.swapaxes(1, 2), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _index_scores_kernels(q, k, w, interpret):
    return _index_fwd_pallas(q, k, w, interpret)


def _index_scores_fwd(q, k, w, interpret):
    return _index_fwd_pallas(q, k, w, interpret), (q, k, w)


def _index_scores_bwd(interpret, res, g):
    with trace.scope("dsa_index"):
        return _index_bwd_pallas(*res, g, interpret)


_index_scores_kernels.defvjp(_index_scores_fwd, _index_scores_bwd)


def index_scores(q, k, w, *, interpret: bool = False,
                 mesh: Optional[Mesh] = None):
    """``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``: ``q (b, s, h,
    d)`` the index heads' queries, ``k (b, s, d)`` the one index key a
    position, ``w (b, s, h)`` float32 the heads' weights -> ``(b, s, s)``
    float32, differentiable in all three. Entries above the diagonal
    are unspecified (the kernels write zeros, the XLA form the value):
    everything downstream reads the causal part alone."""
    kernels = interpret or _on_tpu()
    trace.gauge("dsa.kernel", 1 if kernels else 0)
    # the `pallas_call`s of the backward
    trace.gauge("dsa.index_bwd_kernels", 1 if kernels else 0)
    w = w.astype(jnp.float32)
    if not kernels:
        return _index_scores_xla(q, k, w)
    return _over_batch(
        lambda q, k, w: _index_scores_kernels(q, k, w, interpret),
        mesh, q, k, w)


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------

def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (-0.0 below +0.0)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    negative = bits >> 31 == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(0x80000000))


def _causal(s: int):
    pos = jnp.arange(s, dtype=jnp.int32)
    return pos[None, :] <= pos[:, None]                      # (query, key)


def _causal_bits(scores):
    """`_ordered_bits` of the causal entries; what the causal mask hides
    becomes 0 and sorts below every score."""
    return jnp.where(_causal(scores.shape[-1]), _ordered_bits(scores),
                     jnp.uint32(0))


def _threshold(bits, topk: int):
    """`select_threshold` on a row's `_causal_bits`."""
    s = bits.shape[-1]

    def count(seen):
        return jnp.sum(seen, axis=-1, dtype=jnp.int32)       # (b, s)

    def grow(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(bits >= cand[..., None]) >= topk, cand, tau)

    tau = lax.fori_loop(0, 32, grow, jnp.zeros(bits.shape[:-1], jnp.uint32))
    # ties at tau: keep the `need` of lowest position
    need = topk - count(bits > tau[..., None])
    ties = bits == tau[..., None]
    pos = jnp.arange(s, dtype=jnp.int32)
    n_bits = max(s - 1, 1).bit_length()

    def reach(i, cut):
        cand = cut | (jnp.int32(1) << (n_bits - 1 - i))
        below = count(ties & (pos < cand[..., None]))
        return jnp.where(below < need, cand, cut)

    cut = lax.fori_loop(0, n_bits, reach, jnp.zeros(bits.shape[:-1], jnp.int32))
    return tau, cut


def select_threshold(scores, topk: int):
    """Per row ``t`` of ``scores (b, s, s)``, over its causal entries ``s
    <= t``: ``(tau, cut)`` with ``tau (b, s)`` uint32 the ordered bits
    (`_ordered_bits`) of the ``topk``-th largest and ``cut (b, s)`` int32
    the position of the last entry equal to it that is kept, ties broken
    to the lower ``s``: the row's selection is ``bits > tau``, or ``bits
    == tau`` at ``s <= cut``. A row with no more than ``topk`` causal
    entries gets ``tau`` 0 (everything). Exact: 32 counting passes build
    ``tau`` from its highest bit down, ``s.bit_length()`` more find
    ``cut``."""
    return _threshold(_causal_bits(scores), topk)


def _xla_selection_mask(scores, topk: int):
    """`selection_mask` from `select_threshold`'s passes, XLA ops."""
    s = scores.shape[-1]
    bits = _causal_bits(scores)
    tau, cut = (a[..., None] for a in _threshold(bits, topk))
    pos = jnp.arange(s, dtype=jnp.int32)
    chosen = (bits > tau) | ((bits == tau) & (pos <= cut))
    return (chosen & _causal(s)).astype(jnp.int8)


#: What `_select_rows` lets a grid step of `dsa_select` fill: a block of
#: rows' float32 scores and int8 mask, both double-buffered, and the key
#: copy (`choose_tiles`' margin under `_VMEM_LIMIT`)
_SELECT_BUDGET = _VMEM_BUDGET

#: rows whose counts a run of passes carries in registers, and the
#: columns one trip of a counting loop covers. Measured on the v5e at
#: 16384 positions (PERF.md section 6, PR 55): 4.76 ms a layer here, 5.6
#: at (64, 512), 7.5 at (32, 512)
_SELECT_SUB = 128
_SELECT_TRIP = 1024

_INT_MIN, _INT_MAX = -2**31, 2**31 - 1


def _select_rows(s: int) -> Optional[int]:
    """Query rows a grid step of `dsa_select` holds (whole rows: a count
    is over a row): the largest multiple of 32, the int8 mask's sublane
    tiling, that divides ``s`` and fits `_SELECT_BUDGET`; a sequence 128
    does not divide goes as one block. ``None`` where nothing fits: the
    caller has the XLA form."""
    # a key of a row: the float32 block and the int8 block twice (the
    # pipeline's two buffers) and the int32 copy
    per_row = s * (2 * 4 + 4 + 2 * 1)
    if s % 128:
        return s if s * per_row <= _SELECT_BUDGET else None
    cap = min(s, _SELECT_BUDGET // per_row)
    return next(
        (r for r in range(cap // 32 * 32, 0, -32) if s % r == 0), None)


def _select_kernel(s_ref, o_ref, key_ref, tau_ref, cut_ref, *,
                   rows: int, sub: int, width: int, per_trip: int,
                   topk: int):
    """One block of ``rows`` query rows, whole: `_threshold`'s passes on
    a copy of the rows' keys in VMEM, then the rows' mask. The key is
    `_ordered_bits` with its top bit turned, an int32 whose signed order
    is the floats' (``tau`` here is `select_threshold`'s ``^ 2**31``);
    what the causal mask hides is ``_INT_MIN``, below every candidate.
    A row's scalar is held on every lane of a ``width``-wide tile."""
    i = pl.program_id(1)
    s = s_ref.shape[-1]
    n_tiles = s // width
    row0 = i * rows
    # column tiles that hold a causal entry, in whole trips
    trips = _last_k(i, rows, width, n_tiles) // per_trip + 1
    live = trips * per_trip

    def cols(c):
        return pl.ds(pl.multiple_of(c * width, width), width)

    def pos(c, n):
        return c * width + lax.broadcasted_iota(jnp.int32, (n, width), 1)

    def row(r0, n):
        return row0 + r0 + lax.broadcasted_iota(jnp.int32, (n, width), 0)

    def write(chosen):
        def tile(c, carry):
            o_ref[0, :, cols(c)] = (
                chosen(c) & (pos(c, rows) <= row(0, rows))).astype(jnp.int8)
            return carry

        def above(c, carry):
            o_ref[0, :, cols(c)] = jnp.zeros((rows, width), jnp.int8)
            return carry

        lax.fori_loop(0, live, tile, 0)
        lax.fori_loop(live, n_tiles, above, 0)

    # every row of the block holds no more than topk causal keys
    all_kept = row0 + rows <= topk

    @pl.when(all_kept)
    def _causal_alone():
        write(lambda c: jnp.full((rows, width), True))

    def count(rs, n_trips, seen):
        """Per row of the sub-block ``rs``, how many of its keys in the
        first ``n_trips`` trips ``seen(keys, tile)`` holds for."""
        def trip(t, acc):
            for u in range(per_trip):
                c = t * per_trip + u
                acc = acc + jnp.where(seen(key_ref[rs, cols(c)], c), 1, 0)
            return acc

        acc = lax.fori_loop(0, n_trips, trip,
                            jnp.zeros((sub, width), jnp.int32))
        return jnp.broadcast_to(
            jnp.sum(acc, axis=1, keepdims=True), (sub, width))

    def sub_block(j):
        r0 = pl.multiple_of(j * sub, sub)
        # the trips that hold a causal entry of the sub-block's rows
        return r0, pl.ds(r0, sub), (
            (row0 + r0 + sub - 1) // (width * per_trip) + 1)

    @pl.when(jnp.logical_not(all_kept))
    def _threshold_and_mask():
        def to_keys(c, carry):
            bits = lax.bitcast_convert_type(s_ref[0, :, cols(c)], jnp.int32)
            key = jnp.where(bits < 0, bits ^ jnp.int32(_INT_MAX), bits)
            key_ref[:, cols(c)] = jnp.where(
                pos(c, rows) <= row(0, rows), key, jnp.int32(_INT_MIN))
            return carry

        lax.fori_loop(0, live, to_keys, 0)

        def find_tau(j, most):
            r0, rs, n_trips = sub_block(j)

            def grow(p, carry):
                tau, held = carry
                # p = 0 turns the sign: _INT_MIN -> 0
                cand = tau ^ (jnp.int32(1) << (31 - p))
                n = count(rs, n_trips, lambda key, c: key >= cand)
                take = n >= topk
                return jnp.where(take, cand, tau), jnp.where(take, n, held)

            # held: a row's causal keys at or above its tau
            tau, held = lax.fori_loop(0, 32, grow, (
                jnp.full((sub, width), _INT_MIN, jnp.int32),
                row(r0, sub) + 1))
            tau_ref[rs] = tau
            return jnp.maximum(most, jnp.max(held))

        most = lax.fori_loop(0, rows // sub, find_tau, jnp.int32(0))

        # where no row holds more keys at its tau than it needs every
        # causal tie is kept, and no cut is looked for
        cut_ref[...] = jnp.full_like(cut_ref, _INT_MAX)

        @pl.when(most > topk)
        def _find_cut():
            n_bits = max(s - 1, 1).bit_length()

            def one(j, carry):
                r0, rs, n_trips = sub_block(j)
                tau = tau_ref[rs]
                need = topk - count(rs, n_trips, lambda key, c: key > tau)

                def reach(p, cut):
                    cand = cut | (jnp.int32(1) << (n_bits - 1 - p))
                    below = count(rs, n_trips, lambda key, c: (
                        (key == tau) & (pos(c, sub) < cand)))
                    return jnp.where(below < need, cand, cut)

                cut_ref[rs] = lax.fori_loop(
                    0, n_bits, reach, jnp.zeros((sub, width), jnp.int32))
                return carry

            lax.fori_loop(0, rows // sub, one, 0)

        def chosen(c):
            key, tau = key_ref[:, cols(c)], tau_ref[...]
            return (key > tau) | (
                (key == tau) & (pos(c, rows) <= cut_ref[...]))

        write(chosen)


def _select_pallas(scores, topk: int, rows: int, interpret: bool):
    b, s, _ = scores.shape
    width = 128 if s % 128 == 0 else s
    sub = next((n for n in range(_SELECT_SUB, 0, -32) if rows % n == 0), rows)
    n_tiles = s // width
    per_trip = next(n for n in range(max(_SELECT_TRIP // width, 1), 0, -1)
                    if n_tiles % n == 0)

    def rows_of(bi, i):
        return bi, i, 0

    return pl.pallas_call(
        functools.partial(_select_kernel, rows=rows, sub=sub, width=width,
                          per_trip=per_trip, topk=topk),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, rows, s), rows_of)],
        out_specs=pl.BlockSpec((1, rows, s), rows_of),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)] + [
            pltpu.VMEM((rows, width), jnp.int32)] * 2,
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
        name="dsa_select",
    )(scores)


def selection_mask(scores, topk: int, *, interpret: bool = False,
                   mesh: Optional[Mesh] = None):
    """``(b, s, s)`` int8, 1 where query ``t`` attends to key ``s``: the
    ``topk`` causal keys of largest ``scores[t, s]`` (all of them while
    ``t < topk``), ties to the lower ``s``. What ``flash_attention(
    select=)`` reads. On the TPU (or with ``interpret``) the kernel
    `dsa_select`, which reads the scores once; off it `select_threshold`'s
    passes as XLA ops, the kernel's oracle. The same mask bit for bit.
    Entries of ``scores`` above the diagonal are never counted."""
    s = scores.shape[-1]
    rows = _select_rows(s)
    kernels = (interpret or _on_tpu()) and topk < s and rows is not None
    trace.gauge("dsa.select_kernel", 1 if kernels else 0)
    if topk >= s:
        return jnp.broadcast_to(_causal(s).astype(jnp.int8), scores.shape)
    if not kernels:
        return _xla_selection_mask(scores, topk)
    return _over_batch(
        lambda x: _select_pallas(x, topk, rows, interpret),
        mesh, scores.astype(jnp.float32))


# ---------------------------------------------------------------------------
# What the indexer learns from
# ---------------------------------------------------------------------------

def _probs_xla(q, k, lse, mask, scale: float):
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = 128 if s % 128 == 0 else s

    def one(args):
        qb, lb, mb = args                 # (b, block, h, d), (b, h, block)
        # query head j reads key head j // (h // hkv)
        logits = jnp.einsum(
            "bqngd,bknd->bngqk", qb.reshape(b, block, hkv, h // hkv, d), k,
            preferred_element_type=jnp.float32).reshape(b, h, block, s)
        logits = logits * scale
        p = jnp.exp(logits - lb[..., None])
        return jnp.sum(jnp.where((mb != 0)[:, None], p, 0.0), axis=1)

    out = lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // block, block, h, d), 1, 0),
        jnp.moveaxis(lse.reshape(b, h, s // block, block), 2, 0),
        jnp.moveaxis(mask.reshape(b, s // block, block, s), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, s)


#: query heads a trip of `dsa_probs`' head loop, tried on the v5e at both
#: cells' shapes (PERF.md section 6, PR 62): a head's chain of product,
#: scale, subtract, ``exp`` and add is lighter than the index backward's,
#: and eight a trip run 9 % under four (16 and 32 win 2 and 7 % more of
#: the kernel for 1.5 and 3 s of compile)
_PROBS_HEADS_A_TRIP = 8


def _probs_trip(heads: int) -> int:
    return math.gcd(heads, _PROBS_HEADS_A_TRIP)


def _probs_tiles(s: int, h: int, hkv: int, d: int,
                 itemsize: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` of `dsa_probs`: the sides up to
    ``_MAX_TILE["probs"]`` whose resident blocks fit `_VMEM_BUDGET`, the
    tallest q block first (every key head's block is fetched a tile,
    every query head's a row of tiles), then the widest k block."""
    def held(bq, bk):
        tile = bq * bk * 4
        # a head's chain runs a vreg at a time: beside the sum no
        # temporary of a tile's size is kept (the compiler's own count
        # is 13 MiB at keye-vl's shape: `tests/test_chip_compile.py`)
        return (2 * (h * bq + hkv * bk) * _round_up(d, 128) * itemsize  # q, k
                + 2 * bq * _round_up(h, 128) * 4             # lse
                + 2 * bq * bk + 2 * tile                     # mask, output
                + 2 * tile)                    # the sum, and one to spare

    top_q, top_k = _tiles("probs", s)
    sides = [(bq, bk) for bq in range(top_q, 0, -128) if s % bq == 0
             for bk in range(top_k, 0, -128) if s % bk == 0]
    return next((t for t in sides if held(*t) <= _VMEM_BUDGET), sides[-1])


def _probs_kernel(q_ref, k_ref, lse_ref, m_ref, o_ref, acc_ref, *, bq: int,
                  bk: int, group: int, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[1]

    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _compute():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def head(j):
            # the head's column of the rows' (bq, heads) lse: a masked
            # lane sum, on units the products leave idle
            of_head = lax.broadcasted_iota(
                jnp.int32, lse_ref.shape[1:], 1) == j
            lse = jnp.sum(
                jnp.where(of_head, lse_ref[0], 0.0), axis=1, keepdims=True)
            logits = _dot(q_ref[0, j], k_ref[0, j // group], _NT) * scale
            acc_ref[...] = acc_ref[...] + jnp.exp(logits - lse)

        a_trip = _probs_trip(heads)

        def trip(i, carry):
            for u in range(a_trip):
                head(i * a_trip + u)
            return carry

        lax.fori_loop(0, heads // a_trip, trip, 0)
        # what the mask hides of a head may be inf, never NaN: selected
        # away once a tile, after the sum
        o_ref[0] = jnp.where(
            m_ref[0].astype(jnp.int32) != 0, acc_ref[...], 0.0)

    @pl.when(ki * bk > qi * bq + bq - 1)
    def _above():
        o_ref[0] = jnp.zeros((bq, bk), jnp.float32)


def _probs_pallas(q, k, lse, mask, scale: float, interpret: bool):
    """One grid step a (query block, key block) tile, as the index
    kernels': every head's q block and the rows' ``lse`` stay in VMEM
    for a row of tiles, every key head's k block comes a tile, and the
    heads are a loop inside, `_PROBS_HEADS_A_TRIP` a trip, summed in a
    scratch that is masked and written once a tile."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    bq, bk = _probs_tiles(s, h, hkv, d, q.dtype.itemsize)
    n_q, n_k = s // bq, s // bk

    def k_index(qi, ki):
        return jnp.minimum(ki, _last_k(qi, bq, bk, n_k))

    return pl.pallas_call(
        functools.partial(
            _probs_kernel, bq=bq, bk=bk, group=h // hkv, scale=scale),
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, h, bq, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, hkv, bk, d), lambda bi, qi, ki: (
                bi, 0, k_index(qi, ki), 0)),
            pl.BlockSpec((1, bq, h), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, bq, bk),
                         lambda bi, qi, ki: (bi, qi, k_index(qi, ki))),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, bk), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name="dsa_probs",
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      lse.transpose(0, 2, 1), mask)


def head_summed_probs(q, k, lse, mask, scale: float, *,
                      interpret: bool = False, mesh: Optional[Mesh] = None):
    """``p[t, s] = sum_h exp(scale q[t, h] . k[s, h // group] - lse[h,
    t])`` where ``mask[t, s]`` is set, 0 elsewhere: the main attention's
    probabilities summed over its heads, ``(b, s, s)`` float32. ``q (b,
    s, h, d)``, ``k (b, s, hkv, d)`` with ``group = h / hkv`` query
    heads on a key head (the key is read where it lies, never repeated)
    and ``lse (b, h, s)`` are the flash forward's operands and result
    under the same mask. Not differentiated."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"{q.shape[2]} query heads do not share {k.shape[2]} key heads "
            "evenly")
    q, k, lse = (lax.stop_gradient(a) for a in (q, k, lse))
    scale = float(scale)
    kernels = interpret or _on_tpu()
    # heads a trip of the kernel's loop; 0: XLA's form
    trace.gauge("dsa.probs_heads_a_trip",
                _probs_trip(q.shape[2]) if kernels else 0)
    if not kernels:
        return _probs_xla(q, k, lse, mask, scale)
    return _over_batch(
        lambda q, k, lse, mask: _probs_pallas(
            q, k, lse, mask, scale, interpret),
        mesh, q, k, lse, mask)


#: the name `selected_attention` gives the selection's mask: a checkpoint
#: policy that keeps it spares the recomputed forward the threshold's
#: counting passes
SELECT = "dsa_select"

#: the name `indexer_loss`'s forward gives ``d loss / d scores``: a
#: checkpoint policy that keeps it spares the recomputed forward the
#: score kernel, ``dsa_probs`` and the KL (``models/dots3.py _block_fn``)
LOSS_GRAD = "dsa_loss_grad"


def _kl_and_grad(scores, probs, mask):
    """The KL and its gradient with respect to ``scores`` from one set
    of passes: over a row's selection ``softmax(scores) x (the sum of
    the row's positive targets) - target``, zero elsewhere."""
    seen = mask != 0
    target = probs / jnp.sum(probs, axis=-1, keepdims=True)
    logq = jax.nn.log_softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1)
    live = seen & (target > 0.0)
    terms = jnp.where(
        live,
        target * (jnp.log(jnp.where(target > 0.0, target, 1.0)) - logq), 0.0)
    held = jnp.where(live, target, 0.0)
    grad = jnp.where(
        seen,
        jnp.exp(logq) * jnp.sum(held, axis=-1, keepdims=True) - held, 0.0)
    return jnp.sum(terms), grad


@jax.custom_vjp
def indexer_loss(scores, probs, mask):
    """``sum_t KL(p^_t || softmax_{s in S_t} scores[t, s])`` over every
    row of the batch, ``p^ = probs / sum_{s in S_t} probs`` a constant:
    a float32 scalar whose gradient reaches ``scores`` alone. Because
    ``p^`` is a constant that gradient is known in the forward, which
    forms it beside the KL and names it `LOSS_GRAD`; the backward scales
    it and reads nothing else."""
    return _kl_and_grad(scores, probs, mask)[0]


def _indexer_loss_fwd(scores, probs, mask):
    loss, grad = _kl_and_grad(scores, probs, mask)
    return loss, checkpoint_name(grad, LOSS_GRAD)


def _indexer_loss_bwd(grad, ct):
    with trace.scope("dsa_loss"):
        # formed once: without the barrier XLA forms the product again
        # inside the transpose the key-side score kernel's operand needs
        return lax.optimization_barrier(ct * grad), None, None


indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


# ---------------------------------------------------------------------------
# The whole sequence, for every family with an indexer
# ---------------------------------------------------------------------------

def selected_attention(q, k, v, index_q, index_k, index_w, topk: int,
                       scale: float, *, interpret: bool = False,
                       mesh: Optional[Mesh] = None):
    """Attention over the keys an indexer selects, and what the indexer
    learns from it: `index_scores` of ``index_q (b, s, hi, di)``,
    ``index_k (b, s, di)``, ``index_w (b, s, hi)`` (the family's own
    projections, already turned, under its stop-gradients) ->
    `selection_mask` at ``topk`` (named `SELECT`) ->
    ``flash_attention(select=)`` of ``q (b, s, h, d)`` on ``k, v (b, s,
    hkv, .)`` at ``scale`` -> `head_summed_probs` -> `indexer_loss`.
    Returns ``(out (b, s, h, dv), the KL summed over the rows, mask,
    scores)``. The one copy: latent attention (``models/dots3.py``) and
    grouped heads (``models/keye_vl.py``) both call it."""
    with trace.scope("dsa_index"):
        scores = index_scores(index_q, index_k, index_w,
                              interpret=interpret, mesh=mesh)
    with trace.scope("dsa_select"):
        mask = checkpoint_name(
            selection_mask(lax.stop_gradient(scores), topk,
                           interpret=interpret, mesh=mesh), SELECT)
    out, lse = flash_attention(
        q, k, v, causal=True, mesh=mesh, scale=scale, select=mask,
        interpret=interpret, return_lse=True)
    with trace.scope("dsa_loss"):
        probs = head_summed_probs(
            q, k, lse, mask, scale, interpret=interpret, mesh=mesh)
        l_i = indexer_loss(scores, probs, mask)
    return out, l_i, mask, scores
