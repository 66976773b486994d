"""Block-sparse attention's choice (InfLLM-V2, arXiv 2509.24663, as
MiniCPM4 and MiniCPM-SALA's ``minicpm4`` layers train it): every query
picks ``topk`` blocks of ``block`` keys, one choice a key-value group,
from keys pooled over windows. The choice has no parameters and takes no
gradient.

For key head ``g`` (its ``r`` query heads ``h``) and query position ``i``,
with windows of ``kernel`` keys at stride ``stride``::

    c_j      = mean(k_g[stride j : stride j + kernel])      pooled keys
    visible  : stride j + kernel - 1 <= i                   whole in the past
    p_(h,i,.) = softmax_j(q_(h,i) . c_j scale) over the visible j
    a_(i,j)  = sum_h p_(h,i,j)                               (0 where none)
    B_(i,b)  = max a_(i,j) over the j whose window meets block b
    forced   : b < init_blocks, or block b holds one of [i - window + 1, i]
    chosen   : the forced blocks and, of the others with block b <= i,
               those of largest B until ``topk`` are chosen, ties to the
               lower b

What this file computes:

- `pooled_keys`: ``c (b, n_c, g, d)`` in k's dtype (float32 sums).
- `block_scores`: ``B (b, g, s, s / block)`` float32. On the TPU a Pallas
  kernel (``blk_score``): a grid step is a tile of query rows of one
  group; it holds the group's pooled keys whole, forms each head's
  softmax over them, sums the heads and pools onto blocks in VMEM, so
  HBM never holds an ``(h, s, n_c)`` array. The pooled keys arrive
  ordered by ``j mod (block / stride)``, so the windows of a block are
  the same lane of ``block / stride`` aligned slabs (and one more lane to
  the left for the window that starts before the block). Off the TPU the
  same in XLA's ops, a block of query rows at a time.
- `pick_blocks`: the int8 ``(b, g, s, s / block)`` selection
  ``flash_attention(select=, select_block=)`` reads, by
  ``ops/dsa.py``'s exact threshold (bisection on the float's bits) over
  the block scores, the forced blocks set above every score.
- `selected_pairs`, `live_tiles`: counts of what was chosen.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import dsa
from dlrover_tpu.ops.attention import _NEG_INF, _NT, _VMEM_LIMIT, _dot
from dlrover_tpu.ops.kda import _over_batch_rows
from dlrover_tpu.parallel.mesh import BATCH_AXES

_F32 = jnp.float32
SCORE_ROWS = 256      # query rows a grid step of the scoring kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def n_pooled(s: int, kernel: int, stride: int) -> int:
    return (s - kernel) // stride + 1 if s >= kernel else 0


def pooled_keys(k, kernel: int, stride: int):
    """``k (b, s, g, d)`` -> ``(b, n_c, g, d)``: window ``j`` is the mean
    of keys ``stride j .. stride j + kernel - 1`` (``stride`` divides
    ``kernel`` and the sequence)."""
    b, s, g, d = k.shape
    if kernel % stride or s % stride:
        raise ValueError(f"pooled_keys: windows of {kernel} at stride "
                         f"{stride} over {s} keys")
    parts, n = kernel // stride, n_pooled(s, kernel, stride)
    sums = jnp.sum(k.astype(_F32).reshape(b, s // stride, stride, g, d), 2)
    pooled = sum(sums[:, o:o + n] for o in range(parts)) / kernel
    return pooled.astype(k.dtype)


def _windows_of_block(block: int, kernel: int, stride: int):
    """``(per, before)``: block ``b``'s windows are ``per b - before ..
    per b + per - 1``."""
    if block % stride:
        raise ValueError(f"a block of {block} keys at stride {stride}")
    return block // stride, kernel // stride - 1


def _scores_xla(q, c, block: int, kernel: int, stride: int, scale: float):
    b, s, h, d = q.shape
    n, g = c.shape[1:3]
    nb = s // block
    per, before = _windows_of_block(block, kernel, stride)
    rows = 128 if s % 128 == 0 else s
    last = stride * jnp.arange(n, dtype=jnp.int32) + kernel - 1

    def one(args):
        qb, i0 = args                                     # (b, rows, h, d)
        seen = last[None, :] <= (i0 + jnp.arange(rows, dtype=jnp.int32))[
            :, None]                                      # (rows, n)
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qb.reshape(b, rows, g, h // g, d), c,
            preferred_element_type=_F32) * scale
        logits = jnp.where(seen, logits, _NEG_INF)
        p = jnp.where(seen, jnp.exp(
            logits - jnp.max(logits, axis=-1, keepdims=True)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        a = jnp.sum(p / jnp.where(l == 0.0, 1.0, l), axis=2)  # (b, g, rows, n)
        a = jnp.pad(a, ((0, 0),) * 3 + ((before, per * nb + per - n),))
        return functools.reduce(jnp.maximum, (
            a[..., o:o + per * nb:per] for o in range(per + before)))

    out = lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // rows, rows, h, d), 1, 0),
        jnp.arange(0, s, rows, dtype=jnp.int32)))
    return jnp.moveaxis(out, 0, 2).reshape(b, g, s, nb)


def _score_kernel(q_ref, c_ref, o_ref, *, heads: int, nb: int, per: int,
                  kernel: int, stride: int, scale: float):
    rows = q_ref.shape[3]
    c = c_ref[0, 0]                                        # (per nb, d)
    shape = (rows, per * nb)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    # lane l of the ordered pooled keys is window per (l % nb) + l // nb
    window = per * (lane % nb) + lane // nb
    i = pl.program_id(2) * rows + lax.broadcasted_iota(jnp.int32, shape, 0)
    seen = stride * window + kernel - 1 <= i

    def head(hi, acc):
        logits = jnp.where(seen, _dot(q_ref[0, 0, hi], c, _NT) * scale,
                           _NEG_INF)
        p = jnp.where(seen, jnp.exp(
            logits - jnp.max(logits, axis=1, keepdims=True)), 0.0)
        l = jnp.sum(p, axis=1, keepdims=True)
        return acc + p / jnp.where(l == 0.0, 1.0, l)

    a = lax.fori_loop(0, heads, head, jnp.zeros(shape, _F32))
    slabs = [a[:, r * nb:(r + 1) * nb] for r in range(per)]
    # the window that starts before the block: the last slab's left lane
    # (an iota of its own: Mosaic aborts on a lane slice of the wide one)
    first = lax.broadcasted_iota(jnp.int32, (rows, nb), 1) == 0
    left = jnp.where(first, 0.0, pltpu.roll(slabs[-1], 1, 1))
    o_ref[0, 0] = functools.reduce(jnp.maximum, slabs + [left])


def _scores_pallas(q, c, block, kernel, stride, scale, interpret):
    b, s, h, d = q.shape
    n, g = c.shape[1:3]
    nb = s // block
    per, _ = _windows_of_block(block, kernel, stride)
    rows = SCORE_ROWS if s % SCORE_ROWS == 0 else s
    # window j to lane (j % per) nb + j // per; the windows past the last
    # are zeros no query sees
    ordered = jnp.pad(c, ((0, 0), (0, per * nb - n), (0, 0), (0, 0)))
    ordered = ordered.reshape(b, nb, per, g, d).transpose(0, 3, 2, 1, 4)
    qt = q.reshape(b, s, g, h // g, d).transpose(0, 2, 3, 1, 4)
    return pl.pallas_call(
        functools.partial(_score_kernel, heads=h // g, nb=nb, per=per,
                          kernel=kernel, stride=stride, scale=scale),
        grid=(b, g, s // rows),
        in_specs=[
            pl.BlockSpec((1, 1, h // g, rows, d),
                         lambda bi, gi, ti: (bi, gi, 0, ti, 0)),
            pl.BlockSpec((1, 1, per * nb, d),
                         lambda bi, gi, ti: (bi, gi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, nb),
                               lambda bi, gi, ti: (bi, gi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, s, nb), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="blk_score",
    )(qt, ordered.reshape(b, g, per * nb, d))


def block_scores(q, c, *, block: int, kernel: int, stride: int,
                 scale: float, interpret: bool = False,
                 mesh: Optional[Mesh] = None):
    """``q (b, s, h, d)``, pooled keys ``c (b, n_c, g, d)`` -> ``B (b, g,
    s, s / block)`` float32 (module docstring). The kernel takes windows
    of two strides, what the published sizes are; any other go to XLA's
    ops. The gauge ``attn.blk_score_kernel`` says which form ran."""
    kernels = (interpret or _on_tpu()) and kernel == 2 * stride
    trace.gauge("attn.blk_score_kernel", int(kernels))
    if not kernels:
        return _scores_xla(q, c, block, kernel, stride, scale)
    return _over_batch_rows(
        lambda q, c: _scores_pallas(q, c, block, kernel, stride, scale,
                                    interpret),
        mesh, (q, c), (), P(BATCH_AXES, None, None, None))


def forced_blocks(s: int, block: int, init_blocks: int, window: int):
    """``(eligible, forced)``, bool ``(s, s / block)``: the blocks that
    start at or before the query, and of them the first ``init_blocks``
    and those that hold one of the query's last ``window`` positions."""
    i = jnp.arange(s, dtype=jnp.int32)[:, None]
    first = block * jnp.arange(s // block, dtype=jnp.int32)[None, :]
    eligible = first <= i
    return eligible, eligible & ((first < block * init_blocks) | (
        first + block - 1 >= i - (window - 1)))


def pick_blocks(scores, *, block: int, topk: int, init_blocks: int,
                window: int):
    """``scores (b, g, s, s / block)`` -> int8 of that shape, 1 where the
    query's group attends to the block: the forced blocks and the
    best-scored others up to ``topk`` in all, ties to the lower block."""
    s, nb = scores.shape[-2:]
    eligible, forced = forced_blocks(s, block, init_blocks, window)
    if topk >= nb:
        return jnp.broadcast_to(eligible, scores.shape).astype(jnp.int8)
    bits = jnp.where(forced, jnp.uint32(0xFFFFFFFF), jnp.where(
        eligible, dsa._ordered_bits(scores), jnp.uint32(0)))
    tau, cut = (a[..., None] for a in dsa._threshold(bits, topk))
    at = jnp.arange(nb, dtype=jnp.int32)
    chosen = (bits > tau) | ((bits == tau) & (at <= cut))
    return (chosen & eligible).astype(jnp.int8)


def selected_pairs(s: int, block: int, topk: int) -> int:
    """(query, key) pairs one head attends over under a choice of
    ``topk`` blocks: a query with no more than ``topk`` blocks behind it
    sees them all, any other ``topk - 1`` whole blocks and its own up to
    itself. Whatever the weights."""
    i = np.arange(s, dtype=np.int64)
    chosen = np.minimum(i // block + 1, topk)
    return int(np.sum((chosen - 1) * block + i % block + 1))


def live_tiles(select, block: int, block_q: int, block_k: int):
    """Of the causal (q tile, k tile) visits of a ``block_q x block_k``
    walk, those in which any row of any group chose any block: ``select
    (b, g, s, s / block)`` -> int32 scalar."""
    b, g, s, nb = select.shape
    per = block_k // block
    any_row = jnp.any(select.reshape(b, g, s // block_q, block_q,
                                     nb // per, per) != 0, axis=(1, 3, 5))
    return jnp.sum(any_row, dtype=jnp.int32)
