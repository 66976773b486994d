"""Attention: jnp reference + Pallas TPU flash-attention forward AND backward.

Layout convention everywhere: ``(batch, seq, n_heads, head_dim)``; GQA via
``n_kv_heads <= n_heads`` (kv head ``h // group`` serves query head ``h``
— resolved in the kernels' BlockSpec index_maps, never materialized).

`flash_attention_with_lse` is a `jax.custom_vjp` returning ``(out, lse)``
where ``lse`` is the per-row logsumexp of the attention logits:

- **forward**: Pallas online-softmax kernel — O(seq) memory, MXU-tiled
  blocks, the s×s matrix never exists.
- **backward**: two Pallas kernels (dq, then dk/dv) that *recompute*
  probabilities blockwise from (q, k, v, lse) — also O(seq) memory. The
  ``lse`` output is differentiable: its cotangent folds into the standard
  flash-backward ``delta`` term (``ds = p * (dp - delta + g_lse)``), which
  is what lets ring attention merge per-chunk results by logsumexp and
  still get exact gradients through the merge.

How the three kernels walk the (q, k) plane:

- **tiles** come from `choose_tiles`: per kernel, from the sequence
  lengths, ``head_dim``, the GQA group, the operand dtype and the
  window, the largest that divide the sequences and fit
  ``_VMEM_BUDGET``; under a window those whose walk of the band
  computes least, a grid step counted as ``_STEP_PAIRS`` pairs.
  ``block_q=None, block_k=None`` on the public entry points means
  "choose"; there is no knob.
- **GQA**: forward and dq take a kv head's whole group of query heads
  as one ``(group * block_q, d)`` operand against one K/V block; dk/dv
  keeps its K/V block resident and sweeps (query head, q block). K and V
  are fetched once a group.
- **causal**: a block wholly above the diagonal costs neither compute
  nor DMA: its ``index_map`` names the block the pipeline already
  holds. (Masking only the blocks the diagonal crosses was measured
  and dropped: under 2 % of a kernel, PERF.md section 6, PR 26.)
- **window** (``window=w``, causal only): query ``i`` sees key ``j`` iff
  ``0 <= i - j < w``, its own position and the ``w - 1`` before it. The
  band has a lower edge too, so the walk is over the band alone: the
  grid's last axis is as long as the most blocks one q block (one k
  block, for dk/dv) needs, step ``j`` of it is block ``first + j``, and
  what lies past the block's last is neither computed nor fetched.
  A row's first fetched block can then be wholly masked for that row:
  the forward takes ``exp`` against 0 while a row's running max is
  still the mask's value (docs/design/kernels.md). The window calls are
  named ``attention_fwd_swa``, ``attention_bwd_dq_swa`` and
  ``attention_bwd_dkv_swa``. ``window >= seq`` is the causal call.
- **select** (``select=mask``, causal only): the mask is data. ``mask
  (b, sq, sk)`` int8 is nonzero where the query sees the key, *already
  under the causal mask* (``ops/dsa.py selection_mask`` makes it from
  the indexer's scores: a model that chooses its own keys). The three
  kernels read it tile by tile beside K and V (dk/dv its transpose,
  made once a call) in place of the positions' comparison; the walk is
  the causal walk, since a query's chosen keys lie in any block at or
  under the diagonal, and ``causal`` only says which blocks cannot hold
  a chosen pair. A row may choose nothing of a block it fetches, its
  first included: the forward guards its exponent as under a window.
  The calls are named ``attention_fwd_sel``, ``attention_bwd_dq_sel``
  and ``attention_bwd_dkv_sel``. A call without ``select`` has no such
  operand (it is not passed as "all").
- **select by blocks** (``select=``, ``select_block=n``): ``select (b,
  hkv, s, s / n)`` int8 is nonzero where the queries of a key-value
  group see a *block* of ``n`` keys (``ops/blocksel.py pick_blocks``: a
  model whose groups choose their own blocks); within a chosen block a
  query sees the keys at or before it. A key-level mask a group would be
  ``n`` times the bytes (512 MiB a layer at 16384 positions and two
  groups, against 8), so the kernels widen a tile themselves: forward
  and dq hold a q block's whole strip of blocks and spread it over a k
  block's lanes by one small product with a 0 / 1 matrix (a group's
  heads share it), dk/dv reads the transposed selection's ``block_k /
  n`` rows of its k block and repeats each over its keys' sublanes. The
  walk is the causal walk here too: the queries of a tile choose apart,
  and their union leaves next to no tile out. The calls are named
  ``attention_fwd_blk``, ``attention_bwd_dq_blk`` and
  ``attention_bwd_dkv_blk``.
- **operands** go to the MXU in the dtype they arrive in (bf16 under
  ``activation_dtype: bfloat16``, f32 in the CPU tests) and accumulate
  in f32; ``P`` and ``dS`` are rounded to that dtype before their
  matmuls; the scale is applied to f32 values. Max, sum, ``lse``,
  ``delta`` and every accumulator stay f32.

- **residuals**: the backward reads ``(q, k, v[, select], out, lse)``.
  The forward rules name ``out`` and ``lse`` (`KEPT`): a block whose
  checkpoint keeps the two (``models/stack.py recompute(keep=KEPT)``)
  recomputes q, k, v in its backward pass and never the forward kernel.

On the TPU backend the Pallas kernels are the path: one the chip's
compiler refuses fails the step. Off TPU both directions run the jnp
reference, so the same model code runs in CPU tests; ``interpret=True``
runs the Pallas kernels in interpreter mode for numerics tests without
a TPU.

The reference framework has no attention op at all (it launches
Megatron/DeepSpeed which own the math, SURVEY.md §2.8) — this is part of
the green-field TPU compute path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel.mesh import BATCH_AXES, TP

_NEG_INF = -1e30

# lse/delta carry a broadcast minor lane dim so TPU block shapes tile
# ((second-to-last, last) must be (divisible by 8, divisible by 128) or
# equal to the array dims — 8 lanes satisfies "equal", at 1/16th the HBM
# of upstream flash-attention's 128-lane convention)
_LSE_LANES = 8


def mha_reference_with_lse(
    q: jnp.ndarray,  # (b, sq, h, d)
    k: jnp.ndarray,  # (b, sk, hkv, d)
    v: jnp.ndarray,  # (b, sk, hkv, d)
    causal: bool = True,
    q_offset=0,
    k_offset=0,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    select=None,
):
    """Stable-softmax attention in float32, GQA-aware; returns
    ``(out (b,sq,h,dv), lse (b,h,sq))``. ``q_offset`` / ``k_offset`` are
    *global* positions of element 0 — this is what lets ring-attention
    chunks mask causally against each other. ``v`` may be narrower or
    wider than ``q`` and ``k`` (latent attention: 192 against 128);
    ``scale`` None is ``1 / sqrt(d)`` of the q/k width. ``window`` (with
    ``causal``): a query sees its own position and the ``window - 1``
    before it. ``select (b, sq, sk)``, or ``(b, hkv, sq, sk)`` for one a
    key-value head: nonzero where the query sees the key, the whole mask
    (it replaces the positions' comparison)."""
    assert window is None or causal, "a window is causal"
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = _scale_for(d, scale)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if select is not None:
        seen = (select != 0)[:, None] if select.ndim == 3 else jnp.repeat(
            select != 0, group, axis=1)
        logits = jnp.where(seen, logits, _NEG_INF)
    elif causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)  # (b, h, sq)
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def mha_reference(q, k, v, causal: bool = True, q_offset=0, k_offset=0,
                  scale: Optional[float] = None,
                  window: Optional[int] = None):
    return mha_reference_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset,
        scale=scale, window=window,
    )[0]


def _scale_for(d: int, scale: Optional[float]) -> float:
    """The softmax scale: the caller's (latent attention with yarn
    states its own), else ``1 / sqrt(d)`` of the q/k head width."""
    return 1.0 / math.sqrt(d) if scale is None else float(scale)


# ---------------------------------------------------------------------------
# Tiles: chosen by the kernel from what it can see
# ---------------------------------------------------------------------------

#: Scoped VMEM every kernel asks the compiler for. The default grant is
#: 16 MiB, which a (512, 1024) score tile with its temporaries already
#: overflows; a v5e core has 128 MiB of VMEM, v5p and v6e no less.
_VMEM_LIMIT = 64 * 2**20

#: What `choose_tiles` lets its own count of a kernel's blocks, scratch
#: and temporaries fill. Under the limit by the margin the count cannot
#: see: the compiler's own spills and relayout copies.
_VMEM_BUDGET = 40 * 2**20

#: Largest ``(rows, block_k)`` the chooser offers each kernel, where
#: ``rows`` is ``group * block_q`` for forward and dq (the GQA group is
#: one operand) and ``block_q`` for dk/dv. Measured on the v5e at seq
#: 4096, causal and not (PERF.md section 6, PR 26): the kernels are
#: within 5 % of their best from (1024, 512) up, and past these sides
#: the area a causal call computes and masks away, about
#: ``(block_q + block_k) / 2 / seq`` of it, outgrows the grid steps saved.
#: A window call masks away about ``(block_q + block_k) / window``, at two
#: edges: under one these are the largest sides offered, and
#: `choose_tiles` takes the pair whose walk of the band costs least.
_MAX_TILE = {"fwd": (2048, 512), "dq": (2048, 512), "dkv": (1024, 1024)}

_KERNELS = tuple(_MAX_TILE)

#: Under a window, what `choose_tiles` counts a grid step as, in pairs of
#: the score plane: 0.39 us a step where a computed pair costs 4.9 us a
#: million (the forward at group 1, where a step is small enough to show
#: it; 0.1-0.6 us over the three kernels and the tiles of the sweep:
#: docs/design/kernels.md 1b, PR 61, on the v5e).
_STEP_PAIRS = 80_000

#: Under a window, the narrowest ``block_k`` each kernel is offered.
#: dq's products at 256 keys cost more a pair than the band's edges give
#: back: 13 % slower at (256, 256) than at (256, 512) with a quarter
#: fewer pairs (window 512, group 8), 9 % slower at window 4096. The
#: forward at 128 keys costs a fifth more than at 256 by the same count
#: of pairs and steps. dk/dv needs no floor but that count.
_WINDOW_MIN_K = {"fwd": 256, "dq": 512, "dkv": 0}

_STAT_LANES = 128  # running max / sum: every lane of a row holds it


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(kernel: str, bq: int, bk: int, d: int, group: int,
                itemsize: int, dv: Optional[int] = None) -> int:
    """VMEM one grid step of ``kernel`` occupies at tiles ``(bq, bk)``:
    the pipelined (double-buffered) blocks, the scratch accumulators and
    the ``(rows, bk)`` temporaries of the body, minor dims padded to the
    128 lanes they take. ``d`` is the width of a q/k head, ``dv`` that
    of a v head (and of out, do and dv), ``d`` where None."""
    dl = _round_up(d, 128)
    dvl = dl if dv is None else _round_up(dv, 128)
    stat = _STAT_LANES * 4  # one row of lse / delta / running max, f32
    if kernel == "dkv":
        tile = _round_up(bk, 8) * _round_up(bq, 128)
        blocks = 2 * (bq * (dl + dvl) * itemsize      # q, do
                      + 2 * bk * (dl + dvl) * itemsize  # k, v, dk, dv
                      + 2 * 8 * _round_up(bq, 128) * 4)  # lse, delta rows
        scratch = bk * (dl + dvl) * 4
        return blocks + scratch + tile * (4 * 4 + itemsize)
    rows = group * bq
    tile = _round_up(rows, 8) * _round_up(bk, 128)
    if kernel == "fwd":
        blocks = 2 * (rows * (dl + dvl) * itemsize    # q, out
                      + bk * (dl + dvl) * itemsize    # k, v
                      + rows * stat)                  # lse
        scratch = rows * dvl * 4 + 2 * rows * stat    # acc, m, l
        return blocks + scratch + tile * (3 * 4 + itemsize)
    blocks = 2 * (rows * (2 * dl + dvl) * itemsize    # q, dq, do
                  + bk * (dl + dvl) * itemsize        # k, v
                  + 2 * rows * stat)                  # lse, delta
    scratch = rows * dl * 4
    return blocks + scratch + tile * (4 * 4 + itemsize)


def _tile_sides(s: int, cap: int, lanes_only: bool):
    """Tile sides for a sequence of ``s``, best first: its divisors up
    to ``cap`` that are multiples of 128 (whole MXU passes, whole vector
    registers). A sequence 128 does not divide goes as one block, which
    is always legal, if that fits; else in the multiples of 8 a block
    may start at (not where the side is a block's lane dim)."""
    def divisors(align):
        return [t for t in range(min(cap, s - 1) // align * align, 0, -align)
                if s % t == 0]

    if s % 128 == 0:
        return [s] * (s <= cap) + divisors(128)
    return [s] + ([] if lanes_only else divisors(8))


def choose_tiles(kernel: str, sq: int, sk: int, head_dim: int, group: int,
                 dtype, v_head_dim: Optional[int] = None,
                 window: Optional[int] = None
                 ) -> Optional[Tuple[int, int]]:
    """``(block_q, block_k)`` for one of the three kernels (``"fwd"``,
    ``"dq"``, ``"dkv"``), among the pairs of sides `_tile_sides` offers
    up to ``_MAX_TILE`` whose `_vmem_bytes` fit ``_VMEM_BUDGET``: the
    pair of largest area. ``None`` if not even the smallest pair fits (a
    sequence with no aligned divisor that is too long to be one block):
    the caller has the reference path. Causal or not does not enter: on
    the chip both want the same tiles. ``head_dim`` is the q/k head's
    width, ``v_head_dim`` the v head's where it differs.

    Under a ``window`` shorter than the sequence (of causal
    self-attention: ``sq == sk``) the largest area is not the least
    work: a call computes, and masks away, whatever of its visited
    blocks lies outside the band, about ``(block_q + block_k) / window``
    of it at the band's two edges. The pair is then the one whose walk
    of the band costs least by `band_work`'s count: the pairs it
    computes plus ``_STEP_PAIRS`` for every grid step, among the pairs
    no narrower in ``block_k`` than ``_WINDOW_MIN_K`` (where the
    sequence offers such a side). At a window of 4096 that is the pair
    of largest area again; at 512 dk/dv takes (512, 512), where (1024,
    1024) leaves a quarter of its pairs under the band, and the forward
    at group 8 (256, 256) (docs/design/kernels.md 1b, PR 61).

    Short and awkward sequences come out as before there was a
    chooser: 8 and 64 as one block, 196 and 197 as one block, anything
    128 divides at 128 or more."""
    max_rows, max_k = _MAX_TILE[kernel]
    itemsize = jnp.dtype(dtype).itemsize
    if kernel == "dkv":
        # block_q is the lane dim of the transposed score tile and of
        # the lse / delta rows
        q_sides = _tile_sides(sq, max_rows, lanes_only=True)
    else:
        q_sides = _tile_sides(sq, max(max_rows // group, 128),
                              lanes_only=False)
    k_sides = _tile_sides(sk, max_k, lanes_only=False)
    # the sides descend, so of equals the first met has the wider block_k
    fits = [(bq, bk) for bk in k_sides for bq in q_sides
            if _vmem_bytes(kernel, bq, bk, head_dim, group, itemsize,
                           v_head_dim) <= _VMEM_BUDGET]
    if not fits:
        return None
    if window is None or window >= sq:
        # largest area; of equals the squarer
        return max(fits, key=lambda t: (t[0] * t[1], min(t)))
    fits = [t for t in fits if t[1] >= _WINDOW_MIN_K[kernel]] or fits

    def cost(tile):
        work = band_work(kernel, sq, *tile, group, window)
        return work["computed"] + _STEP_PAIRS * work["steps"]

    # least cost; of equals the larger area
    return min(fits, key=lambda t: (cost(t), -t[0] * t[1]))


def band_work(kernel: str, s: int, block_q: int, block_k: int, group: int,
              window: int) -> dict:
    """What one kv head's walk of the band costs ``kernel`` at these
    tiles, by `_band_blocks`' count: the pairs it ``computed`` (the
    visited blocks x ``block_q`` x ``block_k``, every head of the
    group), the ``band``'s own (``0 <= i - j < window``) and the grid's
    ``steps``, the empty ones of the shorter sweeps included."""
    n_q, n_k = s // block_q, s // block_k
    blocks = _band_blocks("q" if kernel == "dkv" else "k", n_q, n_k,
                          block_q, block_k, window)
    heads = group if kernel == "dkv" else 1  # forward and dq: one operand
    w = min(window, s)
    return {"computed": group * sum(blocks) * block_q * block_k,
            "band": group * (w * (w + 1) // 2 + (s - w) * w),
            "steps": heads * len(blocks) * max(blocks)}


def flash_tiles(sq: int, sk: int, head_dim: int, group: int, dtype,
                v_head_dim: Optional[int] = None,
                window: Optional[int] = None):
    """``{kernel: (block_q, block_k)}`` for the three kernels of one
    call, or None if one of them has no tile that fits."""
    tiles = {
        kernel: choose_tiles(kernel, sq, sk, head_dim, group, dtype,
                             v_head_dim, window)
        for kernel in _KERNELS
    }
    return None if None in tiles.values() else tiles


#: call sites of this build that got tiles of 128 or less
_fallback_sites = 0


def reset_tile_report():
    """A step build starts: `ElasticTrainer.lower_step` calls this
    before it traces, so ``attn.tile_fallback`` counts one build's call
    sites."""
    global _fallback_sites
    _fallback_sites = 0
    trace.gauge("attn.tile_fallback", 0)


def _tiles_for(q, k, v, block_q, block_k, window: Optional[int] = None):
    """``{kernel: (block_q, block_k)}`` for this call: a pinned pair
    goes to all three kernels; None (both) is `flash_tiles`' choice,
    made while the step is traced, from the call's effective window
    too."""
    if block_q is not None or block_k is not None:
        assert block_q is not None and block_k is not None, (block_q, block_k)
        return dict.fromkeys(_KERNELS, (block_q, block_k))
    sq, h, d = q.shape[1:]
    sk, hkv = k.shape[1:3]
    tiles = flash_tiles(sq, sk, d, h // hkv, q.dtype, v.shape[3], window)
    if tiles is None:
        raise ValueError(
            f"flash attention: no tile of seq ({sq}, {sk}) at head_dim "
            f"{d} (v {v.shape[3]}), group {h // hkv} fits {_VMEM_BUDGET} "
            "bytes of VMEM; "
            "pad the sequence to a multiple of 128 or take mha_reference"
        )
    return tiles


#: matmuls a pair of each kernel: what weighs its computed pairs
_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def _report_tiles(tiles: dict, seq: int = 0, window: Optional[int] = None):
    """The gauges that say which tiling the job runs: the forward's own
    chosen tiles (a window call's under names of their own, with dk/dv's
    beside them and the share of the pairs the three kernels compute,
    weighted by their products, that lie under the band), and the count
    of call sites left at 128 or less."""
    global _fallback_sites
    block_q, block_k = tiles["fwd"]
    kind = "" if window is None else "window_"
    trace.gauge(f"attn.{kind}block_q", block_q)
    trace.gauge(f"attn.{kind}block_k", block_k)
    if window is not None:
        trace.gauge("attn.window_dkv_block_q", tiles["dkv"][0])
        trace.gauge("attn.window_dkv_block_k", tiles["dkv"][1])
        work = {kernel: band_work(kernel, seq, *tiles[kernel], 1, window)
                for kernel in _KERNELS}
        trace.gauge("attn.window_band_pct", round(
            100 * sum(_PRODUCTS.values()) * work["fwd"]["band"]
            / sum(n * work[kernel]["computed"]
                  for kernel, n in _PRODUCTS.items()), 1))
    if max(block_q, block_k) <= 128:
        _fallback_sites += 1
        trace.gauge("attn.tile_fallback", _fallback_sites)


# ---------------------------------------------------------------------------
# What the kernels share
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lane_fill(x, n: int):
    """``x`` (rows, lanes) with every lane of a row equal -> (rows, n),
    by whole-register copies where ``n`` allows it."""
    lanes = x.shape[1]
    if n == lanes:
        return x
    if n % lanes == 0:
        return pltpu.repeat(x, n // lanes, axis=1)
    if n < lanes:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _clip_tiles(sq, sk, block_q, block_k):
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    return block_q, block_k


def _last_k_block(qi, block_q: int, block_k: int, n_k: int):
    """Last k block the q block ``qi`` needs under the causal mask."""
    return jnp.minimum(((qi + 1) * block_q - 1) // block_k, n_k - 1)


def _first_q_block(ki, block_q: int, block_k: int, n_q: int):
    """First q block the k block ``ki`` needs under the causal mask."""
    return jnp.minimum((ki * block_k) // block_q, n_q - 1)


def _first_k_block(qi, block_q: int, block_k: int, window: int):
    """First k block the q block ``qi`` needs under a window: the one
    that holds its first row's oldest key."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _last_q_block(ki, block_q: int, block_k: int, n_q: int, window: int):
    """Last q block the k block ``ki`` needs under a window: the one
    that holds the last query to see its last key."""
    return jnp.minimum(
        (ki * block_k + block_k - 1 + window - 1) // block_q, n_q - 1)


def _band_blocks(walk: str, n_q: int, n_k: int, block_q: int, block_k: int,
                 window: int) -> list:
    """The blocks a window call visits: of every q block its k blocks
    (``walk="k"``: forward and dq), of every k block its q blocks
    (``walk="q"``: dk/dv). The four edges above again, on Python ints."""
    if walk == "k":
        return [
            min(((qi + 1) * block_q - 1) // block_k, n_k - 1)
            - max(qi * block_q - (window - 1), 0) // block_k + 1
            for qi in range(n_q)]
    return [
        min((ki * block_k + block_k - 1 + window - 1) // block_q, n_q - 1)
        - min(ki * block_k // block_q, n_q - 1) + 1
        for ki in range(n_k)]


def _band_steps(walk: str, n_q: int, n_k: int, block_q: int, block_k: int,
                window: int) -> int:
    """The length of a window call's inner grid axis: the most k blocks
    one q block needs or the most q blocks one k block needs
    (`_band_blocks`); a grid's length is static."""
    return max(_band_blocks(walk, n_q, n_k, block_q, block_k, window))


def _when_needed(causal: bool, qi, ki, block_q: int, block_k: int):
    """Decorator: run the body unless the (qi, ki) block of the score
    plane lies wholly above the causal diagonal."""
    if not causal:
        return lambda body: body()
    return pl.when(ki * block_k <= qi * block_q + block_q - 1)


def _causal_mask(qi, ki, group: int, block_q: int, block_k: int,
                 window: Optional[int] = None):
    """(group * block_q, block_k) bool: query position >= key position
    and, under a window, less than ``window`` past it. The group's heads
    repeat the q block's positions."""
    shape = (group, block_q, block_k)
    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, shape, 1)
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, shape, 2)
    rows = (group * block_q, block_k)
    qpos, kpos = qpos.reshape(rows), kpos.reshape(rows)
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


def _name_suffix(window: Optional[int], select: bool = False,
                 select_block: int = 0) -> str:
    """What a window call's or a selection call's kernels are named by,
    after the prefix the plain calls have: a trace tells the kinds of
    layer apart."""
    if select:
        return "_blk" if select_block else "_sel"
    return "" if window is None else "_swa"


def _split_select(refs, select: bool):
    """A kernel's refs after K and V: ``(the selection's tile or None,
    the rest)``."""
    return (refs[0], refs[1:]) if select else (None, refs)


def _selected(sel_ref, group: int):
    """(group * rows, cols) bool from the selection's (1, rows, cols)
    int8 tile; the group's heads repeat the q block's rows."""
    return _over_group(sel_ref[0].astype(jnp.int32) != 0, group)


def _over_group(seen, group: int):
    """(rows, cols) bool -> (group * rows, cols): the group's heads
    repeat the q block's rows."""
    if group == 1:
        return seen
    rows, cols = seen.shape
    return jnp.broadcast_to(
        seen[None], (group, rows, cols)).reshape(group * rows, cols)


def _selected_blocks(sel_ref, qi, ki, group: int, block_q: int,
                     block_k: int, select_block: int):
    """(group * block_q, block_k) bool from a q block's strip of the
    selection by blocks, ``(1, 1, block_q, s / select_block)`` int8: key
    ``t`` of the k block takes the column of its block (a product with a
    0 / 1 matrix spreads the strip over the lanes), under the causal
    mask."""
    strip = sel_ref[0, 0].astype(jnp.int32).astype(jnp.float32)
    n = strip.shape[1]
    shape = (n, block_k)
    key = ki * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    spread = (lax.broadcasted_iota(jnp.int32, shape, 0)
              == key // select_block).astype(jnp.bfloat16)
    seen = _dot(strip.astype(jnp.bfloat16), spread, _NN) > 0.5
    return _over_group(seen & _causal_mask(qi, ki, 1, block_q, block_k),
                       group)


def _selected_blocks_t(sel_ref, qi, ki, block_q: int, block_k: int,
                       select_block: int):
    """(block_k, block_q) bool from the transposed selection's rows of
    this k block, ``(1, 1, 1, block_k / select_block, block_q)`` int8:
    each row repeated over its block's keys (sublanes), under the causal
    mask."""
    rows = sel_ref[0, 0, 0].astype(jnp.int32) != 0
    seen = jnp.concatenate([
        jnp.broadcast_to(rows[r:r + 1], (select_block, block_q))
        for r in range(block_k // select_block)])
    kpos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    qpos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    return seen & (qpos >= kpos)


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    group: int, block_q: int, block_k: int, n_kblocks: int,
    causal: bool, scale: float, window: Optional[int] = None,
    select: bool = False, select_block: int = 0
):
    sel_ref, (o_ref, lse_ref, acc_ref, m_ref, l_ref) = _split_select(
        rest, select)
    qi = pl.program_id(2)
    step = pl.program_id(3)
    rows, dv = acc_ref.shape          # out is as wide as a v head
    d = q_ref.shape[-1]               # scores run over the q/k width
    last_k = (_last_k_block(qi, block_q, block_k, n_kblocks) if causal
              else n_kblocks - 1)
    # under a window the inner axis walks the band: step 0 is the q
    # block's first k block, not k block 0
    ki = step if window is None else (
        _first_k_block(qi, block_q, block_k, window) + step)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # without a window k block 0 is every row's first and holds its
    # position 0, so the running max is a real score before any wholly
    # masked row of a later block meets it (exp(-1e30 - m) == 0, never
    # exp(0)). Under a window a row's first fetched block can lie wholly
    # before its oldest key: while a row's max is still the mask's value
    # the exponent is taken against 0, so p, l and acc stay 0. A row
    # under a selection may have chosen nothing of block 0 either
    @_when_needed(causal, qi, ki, block_q, block_k)
    def _compute():
        q = q_ref[0, 0].reshape(rows, d)                     # (G*bq, d)
        k = k_ref[0, 0]                                      # (bk, d)
        v = v_ref[0, 0]
        s = _dot(q, k, _NT) * scale                          # (G*bq, bk) f32
        if select_block:
            s = jnp.where(_selected_blocks(
                sel_ref, qi, ki, group, block_q, block_k, select_block),
                s, _NEG_INF)
        elif select:
            s = jnp.where(_selected(sel_ref, group), s, _NEG_INF)
        elif causal:
            s = jnp.where(
                _causal_mask(qi, ki, group, block_q, block_k, window),
                s, _NEG_INF)
        m_prev = m_ref[...]                                  # (G*bq, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_exp = m_next if window is None and not select else jnp.where(
            m_next < 0.5 * _NEG_INF, 0.0, m_next)
        p = jnp.exp(s - _lane_fill(m_exp, block_k))
        corr = jnp.exp(m_prev - m_next)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lane_fill(corr, dv) + _dot(
            p.astype(v.dtype), v, _NN
        )

    @pl.when(ki == last_k)
    def _finalize():
        l = l_ref[...]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / _lane_fill(lsafe, dv)
        o_ref[0, 0] = out.reshape(group, block_q, dv).astype(o_ref.dtype)
        # lse carries a broadcast minor lane dim for TPU block tiling
        # (see _LSE_LANES)
        lse = (m_ref[...] + jnp.log(lsafe))[:, :_LSE_LANES]
        lse_ref[0, 0] = lse.reshape(group, block_q, _LSE_LANES)


def _kv_specs(block_k: int, d: int, dv: int, causal: bool, block_q: int,
              n_k: int, window: Optional[int] = None):
    """K and V BlockSpecs of the (b, hkv, n_q, n_k) grids. Above the
    causal diagonal the index stays on the last block the q block
    needs: the pipeline sees an unchanged index and issues no DMA.
    Under a window the inner axis counts from the q block's first k
    block (`_band_steps` long)."""
    def index(bi, hi, qi, ki):
        if window is not None:
            ki = ki + _first_k_block(qi, block_q, block_k, window)
        if causal:
            ki = jnp.minimum(ki, _last_k_block(qi, block_q, block_k, n_k))
        return (bi, hi, ki, 0)

    return (pl.BlockSpec((1, 1, block_k, d), index),
            pl.BlockSpec((1, 1, block_k, dv), index))


def _select_specs(select, block_q: int, block_k: int, causal: bool,
                  n_k: int, select_block: int = 0):
    """The selection's BlockSpec of the (b, hkv, n_q, n_k) grids, as a
    list (empty without one): the (block_q, block_k) tile of the K / V
    block's step, every head's the same; of a selection by blocks the
    key-value head's own strip of the q block, whole (fetched once a q
    block)."""
    if select is None:
        return []
    if select_block:
        return [pl.BlockSpec((1, 1, block_q, select.shape[-1]),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0))]

    def index(bi, hi, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_k_block(qi, block_q, block_k, n_k))
        return (bi, qi, ki)

    return [pl.BlockSpec((1, block_q, block_k), index)]


def _operands(select):
    """A call's selection as its kernels' extra operand, if it has one."""
    return () if select is None else (select,)


def _flash_fwd_pallas(q, k, v, causal: bool, block_q: int, block_k: int,
                      interpret: bool = False,
                      scale: Optional[float] = None,
                      window: Optional[int] = None, select=None,
                      select_block: int = 0):
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    block_q, block_k = _clip_tiles(sq, sk, block_q, block_k)
    n_q, n_k = sq // block_q, sk // block_k
    rows = group * block_q
    k_steps = n_k if window is None else _band_steps(
        "k", n_q, n_k, block_q, block_k, window)

    # (b, s, h, d) → (b, h, s, d) so the contiguous minor dims tile
    # cleanly; the query heads of a kv head are adjacent, so splitting
    # h into (hkv, group) is free
    qt = q.transpose(0, 2, 1, 3).reshape(b, hkv, group, sq, d)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    def q_rows(lanes):
        return pl.BlockSpec((1, 1, group, block_q, lanes),
                            lambda bi, hi, qi, ki: (bi, hi, 0, qi, 0))

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, group=group, block_q=block_q,
            block_k=block_k, n_kblocks=n_k, causal=causal,
            scale=_scale_for(d, scale), window=window,
            select=select is not None, select_block=select_block,
        ),
        grid=(b, hkv, n_q, k_steps),
        in_specs=[q_rows(d), *_kv_specs(block_k, d, dv, causal, block_q,
                                        n_k, window),
                  *_select_specs(select, block_q, block_k, causal, n_k,
                                 select_block)],
        out_specs=[q_rows(dv), q_rows(_LSE_LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hkv, group, sq, _LSE_LANES),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="attention_fwd" + _name_suffix(window, select is not None,
                                            select_block),
    )(qt, kt, vt, *_operands(select))
    out = out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
    return out, lse.reshape(b, h, sq, _LSE_LANES)[..., 0]


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels
# ---------------------------------------------------------------------------
#
# Standard flash backward, blockwise recompute from (q, k, v, lse):
#   p  = exp(s - lse)            s = scale * q @ k^T  (+ causal mask)
#   dp = do @ v^T
#   ds = p * (dp - delta) * scale     delta = rowsum(do * o) - g_lse
#   dq = ds @ k ; dk = ds^T @ q ; dv = p^T @ do
# dq iterates k blocks per q block, the GQA group as one operand like
# the forward. dk/dv iterates (query head of the group, q block) per k
# block and computes the transposed tiles s^T, p^T, dp^T, ds^T
# directly, so every product is a plain a @ b or a @ b^T and lse /
# delta broadcast along sublanes from a (1, block_q) row. The scale
# factor of ds is applied once to the finished dq / dk accumulators.


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, *rest,
    group: int, block_q: int, block_k: int, n_kblocks: int,
    causal: bool, scale: float, window: Optional[int] = None,
    select: bool = False, select_block: int = 0
):
    sel_ref, (do_ref, lse_ref, delta_ref, dq_ref, acc_ref) = _split_select(
        rest, select)
    qi = pl.program_id(2)
    step = pl.program_id(3)
    rows, d = acc_ref.shape
    last_k = (_last_k_block(qi, block_q, block_k, n_kblocks) if causal
              else n_kblocks - 1)
    ki = step if window is None else (
        _first_k_block(qi, block_q, block_k, window) + step)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @_when_needed(causal, qi, ki, block_q, block_k)
    def _compute():
        q = q_ref[0, 0].reshape(rows, d)
        do = do_ref[0, 0].reshape(rows, do_ref.shape[-1])
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        lse = lse_ref[0, 0].reshape(rows, _LSE_LANES)[:, :1]     # (G*bq, 1)
        delta = delta_ref[0, 0].reshape(rows, _LSE_LANES)[:, :1]
        s = _dot(q, k, _NT) * scale
        if select_block:
            s = jnp.where(_selected_blocks(
                sel_ref, qi, ki, group, block_q, block_k, select_block),
                s, _NEG_INF)
        elif select:
            s = jnp.where(_selected(sel_ref, group), s, _NEG_INF)
        elif causal:
            s = jnp.where(
                _causal_mask(qi, ki, group, block_q, block_k, window),
                s, _NEG_INF)
        p = jnp.exp(s - lse)                                     # (G*bq, bk)
        ds = p * (_dot(do, v, _NT) - delta)
        acc_ref[...] = acc_ref[...] + _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(ki == last_k)
    def _finalize():
        dq = acc_ref[...] * scale
        dq_ref[0, 0] = dq.reshape(group, block_q, d).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, *rest,
    block_q: int, block_k: int, n_qblocks: int, causal: bool,
    scale: float, window: Optional[int] = None, q_steps: int = 0,
    select: bool = False, select_block: int = 0
):
    # with a selection, its transposed (block_k, block_q) tile
    sel_ref, (do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
              dk_acc, dv_acc) = _split_select(rest, select)
    # kv-head-major: grid dim 1 is the KV head; dim 3 sweeps
    # (query_head_in_group, q_block) pairs so the group's contributions
    # accumulate in VMEM and dk/dv are written once per kv head — no
    # (b, h, sk, d) per-query-head buffers in HBM (round-2 Weak #7).
    ki = pl.program_id(2)
    j = pl.program_id(3)
    if window is None:
        qi = j % n_qblocks
    else:
        # a head's sweep walks the band: q_steps blocks from the first
        # that sees this k block
        qi = _first_q_block(ki, block_q, block_k, n_qblocks) + j % q_steps

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def needed(body):
        if window is None:
            return _when_needed(causal, qi, ki, block_q, block_k)(body)
        # the blocks of the sweep past the band's last compute nothing
        return pl.when(qi <= _last_q_block(
            ki, block_q, block_k, n_qblocks, window))(body)

    @needed
    def _compute():
        q = q_ref[0, 0]                                       # (bq, d)
        do = do_ref[0, 0]
        k = k_ref[0, 0]                                       # (bk, d)
        v = v_ref[0, 0]
        lse = lse_ref[0, 0]                                   # (1, bq)
        delta = delta_ref[0, 0]
        st = _dot(k, q, _NT) * scale                          # (bk, bq)
        if select_block:
            st = jnp.where(_selected_blocks_t(
                sel_ref, qi, ki, block_q, block_k, select_block),
                st, _NEG_INF)
        elif select:
            st = jnp.where(_selected(sel_ref, 1), st, _NEG_INF)
        elif causal:
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            seen = qpos >= kpos
            if window is not None:
                seen &= qpos - kpos < window
            st = jnp.where(seen, st, _NEG_INF)
        pt = jnp.exp(st - lse)
        # dv += p^T @ do
        dv_acc[...] = dv_acc[...] + _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v, do, _NT) - delta)
        # dk += ds^T @ q
        dk_acc[...] = dk_acc[...] + _dot(dst.astype(q.dtype), q, _NN)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, g_lse, causal,
                      dq_tiles, dkv_tiles, interpret=False, scale=None,
                      window: Optional[int] = None, select=None,
                      select_block: int = 0):
    b, sq, h, d = q.shape
    sk, hkv, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    scale = _scale_for(d, scale)

    # delta rows; the lse cotangent folds in here (see module docstring)
    delta = jnp.einsum(
        "bshd,bshd->bhs", do.astype(jnp.float32), o.astype(jnp.float32)
    )
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)

    # -- dq: grid (b, hkv, n_q, n_k), the group's q block fixed, k rotates --
    block_q, block_k = _clip_tiles(sq, sk, *dq_tiles)
    n_q, n_k = sq // block_q, sk // block_k
    k_steps = n_k if window is None else _band_steps(
        "k", n_q, n_k, block_q, block_k, window)

    def q_rows(lanes):
        return pl.BlockSpec((1, 1, group, block_q, lanes),
                            lambda bi, hi, i, j: (bi, hi, 0, i, 0))

    def lanes8(x):
        # broadcast minor lane dim for TPU block tiling (see fwd kernel)
        return jnp.broadcast_to(
            x.reshape(b, hkv, group, sq, 1), (b, hkv, group, sq, _LSE_LANES)
        )

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, group=group, block_q=block_q,
            block_k=block_k, n_kblocks=n_k, causal=causal, scale=scale,
            window=window, select=select is not None,
            select_block=select_block,
        ),
        grid=(b, hkv, n_q, k_steps),
        in_specs=[
            q_rows(d),
            *_kv_specs(block_k, d, dv, causal, block_q, n_k, window),
            *_select_specs(select, block_q, block_k, causal, n_k,
                           select_block),
            q_rows(dv), q_rows(_LSE_LANES), q_rows(_LSE_LANES),
        ],
        out_specs=q_rows(d),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((group * block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="attention_bwd_dq" + _name_suffix(window, select is not None,
                                               select_block),
    )(
        qt.reshape(b, hkv, group, sq, d), kt, vt, *_operands(select),
        dot.reshape(b, hkv, group, sq, dv), lanes8(lse), lanes8(delta),
    )

    # -- dk/dv: kv-head-major grid (b, hkv, n_k, group*n_q): the group's
    # query heads accumulate into one VMEM scratch per kv head, so HBM
    # holds (b, hkv, sk, d) outputs — group x less traffic than the
    # per-query-head form (round-2 Weak #7), which matters at 8:1 GQA.
    block_q, block_k = _clip_tiles(sq, sk, *dkv_tiles)
    n_q, n_k = sq // block_q, sk // block_k
    q_steps = n_q if window is None else _band_steps(
        "q", n_q, n_k, block_q, block_k, window)

    def q_head(bi, hi, i, j):
        # above the diagonal the index waits on the first q block this
        # k block needs: no DMA for blocks that compute nothing
        qi = j % q_steps
        if window is not None:
            # the band's sweep starts there, and waits on its last
            qi = jnp.minimum(
                qi + _first_q_block(i, block_q, block_k, n_q),
                _last_q_block(i, block_q, block_k, n_q, window))
        elif causal:
            qi = jnp.maximum(qi, _first_q_block(i, block_q, block_k, n_q))
        return (bi, hi * group + j // q_steps, qi, 0)

    def q_head_row(bi, hi, i, j):
        bi, head, qi, _ = q_head(bi, hi, i, j)
        return (bi, head, 0, qi)

    def kv_block(lanes):
        return pl.BlockSpec((1, 1, block_k, lanes),
                            lambda bi, hi, i, j: (bi, hi, i, 0))

    # dk/dv computes transposed tiles: the selection transposed, once
    # (by blocks: its rows a k block, ``block_k / select_block`` of them)
    if select is None:
        select_t, select_t_specs = (), []
    elif select_block:
        if block_k % select_block:
            raise ValueError(f"flash attention: a k block of {block_k} "
                             f"keys is no whole blocks of {select_block}")
        select_t = (jnp.swapaxes(select, 2, 3).reshape(
            b, hkv, n_k, block_k // select_block, sq),)
        select_t_specs = [pl.BlockSpec(
            (1, 1, 1, block_k // select_block, block_q),
            lambda bi, hi, i, j: (bi, hi, i, 0, q_head(bi, hi, i, j)[2]))]
    else:
        select_t = (jnp.swapaxes(select, 1, 2),)
        select_t_specs = [pl.BlockSpec(
            (1, block_k, block_q),
            lambda bi, hi, i, j: (bi, i, q_head(bi, hi, i, j)[2]))]

    dkh, dvh = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            n_qblocks=n_q, causal=causal, scale=scale, window=window,
            q_steps=q_steps, select=select is not None,
            select_block=select_block,
        ),
        grid=(b, hkv, n_k, group * q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_head),
            kv_block(d), kv_block(dv),
            *select_t_specs,
            pl.BlockSpec((1, 1, block_q, dv), q_head),
            pl.BlockSpec((1, 1, 1, block_q), q_head_row),
            pl.BlockSpec((1, 1, 1, block_q), q_head_row),
        ],
        out_specs=[kv_block(d), kv_block(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="attention_bwd_dkv" + _name_suffix(window, select is not None,
                                                select_block),
    )(qt, kt, vt, *select_t, dot, lse.reshape(b, h, 1, sq), delta.reshape(b, h, 1, sq))

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return dq, dkh.transpose(0, 2, 1, 3), dvh.transpose(0, 2, 1, 3)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: the names both forwards give their output and its ``lse``, which with
#: q, k, v (and the selection) are all the backward kernels read: a
#: checkpoint policy that keeps the two spares the recomputed forward the
#: forward kernel (``models/stack.py recompute(keep=KEPT)``). Under
#: ``nothing_saveable``, and outside a checkpoint, a name is an identity.
KEPT = ("attn_out", "attn_lse")


def _named(out, lse):
    return checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[1])


def report_kept(name: str):
    """A ``recompute(kept=)`` callback: the gauge ``attn.out_kept`` reads
    1 once a block's checkpoint has met a forward's output and kept it."""
    if name == KEPT[0]:
        trace.gauge("attn.out_kept", 1)


# ---------------------------------------------------------------------------
# custom_vjp surfaces
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False,
                             scale: Optional[float] = None,
                             window: Optional[int] = None):
    """(out (b,s,h,dv), lse (b,h,s)) — both differentiable. ``block_q``
    / ``block_k`` None (both): each kernel takes `choose_tiles`' pair;
    a pinned pair goes to all three. ``v`` heads may have another width
    than q/k heads; ``scale`` None is ``1 / sqrt`` of the q/k width.
    ``window`` (static, causal self-attention only): a query sees its
    own position and the ``window - 1`` before it; None, or a window no
    shorter than the sequence, is the plain causal call."""
    return _flash_with_lse_fwd(q, k, v, causal, block_q, block_k, interpret,
                               scale, window)[0]


def _effective_window(q, k, causal, window) -> Optional[int]:
    """The window the kernels are built for: None where it masks
    nothing the causal mask leaves."""
    if window is None:
        return None
    if not causal or window < 1 or q.shape[1] != k.shape[1]:
        raise ValueError(
            f"window={window}: a window is the last `window` positions of "
            f"causal self-attention (causal={causal}, seq {q.shape[1]} "
            f"against {k.shape[1]})")
    return None if window >= k.shape[1] else int(window)


def _flash_with_lse_fwd(q, k, v, causal, block_q, block_k, interpret,
                        scale=None, window=None):
    window = _effective_window(q, k, causal, window)
    # named scope = the kernel ledger's attribution key
    # (profiler/kernel_ledger.py classifies HLO sites by op_name path)
    with trace.scope("attention_fwd"):
        if interpret or _on_tpu():
            tiles = _tiles_for(q, k, v, block_q, block_k, window)
            if block_q is None:
                _report_tiles(tiles, q.shape[1], window)
            out, lse = _flash_fwd_pallas(q, k, v, causal, *tiles["fwd"],
                                         interpret=interpret, scale=scale,
                                         window=window)
        else:
            out, lse = mha_reference_with_lse(q, k, v, causal=causal,
                                              scale=scale, window=window)
    out, lse = _named(out, lse)
    return (out, lse), (q, k, v, out, lse)


def _flash_with_lse_bwd(causal, block_q, block_k, interpret, scale, window,
                        res, g):
    q, k, v, o, lse = res
    g_out, g_lse = g
    window = _effective_window(q, k, causal, window)
    with trace.scope("attention_bwd"):
        if interpret or _on_tpu():
            tiles = _tiles_for(q, k, v, block_q, block_k, window)
            return _flash_bwd_pallas(
                q, k, v, o, lse, g_out, g_lse, causal,
                tiles["dq"], tiles["dkv"], interpret=interpret, scale=scale,
                window=window,
            )
        _, vjp = jax.vjp(
            lambda q, k, v: mha_reference_with_lse(
                q, k, v, causal=causal, scale=scale, window=window),
            q, k, v,
        )
        return vjp((g_out, g_lse))


flash_attention_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_select_with_lse(q, k, v, select,
                                    block_q: Optional[int] = None,
                                    block_k: Optional[int] = None,
                                    interpret: bool = False,
                                    scale: Optional[float] = None,
                                    select_block: int = 0):
    """Causal self-attention over the keys ``select`` names, int8, in one
    of two forms. ``select_block`` 0: ``(b, s, s)``, nonzero where the
    query sees the key, already under the causal mask, one mask for every
    head of a batch row. ``select_block`` ``n``: ``(b, hkv, s, s / n)``,
    nonzero where the queries of a key-value head's group see a block of
    ``n`` keys, of which a query sees those at or before it. Every row
    sees at least one key. ``(out, lse)`` as `flash_attention_with_lse`,
    differentiable in q, k and v; the selection gets no gradient. On the
    TPU (and under ``interpret``) the ``_sel`` (``_blk``) kernels, off it
    the jnp reference under the same mask."""
    return _flash_select_fwd(q, k, v, select, block_q, block_k, interpret,
                             scale, select_block)[0]


def _check_select(q, k, select, select_block: int = 0):
    b, sq = q.shape[:2]
    want = (b, k.shape[2], sq, sq // select_block) if select_block else (
        b, sq, sq)
    if (sq != k.shape[1] or select.shape != want
            or select.dtype != jnp.int8
            or (select_block and sq % select_block)):
        raise ValueError(
            f"select {select.shape} {select.dtype}: a selection of causal "
            "self-attention is int8, one (batch, seq, seq) mask for every "
            "head of a row or, with select_block, one (batch, kv heads, "
            f"seq, seq / select_block) choice of blocks a key-value head "
            f"(wanted {want}: q {q.shape}, k {k.shape}, select_block "
            f"{select_block})")


def select_by_keys(select, select_block: int):
    """A selection by blocks as the key-level mask a key-value head, ``(b,
    hkv, s, s)`` bool under the causal mask: what the jnp reference (and
    a test) reads. ``s / select_block`` times the bytes."""
    s = select.shape[2]
    pos = jnp.arange(s, dtype=jnp.int32)
    return (jnp.repeat(select != 0, select_block, axis=3)
            & (pos[None, :] <= pos[:, None]))


def _reference_select(q, k, v, select, scale, select_block):
    if select_block:
        select = select_by_keys(select, select_block)
    return mha_reference_with_lse(q, k, v, causal=True, scale=scale,
                                  select=select)


def _flash_select_fwd(q, k, v, select, block_q, block_k, interpret, scale,
                      select_block=0):
    _check_select(q, k, select, select_block)
    with trace.scope("attention_fwd"):
        if interpret or _on_tpu():
            tiles = _tiles_for(q, k, v, block_q, block_k)
            if block_q is None and select_block:
                _report_tiles(tiles)
            elif block_q is None:
                trace.gauge("attn.select_block_q", tiles["fwd"][0])
                trace.gauge("attn.select_block_k", tiles["fwd"][1])
            out, lse = _flash_fwd_pallas(
                q, k, v, True, *tiles["fwd"], interpret=interpret,
                scale=scale, select=select, select_block=select_block)
        else:
            out, lse = _reference_select(q, k, v, select, scale,
                                         select_block)
    out, lse = _named(out, lse)
    return (out, lse), (q, k, v, select, out, lse)


def _flash_select_bwd(block_q, block_k, interpret, scale, select_block,
                      res, g):
    q, k, v, select, o, lse = res
    g_out, g_lse = g
    no_grad = np.zeros(select.shape, jax.dtypes.float0)
    with trace.scope("attention_bwd"):
        if interpret or _on_tpu():
            tiles = _tiles_for(q, k, v, block_q, block_k)
            return _flash_bwd_pallas(
                q, k, v, o, lse, g_out, g_lse, True, tiles["dq"],
                tiles["dkv"], interpret=interpret, scale=scale,
                select=select, select_block=select_block) + (no_grad,)
        _, vjp = jax.vjp(
            lambda q, k, v: _reference_select(q, k, v, select, scale,
                                              select_block),
            q, k, v)
        return vjp((g_out, g_lse)) + (no_grad,)


flash_attention_select_with_lse.defvjp(_flash_select_fwd, _flash_select_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    mesh: Optional[Mesh] = None,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    select=None, return_lse: bool = False,
                    select_block: int = 0):
    """``mesh``: the mesh the caller's jit partitions over. The compiler
    partitions the reference path itself, but not a Mosaic kernel
    ("cannot be automatically partitioned"), so over more than one
    device the kernels run under ``shard_map`` on each device's batch
    rows (data axes) and heads (tp), every sequence whole. Callers
    already inside a manual ``shard_map`` (ring, ulysses, the pp
    stages) pass no mesh. ``select``, ``select_block``: see
    `flash_attention_select_with_lse` (causal, no window; one key-level
    mask for every head of a batch row or, with ``select_block``, a
    choice of blocks a key-value head). ``return_lse``: ``(out, lse (b, h,
    s))`` instead of ``out``."""
    both = (lambda r: r) if return_lse else (lambda r: r[0])
    if select is not None:
        if not causal or window is not None:
            raise ValueError(
                "select= is the whole mask of causal self-attention: no "
                f"window beside it (causal={causal}, window={window})")

        def attn(q, k, v, select):
            return both(flash_attention_select_with_lse(
                q, k, v, select, block_q, block_k, interpret, scale,
                select_block))

        operands = (q, k, v, select)
    else:
        def attn(q, k, v):
            return both(flash_attention_with_lse(
                q, k, v, causal, block_q, block_k, interpret, scale, window
            ))

        operands = (q, k, v)
    if mesh is None or mesh.size == 1 or not (interpret or _on_tpu()):
        return attn(*operands)
    spec = P(BATCH_AXES, None, TP, None)
    specs = (spec,) * 3 + (
        P(BATCH_AXES, TP, None, None) if select_block
        else P(BATCH_AXES, None, None),) * (select is not None)
    return shard_map(
        attn, mesh=mesh, in_specs=specs,
        out_specs=(spec, P(BATCH_AXES, TP, None)) if return_lse else spec,
        check_vma=False,
    )(*operands)
