"""Fused-CE Pallas TPU kernel: unembed matmul + softmax-CE per vocab tile
in VMEM — the kernel rung above ops/chunked_ce.py.

The chunked-CE scan (PR 1) already keeps the [B, T, V] logits out of HBM,
but each scan step still materializes a [tokens, chunk] f32 logits buffer
in HBM between the matmul and the online-softmax update. This kernel
closes that last round-trip: a vocab tile's logits live only in VMEM
registers between the MXU matmul and the streaming-lse update, exactly as
flash attention (ops/attention.py) keeps the s×s matrix out of HBM.

One "unit" below is a product over the whole vocabulary, 2·T·d·V FLOPs.
The mathematics needs three (logits, dX, dW); a differentiated loss here
executes four, an undifferentiated one one:

- **forward** (grid token-blocks × vocab-tiles): per-tile logits
  ``x_blk @ w_tile`` with f32 MXU accumulation, online-softmax carry
  ``(m, s)`` in VMEM scratch, target-logit gather via an iota==target
  one-hot reduction (the target's column lands in exactly one tile); the
  last tile finalizes per-token ``logz`` and ``gold``. O(tokens)
  outputs, 1 unit: what a loss that nobody differentiates runs.
- **forward under differentiation** (``jax.custom_vjp``'s rule; nothing
  selects it but ``jax.grad``): the same sweep also carries
  ``acc = acc · exp(m_prev − m_cur) + exp(logits − m_cur) @ w_tile^T``
  in its ``(block_t, d)`` f32 output block, the online rescaling flash
  attention uses, and its last tile divides by ``s``, which leaves
  ``softmax(logits) @ w^T``: dX's vocabulary-wide term, from the logits
  the sweep already had in VMEM. It leaves the kernel in f32: where the
  model is sure of its target the term all but equals the target's
  column, and what the subtraction leaves would be the rounding of a
  narrower residual. 2 units.
- **backward**: dX is that residual less the targets' columns of the
  head (a lookup of T columns, not a product), times the cotangent, in
  f32 and rounded once. dW cannot ride the forward sweep the same way:
  its sum runs over tokens, and a token's normaliser is known only when
  that token's sweep ends, so no vocabulary tile of dW can be finished
  before every token block has been swept. It stays a kernel of its
  own, vocab-major (token blocks accumulate in VMEM, each vocab tile
  written exactly once), re-forming tile logits from the saved
  ``(x, w, logz)``. 2 units.

Dispatch contract (``cross_entropy_sums``): on the TPU backend the
Pallas kernel is the path (and under ``interpret=True`` for CPU numerics
tests) — a kernel the chip's compiler refuses fails the step, it is not
replaced. Off TPU — and when the ``DLROVER_TPU_FUSED_CE=0`` kill-switch
is set — the scan-based ``chunked_cross_entropy`` runs, so CPU tests,
contract lowering and bisection all keep the PR 1 program. Same
``(nll_sum, n_valid)`` two-number return, same ``targets < 0`` pad
sentinel, same compute-dtype-operands / f32-accumulation contract.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common import flags
from dlrover_tpu.observability import trace
from dlrover_tpu.ops.chunked_ce import (
    DEFAULT_CHUNK_SIZE,
    chunked_cross_entropy,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, SP

_NEG_INF = -1e30

#: Broadcast minor lane dim for per-token (1-D) kernel operands/results —
#: same convention as ops/attention.py's lse (block shapes need a minor
#: dim divisible by 128 or equal to the array dim).
_LANES = 8

#: Largest tiles a kernel is given. Every kernel blocks the whole
#: feature dim — ``(block_t, d)`` and ``(d, block_v)`` operand blocks,
#: and where it accumulates a gradient an output block of one of those
#: shapes (dw's with an f32 accumulator beside it) — so its VMEM
#: footprint grows with ``d``: ``_tile_geometry`` halves these until
#: ``_vmem_bytes`` fits the budget.
DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_V = 512

#: Scoped VMEM every kernel asks the compiler for. The default grant is
#: 16 MiB, which the backward's blocks overflow from d=2048 up at the
#: default tiles ("Scoped allocation with size 22.52M and limit 16.00M"
#: at d=4096); a v5e core has 128 MiB of VMEM, v5p and v6e no less than
#: this.
_VMEM_LIMIT = 64 * 2**20
#: What the blocks may take of it; the rest is left to the compiler's
#: own temporaries (the live f32 logits tile and its copies).
_VMEM_BUDGET = 48 * 2**20


def fused_ce_enabled() -> bool:
    """Env kill-switch (bisection aid): ``DLROVER_TPU_FUSED_CE=0``
    restores the scan-based chunked-CE program even on TPU. Read at
    trace time — set it before the first loss call / trainer step of the
    process (the jitted step caches the trace)."""
    return flags.FUSED_CE.get()


def fused_ce_available(interpret: bool = False) -> bool:
    """True where the Pallas kernel is the path: the TPU backend, or
    interpreter mode. The dispatcher below keys off this."""
    return interpret or _on_tpu()


def cross_entropy_sums(
    x: jnp.ndarray,
    w_unembed: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
    mesh: Optional[Mesh] = None,
):
    """The models' CE entry: the fused Pallas kernel on TPU (or under
    ``interpret``) unless the kill-switch is set, else the scan-based
    chunked path (same math, same ``(nll_sum, n_valid)`` contract).
    ``chunk_size`` parameterizes the chunked path only;
    ``block_t``/``block_v`` the kernel only.

    ``mesh``: the mesh the caller's jit partitions over. The compiler
    partitions the chunked path itself, but not a Mosaic kernel ("cannot
    be automatically partitioned"), so over more than one device the
    kernel runs on each device's tokens under ``shard_map`` — ``x`` and
    ``targets`` split over the data axes (and the sequence over sp),
    the unembed matrix gathered whole, the two sums reduced over those
    axes. Callers already inside a manual ``shard_map`` pass no mesh."""
    if not (fused_ce_enabled() and fused_ce_available(interpret)):
        return chunked_cross_entropy(x, w_unembed, targets,
                                     chunk_size=chunk_size)
    kernel = functools.partial(
        fused_cross_entropy,
        block_t=block_t, block_v=block_v, interpret=interpret,
    )
    if mesh is None or mesh.size == 1:
        return kernel(x, w_unembed, targets)
    # (b, s) token grids split over (data axes, sp); (b,) over the data
    # axes alone (vit's pooled head)
    token_axes = (BATCH_AXES, SP)[: targets.ndim]
    reduce_axes = BATCH_AXES + (SP,) * (targets.ndim > 1)

    def per_shard(x, w, tgt):
        nll_sum, n_valid = kernel(x, w, tgt)
        return (lax.psum(nll_sum, reduce_axes),
                lax.psum(n_valid, reduce_axes))

    return shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(*token_axes, None), P(None, None), P(*token_axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )(x, w_unembed, targets)


def fused_cross_entropy(
    x: jnp.ndarray,
    w_unembed: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    block_t: int = DEFAULT_BLOCK_T,
    block_v: int = DEFAULT_BLOCK_V,
    interpret: bool = False,
):
    """Fused ``softmax_ce(x @ w_unembed, targets)`` as a Pallas kernel.

    Args/returns match :func:`~dlrover_tpu.ops.chunked_ce.
    chunked_cross_entropy`: ``x (..., d)``, ``w_unembed (d, v)``,
    ``targets (...)`` with ``targets < 0`` ignored; returns f32
    ``(nll_sum, n_valid)``. Raises off TPU without ``interpret`` —
    callers wanting the platform dispatch use :func:`cross_entropy_sums`.
    """
    if x.shape[:-1] != targets.shape:
        raise ValueError(
            f"x leading dims {x.shape[:-1]} != targets shape {targets.shape}"
        )
    if x.shape[-1] != w_unembed.shape[0]:
        raise ValueError(
            f"x feature dim {x.shape[-1]} != w_unembed rows "
            f"{w_unembed.shape[0]}"
        )
    if not fused_ce_available(interpret):
        raise RuntimeError(
            "fused_cross_entropy needs the TPU backend (or interpret=True); "
            "use cross_entropy_sums for the platform dispatch"
        )
    return _fused_ce(int(block_t), int(block_v), bool(interpret),
                     x, w_unembed, targets)


# ---------------------------------------------------------------------------
# tiling / padding helpers
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


#: The three kernels, as ``_vmem_bytes`` and ``_tile_geometry`` name them.
LOSS, LOSS_DX, DW = "loss", "loss_dx", "dw"


def _vmem_bytes(bt: int, bv: int, d: int, xb: int, wb: int,
                kernel: str) -> int:
    """VMEM one kernel's blocks occupy: the pipelined (double-buffered)
    ``(bt, d)`` x and ``(d, bv)`` w blocks, w's compute-dtype copy when
    the dtypes differ, and the gradient block the kernel accumulates
    (double-buffered): ``(bt, d)`` f32 in the training forward
    (``LOSS_DX``), which accumulates in the block itself; ``(d, bv)``
    with an f32 accumulator in ``DW``; none in ``LOSS``."""
    total = 2 * bt * d * xb + 2 * d * bv * wb
    if wb != xb:
        total += d * bv * xb
    if kernel == LOSS_DX:
        total += bt * d * 2 * 4
    elif kernel == DW:
        total += d * bv * (2 * wb + 4)
    return total


def _tile_geometry(n: int, v: int, d: int, x_dtype, w_dtype,
                   block_t: int, block_v: int, kernel: str):
    """Clip the requested tiles to the (8, 128)-aligned problem size,
    halve them until the kernel's blocks fit ``_VMEM_BUDGET`` at this
    ``d``, and return ``(bt, bv, n_pad, v_pad)`` with the padded array
    dims exact tile multiples — every BlockSpec start is then in
    range. Two kernels of one loss may end with different tiles:
    ``logz`` is per token, so nothing depends on that."""
    bt = max(8, min(block_t, _round_up(n, 8)))
    bv = max(128, min(block_v, _round_up(v, 128)))
    xb, wb = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    while _vmem_bytes(bt, bv, d, xb, wb, kernel) > _VMEM_BUDGET:
        # shrink the side that holds more VMEM; the vocab tile stays a
        # multiple of the 128-lane width, the token tile of 8 sublanes
        if bv > 128 and (bv * wb >= bt * xb or bt <= 8):
            bv = _round_up(bv // 2, 128)
        elif bt > 8:
            bt = _round_up(bt // 2, 8)
        else:
            raise ValueError(
                f"fused CE blocks the whole feature dim and cannot fit "
                f"d={d} into {_VMEM_BUDGET} bytes of VMEM even at "
                f"(8, 128) tiles; {flags.FUSED_CE.name}=0 selects the "
                f"chunked path"
            )
    # a vocabulary the tile does not divide is padded into a copy of the
    # whole head every step (206 MB at d=2048, V=50304): where a smaller
    # multiple of the 128 lanes divides it, take the largest such
    # (50304 = 131 x 384); V=32768 stays at 512
    bv = next((t for t in range(bv, 127, -128) if v % t == 0), bv)
    return bt, bv, _round_up(n, bt), _round_up(v, bv)


_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _pad_operands(x2, w, tgt1, n_pad: int, v_pad: int):
    """Zero-pad tokens and vocab up to tile multiples. Padded token rows
    carry the -1 target sentinel (excluded from n_valid AND given a zero
    backward row_scale); padded vocab columns are masked to -inf inside
    the kernels (exp -> 0), so neither contributes anywhere."""
    n, d = x2.shape
    v = w.shape[1]
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
        tgt1 = jnp.pad(tgt1, (0, n_pad - n), constant_values=-1)
    if v_pad != v:
        w = jnp.pad(w, ((0, 0), (0, v_pad - v)))
    return x2, w, tgt1


def _lanes(a):
    """(n,) -> (n, _LANES) broadcast copy (TPU minor-dim tiling)."""
    return jnp.broadcast_to(a[:, None], (a.shape[0], _LANES))


def _tile_logits(x_ref, w_ref, vi, bt: int, bv: int, v: int):
    """One tile's logits ``(bt, bv)`` f32: compute-dtype operands on the
    MXU with f32 accumulation + padded-column -inf masking (same
    contract as chunked_ce._chunk_logits)."""
    logits = lax.dot_general(
        x_ref[...], w_ref[...].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    col = vi * bv + lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    return jnp.where(col < v, logits, _NEG_INF), col


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fused_ce_fwd_kernel(
    x_ref, w_ref, tgt_ref, logz_ref, gold_ref, *rest,
    block_t: int, block_v: int, n_vblocks: int, v: int, with_dx: bool
):
    """One body, two forms: ``with_dx`` adds the f32 ``softmax @ w^T``
    output to the refs (outputs before scratch). Its block stays in VMEM
    through a token block's sweep, so the sum accumulates in it."""
    if with_dx:
        dx_ref, m_ref, s_ref, g_ref = rest
    else:
        m_ref, s_ref, g_ref = rest
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        s_ref[:] = jnp.zeros_like(s_ref)
        g_ref[:] = jnp.zeros_like(g_ref)
        if with_dx:
            dx_ref[...] = jnp.zeros_like(dx_ref)

    logits, col = _tile_logits(x_ref, w_ref, vi, block_t, block_v, v)
    # online softmax: rescale the running sumexp to the new max. Fully
    # padded tiles contribute exp(-inf)=0; at least one tile holds real
    # columns, so the final s is positive for every row.
    m_prev = m_ref[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(logits - m_cur[:, None])
    s_ref[:, 0] = s_ref[:, 0] * alpha + jnp.sum(p, axis=1)
    m_ref[:, 0] = m_cur
    # the target column lands in exactly one tile: one-hot reduction
    # instead of a gather (pad sentinel -1 matches no column)
    tgt = tgt_ref[:, 0]
    g_ref[:, 0] = g_ref[:, 0] + jnp.sum(
        jnp.where(col == tgt[:, None], logits, 0.0), axis=1
    )
    if with_dx:
        # sum_v exp(l_v - m) w_v under the same carry: what it held is
        # rescaled to the new max, as s is; p goes to the MXU in the
        # operands' dtype, as chunked_ce._ce_bwd's q does
        dx_ref[...] = dx_ref[...] * alpha[:, None] + lax.dot_general(
            p.astype(x_ref.dtype), w_ref[...].astype(x_ref.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(vi == n_vblocks - 1)
    def _finalize():
        s = s_ref[:, 0]
        s = jnp.where(s == 0.0, 1.0, s)
        logz = m_ref[:, 0] + jnp.log(s)
        logz_ref[...] = jnp.broadcast_to(logz[:, None], logz_ref.shape)
        gold_ref[...] = jnp.broadcast_to(
            g_ref[:, 0][:, None], gold_ref.shape
        )
        if with_dx:
            dx_ref[...] = dx_ref[...] / s[:, None]


def _fused_ce_fwd_pallas(x2, w, tgt1, v, bt, bv, interpret, with_dx):
    """Padded-operand forward: returns (logz (n_pad,), gold (n_pad,))
    and, ``with_dx``, ``softmax(logits) @ w^T`` (n_pad, d) in f32.
    ``v`` is the REAL vocab width — padded columns beyond it are masked
    to -inf inside the kernel."""
    n_pad, d = x2.shape
    v_pad = w.shape[1]
    n_t, n_v = n_pad // bt, v_pad // bv
    kernel = functools.partial(
        _fused_ce_fwd_kernel,
        block_t=bt, block_v=bv, n_vblocks=n_v, v=v, with_dx=with_dx,
    )
    lane_spec = pl.BlockSpec((bt, _LANES), lambda ti, vi: (ti, 0))
    lane_shape = jax.ShapeDtypeStruct((n_pad, _LANES), jnp.float32)
    carry = pltpu.VMEM((bt, 128), jnp.float32)
    x_spec = pl.BlockSpec((bt, d), lambda ti, vi: (ti, 0))
    out_specs, out_shape = [lane_spec, lane_spec], [lane_shape, lane_shape]
    if with_dx:
        out_specs.append(x_spec)
        out_shape.append(jax.ShapeDtypeStruct((n_pad, d), jnp.float32))
    logz, gold, *dx = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            x_spec,
            pl.BlockSpec((d, bv), lambda ti, vi: (0, vi)),
            lane_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[carry, carry, carry],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_fwd",
    )(x2, w, _lanes(tgt1))
    return (logz[:, 0], gold[:, 0], *dx)


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------
#
# d(nll_sum)/d(logits_tile) = (softmax_tile - onehot_tile) * row_scale,
# recomputed tile by tile from the O(tokens) logz residual:
#   p = exp(logits - logz) ; q = (p - onehot) * row_scale
#   dw = x^T @ q   (vocab-major: token blocks accumulate per vocab tile,
#                   each dw tile written exactly once — disjoint, like
#                   the chunked path's dynamic_update_slice chunks)
# dx = q @ w^T needs no kernel here: its softmax term left the forward
# sweep and its one-hot term is a lookup (_fused_ce_bwd).


def _fused_ce_dw_kernel(
    x_ref, w_ref, tgt_ref, logz_ref, scale_ref, dw_ref, acc_ref,
    *, block_t: int, block_v: int, n_tblocks: int, v: int
):
    # vocab-major grid: program_id(0) is the vocab tile, (1) sweeps token
    # blocks so the tile's dw accumulates in VMEM and is written once
    vi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    logits, col = _tile_logits(x_ref, w_ref, vi, block_t, block_v, v)
    p = jnp.exp(logits - logz_ref[:, 0][:, None])  # padded cols: exp(-inf)=0
    onehot = (col == tgt_ref[:, 0][:, None]).astype(jnp.float32)
    # cast for the MXU, as chunked_ce._ce_bwd does
    q = ((p - onehot) * scale_ref[:, 0][:, None]).astype(x_ref.dtype)
    acc_ref[:] = acc_ref[:] + lax.dot_general(
        x_ref[...], q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ti == n_tblocks - 1)
    def _finalize():
        dw_ref[...] = acc_ref[:].astype(dw_ref.dtype)


def _fused_ce_dw_pallas(x2, w, tgt1, logz, row_scale, v, bt, bv,
                        interpret):
    """Padded-operand backward: returns dw (d, v_pad). ``v`` is the REAL
    vocab width (padded-column mask, as in fwd)."""
    n_pad, d = x2.shape
    v_pad = w.shape[1]
    n_t, n_v = n_pad // bt, v_pad // bv
    lane_spec = pl.BlockSpec((bt, _LANES), lambda vi, ti: (ti, 0))
    return pl.pallas_call(
        functools.partial(
            _fused_ce_dw_kernel,
            block_t=bt, block_v=bv, n_tblocks=n_t, v=v,
        ),
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((bt, d), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((d, bv), lambda vi, ti: (0, vi)),
            lane_spec, lane_spec, lane_spec,
        ],
        out_specs=pl.BlockSpec((d, bv), lambda vi, ti: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((d, v_pad), w.dtype),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(x2, w, _lanes(tgt1), _lanes(logz), _lanes(row_scale))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


#: losses traced since the last step build: [lean forwards, backwards]
_traced = [0, 0]


def reset_sweep_report():
    """A step build starts: `ElasticTrainer.lower_step` calls this
    before it traces, so ``fused_ce.logit_sweeps`` says what that build's
    losses run, whatever is traced after it."""
    _traced[:] = [0, 0]
    trace.gauge("fused_ce.logit_sweeps", 0)


def _report_sweeps(differentiated: bool):
    """The gauge: products over the whole vocabulary that form logits in
    the losses traced since the last step build. A differentiated loss
    counts 2 (the forward sweep and the dw kernel), so a training step
    reads 2 and one with a second head through the same kernel 4; where
    nothing is differentiated, each lean forward counts 1. An evaluation
    or a reference check traced after the step changes nothing."""
    _traced[differentiated] += 1
    lean, backward = _traced
    trace.gauge("fused_ce.logit_sweeps", 2 * backward if backward else lean)


# ---------------------------------------------------------------------------
# custom_vjp surface
# ---------------------------------------------------------------------------


def _flatten(x, tgt):
    d = x.shape[-1]
    n = int(np.prod(tgt.shape)) if tgt.shape else 1
    return x.reshape(n, d), tgt.reshape(n)


def _padded(kernel, block_t, block_v, x, w, tgt):
    """The kernel's tiles for these operands and the operands padded to
    them: ``(bt, bv, n, x2p, wp, tgt1p)``, ``n`` the real token count."""
    x2, tgt1 = _flatten(x, tgt)
    bt, bv, n_pad, v_pad = _tile_geometry(
        x2.shape[0], w.shape[1], x2.shape[1], x.dtype, w.dtype,
        block_t, block_v, kernel,
    )
    return (bt, bv, x2.shape[0],
            *_pad_operands(x2, w, tgt1, n_pad, v_pad))


def _fused_ce_run_fwd(block_t, block_v, interpret, x, w, tgt, with_dx):
    """Shared fwd: returns (nll_sum, n_valid, logz (n,) f32 residual)
    and, ``with_dx``, the f32 ``softmax(logits) @ w^T`` (n, d) residual."""
    _report_sweeps(False)
    # named scope = the kernel ledger's attribution key
    # (profiler/kernel_ledger.py classifies HLO sites by op_name path)
    with trace.scope("fused_ce_fwd"):
        bt, bv, n, x2p, wp, tgt1p = _padded(
            LOSS_DX if with_dx else LOSS, block_t, block_v, x, w, tgt
        )
        logz, gold, *dx_soft = _fused_ce_fwd_pallas(
            x2p, wp, tgt1p, w.shape[1], bt, bv, interpret, with_dx
        )
        logz, gold = logz[:n], gold[:n]
        vf = (tgt1p[:n] >= 0).astype(jnp.float32)
        nll_sum = jnp.sum((logz - gold) * vf)
        n_valid = jnp.sum(vf)
    return (nll_sum, n_valid, logz, *(a[:n] for a in dx_soft))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_ce(block_t: int, block_v: int, interpret: bool, x, w, tgt):
    # the primal: a loss nobody differentiates (evaluation, a reference
    # check) runs the lean sweep, one unit and O(tokens) outputs
    nll_sum, n_valid, _ = _fused_ce_run_fwd(
        block_t, block_v, interpret, x, w, tgt, with_dx=False
    )
    return nll_sum, n_valid


def _fused_ce_fwd(block_t, block_v, interpret, x, w, tgt):
    nll_sum, n_valid, logz, dx_soft = _fused_ce_run_fwd(
        block_t, block_v, interpret, x, w, tgt, with_dx=True
    )
    return (nll_sum, n_valid), (x, w, tgt, logz, dx_soft)


def _fused_ce_bwd(block_t, block_v, interpret, res, cot):
    """n_valid carries no float dependence on (x, w); its cotangent is
    dropped — same contract as the chunked path."""
    x, w, tgt, logz, dx_soft = res
    g_nll, _g_nv = cot
    _report_sweeps(True)    # the forward's sweep, and dw's
    with trace.scope("fused_ce_bwd"):
        bt, bv, n, x2p, wp, tgt1p = _padded(
            DW, block_t, block_v, x, w, tgt
        )
        vf = (tgt1p >= 0).astype(jnp.float32)
        row_scale = vf * g_nll.astype(jnp.float32)
        logz_p = jnp.pad(logz, (0, tgt1p.shape[0] - n))
        dw = _fused_ce_dw_pallas(
            x2p, wp, tgt1p, logz_p, row_scale, w.shape[1], bt, bv,
            interpret,
        )[:, :w.shape[1]]
        # dx = (softmax @ w^T - w[:, tgt]^T) * row_scale: the one-hot
        # term is the targets' columns of the head, exact (a masked
        # target's is scaled by zero); one f32 pass, rounded once
        w_tgt = jnp.take(w, tgt1p[:n], axis=1, mode="clip")
        dx = (dx_soft - w_tgt.T.astype(jnp.float32)) * row_scale[:n, None]
        dx = dx.astype(x.dtype).reshape(x.shape)
    dtgt = np.zeros(tgt.shape, jax.dtypes.float0)
    return dx, dw, dtgt


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)
