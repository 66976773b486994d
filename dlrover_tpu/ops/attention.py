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
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

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
):
    """Stable-softmax attention in float32, GQA-aware; returns
    ``(out (b,sq,h,d), lse (b,h,sq))``. ``q_offset`` / ``k_offset`` are
    *global* positions of element 0 — this is what lets ring-attention
    chunks mask causally against each other."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    scale = 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        qpos = q_offset + jnp.arange(sq)
        kpos = k_offset + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)  # (b, h, sq)
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def mha_reference(q, k, v, causal: bool = True, q_offset=0, k_offset=0):
    return mha_reference_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, k_offset=k_offset
    )[0]


# ---------------------------------------------------------------------------
# Pallas TPU forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, n_kblocks: int, causal: bool, scale: float
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Entire k block above the causal diagonal → skip all compute.
    if causal:
        block_needed = ki * block_k <= qi * block_q + block_q - 1
    else:
        block_needed = qi >= 0  # always true, traced

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (bq, bk)
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_ref[:, 0]                                  # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(m_prev - m_cur)
        l_ref[:, 0] = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, 0] = m_cur

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / lsafe[:, None]).astype(o_ref.dtype)
        # lse carries a broadcast minor lane dim for TPU block tiling
        # (see _LSE_LANES)
        lse = m_ref[:, 0] + jnp.log(lsafe)
        lse_ref[0, 0] = jnp.broadcast_to(lse[:, None], lse_ref[0, 0].shape)


def _flash_fwd_pallas(q, k, v, causal: bool, block_q: int, block_k: int,
                      interpret: bool = False):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    n_q, n_k = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(d)

    # (b, s, h, d) → (b, h, s, d) so the contiguous minor dims tile cleanly.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, n_q, n_k)
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q, block_k=block_k, n_kblocks=n_k,
        causal=causal, scale=scale,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda bi, hi, qi, ki, _g=group: (bi, hi // _g, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda bi, hi, qi, ki, _g=group: (bi, hi // _g, ki, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, _LSE_LANES),
                lambda bi, hi, qi, ki: (bi, hi, qi, 0),
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, _LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# Pallas TPU backward kernels
# ---------------------------------------------------------------------------
#
# Standard flash backward, blockwise recompute from (q, k, v, lse):
#   p  = exp(s - lse)            s = scale * q @ k^T  (+ causal mask)
#   dp = do @ v^T
#   ds = p * (dp - delta) * scale     delta = rowsum(do * o) - g_lse
#   dq = ds @ k ; dk = ds^T @ q ; dv = p^T @ do
# dq iterates k blocks per q block; dk/dv iterates q blocks per k block
# (per *query* head — the group sum down to kv heads happens outside,
# keeping the kernels free of cross-block output contention).


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, block_q: int, block_k: int, n_kblocks: int, causal: bool, scale: float
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if causal:
        block_needed = ki * block_k <= qi * block_q + block_q - 1
    else:
        block_needed = qi >= 0

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]                             # (bq,)
        delta = delta_ref[0, 0, :, 0]                         # (bq,)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                         # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_kblocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, block_k: int, n_qblocks: int, causal: bool, scale: float
):
    # kv-head-major: grid dim 1 is the KV head; dim 3 sweeps
    # (query_head_in_group, q_block) pairs so the group's contributions
    # accumulate in VMEM and dk/dv are written once per kv head — no
    # (b, h, sk, d) per-query-head buffers in HBM (round-2 Weak #7).
    ki = pl.program_id(2)
    j = pl.program_id(3)
    qi = j % n_qblocks

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        block_needed = qi * block_q + block_q - 1 >= ki * block_k
    else:
        block_needed = ki >= 0

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (bq, bk)
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        # dv += p^T @ do
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        # dk += ds^T @ q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, g_lse, causal,
                      block_q, block_k, interpret=False):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    n_q, n_k = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(d)

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
    # broadcast minor lane dim for TPU block tiling (see fwd kernel)
    lse4 = jnp.broadcast_to(lse[..., None], (b, h, sq, _LSE_LANES))
    delta4 = jnp.broadcast_to(delta[..., None], (b, h, sq, _LSE_LANES))

    # -- dq: grid (b, h, n_q, n_k), q block fixed per-(i), k rotates (j) --
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            n_kblocks=n_k, causal=causal, scale=scale,
        ),
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda bi, hi, i, j, _g=group: (bi, hi // _g, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda bi, hi, i, j, _g=group: (bi, hi // _g, j, 0),
            ),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec(
                (1, 1, block_q, _LSE_LANES),
                lambda bi, hi, i, j: (bi, hi, i, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_q, _LSE_LANES),
                lambda bi, hi, i, j: (bi, hi, i, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse4, delta4)

    # -- dk/dv: kv-head-major grid (b, hkv, n_k, group*n_q): the group's
    # query heads accumulate into one VMEM scratch per kv head, so HBM
    # holds (b, hkv, sk, d) outputs — group x less traffic than the
    # per-query-head form (round-2 Weak #7), which matters at 8:1 GQA.
    def _q_head(bi, hi, i, j, _g=group, _nq=n_q):
        return (bi, hi * _g + j // _nq, j % _nq, 0)

    dkh, dvh = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            n_qblocks=n_q, causal=causal, scale=scale,
        ),
        grid=(b, hkv, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), _q_head),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, d), _q_head),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES), _q_head),
            pl.BlockSpec((1, 1, block_q, _LSE_LANES), _q_head),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, lse4, delta4)

    dq = dq.transpose(0, 2, 1, 3)
    dk = dkh.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dvh.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# custom_vjp surfaces
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False):
    """(out (b,s,h,d), lse (b,h,s)) — both differentiable."""
    return _flash_with_lse_fwd(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_with_lse_fwd(q, k, v, causal, block_q, block_k, interpret):
    # named scope = the kernel ledger's attribution key
    # (profiler/kernel_ledger.py classifies HLO sites by op_name path)
    with jax.named_scope("attention_fwd"):
        if interpret or _on_tpu():
            out, lse = _flash_fwd_pallas(q, k, v, causal, block_q,
                                         block_k, interpret=interpret)
        else:
            out, lse = mha_reference_with_lse(q, k, v, causal=causal)
    return (out, lse), (q, k, v, out, lse)


def _flash_with_lse_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    g_out, g_lse = g
    with jax.named_scope("attention_bwd"):
        if interpret or _on_tpu():
            return _flash_bwd_pallas(
                q, k, v, o, lse, g_out, g_lse, causal, block_q, block_k,
                interpret=interpret,
            )
        _, vjp = jax.vjp(
            lambda q, k, v: mha_reference_with_lse(q, k, v,
                                                   causal=causal),
            q, k, v,
        )
        return vjp((g_out, g_lse))


flash_attention_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    mesh: Optional[Mesh] = None):
    """``mesh``: the mesh the caller's jit partitions over. The compiler
    partitions the reference path itself, but not a Mosaic kernel
    ("cannot be automatically partitioned"), so over more than one
    device the kernels run under ``shard_map`` on each device's batch
    rows (data axes) and heads (tp), every sequence whole. Callers
    already inside a manual ``shard_map`` (ring, ulysses, the pp
    stages) pass no mesh."""
    def attn(q, k, v):
        return flash_attention_with_lse(
            q, k, v, causal, block_q, block_k, interpret
        )[0]

    if mesh is None or mesh.size == 1 or not (interpret or _on_tpu()):
        return attn(q, k, v)
    spec = P(BATCH_AXES, None, TP, None)
    return shard_map(
        attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)

