"""Chunked fused cross-entropy: unembed matmul + softmax-CE without the
[B, T, V] logits materialization.

A dense loss computes full f32 logits ``x @ w_unembed`` of shape
``[B, T, V]`` before logsumexp — at 8192 tokens and V=32768 that is 1 GiB
of f32 activations (plus the bwd residuals) on a 16 GB chip. This op
fuses the lm-head matmul into the loss and iterates VOCAB chunks under
``lax.scan``:

- per-chunk logits ``[tokens, chunk]`` in compute-dtype operands with f32
  MXU accumulation (``preferred_element_type``);
- a running streaming logsumexp carry ``(max, sumexp)`` — the standard
  online-softmax recurrence, so no chunk's result depends on seeing the
  whole row;
- a target-logit gather per chunk (the target's column lands in exactly
  one chunk).

Peak activation memory drops from ``O(B*T*V)`` to ``O(B*T*chunk)`` in both
fwd and bwd: the custom VJP recomputes each chunk's logits in the backward
(one extra unembed-matmul pass, the same trade rematerialization makes for
the decoder layers — and like remat, the recompute is NOT credited in
``mfu``'s model-FLOPs accounting) and writes the ``dW`` chunks disjointly,
so no ``[tokens, V]`` intermediate ever exists in either direction.
Megatron-LM's fused vocab-parallel CE is the reference design.

Leading dims are never reshaped away — the op broadcasts over them — so
batch/sequence shardings (dp/fsdp/sp) pass straight through under SPMD
and the op composes inside shard_map manual regions (the pp head path).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.observability import trace

#: Default vocab-chunk width: 16 MXU lanes of 128 — wide enough that the
#: per-chunk [tokens, chunk] matmul stays MXU-bound, narrow enough that
#: the largest live loss activation is tokens*2048*4 bytes, not tokens*V*4.
DEFAULT_CHUNK_SIZE = 2048


def chunked_cross_entropy(
    x: jnp.ndarray,
    w_unembed: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
):
    """Fused ``softmax_ce(x @ w_unembed, targets)`` in vocab chunks.

    Args:
      x: ``(..., d)`` hidden states (post final-norm, pre-unembed).
      w_unembed: ``(d, v)`` unembedding / lm-head / classifier weights.
      targets: ``(...)`` int class ids; ``targets < 0`` are ignored
        (the repo-wide pad sentinel).
      chunk_size: vocab columns per scan step (clipped to ``v``); peak
        loss activation is ``prod(targets.shape) * chunk_size`` f32.

    Returns:
      ``(nll_sum, n_valid)`` — the f32 sum of per-token negative
      log-likelihoods over valid targets and the f32 count of valid
      targets (the caller divides; the two-number form is what psum-based
      sharded losses need).
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
    v = w_unembed.shape[1]
    chunk = max(1, min(int(chunk_size), v))
    return _chunked_ce(chunk, x, w_unembed, targets)


# ---------------------------------------------------------------------------
# implementation
# ---------------------------------------------------------------------------


def _chunk_starts(v: int, chunk: int):
    n_chunks = -(-v // chunk)
    return n_chunks, jnp.arange(n_chunks, dtype=jnp.int32) * chunk


def _pad_vocab(w, n_chunks: int, chunk: int):
    """Zero-pad the vocab axis up to a chunk multiple so every
    dynamic_slice start is in range (a clamped start would silently
    overlap the previous chunk and double-count its columns)."""
    v_pad = n_chunks * chunk
    if v_pad != w.shape[1]:
        w = jnp.pad(w, ((0, 0), (0, v_pad - w.shape[1])))
    return w


def _chunk_logits(x, w_p, start, chunk: int, v: int):
    """One chunk's logits ``(..., chunk)``: compute-dtype operands, f32
    accumulation (same contract as the dense unembed); padded tail
    columns forced to -inf so they vanish from the lse (exp -> 0)."""
    w_c = lax.dynamic_slice_in_dim(w_p, start, chunk, axis=1)
    logits = lax.dot_general(
        x, w_c.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    col = start + jnp.arange(chunk, dtype=jnp.int32)
    return jnp.where(col < v, logits, -jnp.inf), w_c


def _ce_forward(chunk: int, x, w, tgt):
    """Streaming-lse forward; returns (nll_sum, n_valid, logz) with logz
    ``(...)`` kept as the bwd residual (O(tokens), not O(tokens*v))."""
    v = w.shape[1]
    n_chunks, starts = _chunk_starts(v, chunk)
    w_p = _pad_vocab(w, n_chunks, chunk)
    valid = tgt >= 0
    vf = valid.astype(jnp.float32)
    tgt_c = jnp.where(valid, tgt, 0)
    lead = tgt.shape
    f32 = jnp.float32

    def body(carry, start):
        m, s, gold = carry
        logits, _ = _chunk_logits(x, w_p, start, chunk, v)
        # online softmax: rescale the running sumexp to the new max.
        # every chunk holds >= 1 real column (n_chunks = ceil(v/chunk)),
        # so m_new is finite from the first step on and the -inf initial
        # max contributes exp(-inf) = 0, never a nan.
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        # the target column lands in exactly one chunk: gather it there
        local = tgt_c - start
        in_chunk = (local >= 0) & (local < chunk)
        g = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[..., None], axis=-1
        )[..., 0]
        gold = jnp.where(in_chunk, g, gold)
        return (m_new, s, gold), None

    init = (
        jnp.full(lead, -jnp.inf, f32),
        jnp.zeros(lead, f32),
        jnp.zeros(lead, f32),
    )
    (m, s, gold), _ = lax.scan(body, init, starts)
    logz = m + jnp.log(s)
    nll_sum = jnp.sum((logz - gold) * vf)
    n_valid = jnp.sum(vf)
    return nll_sum, n_valid, logz


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunked_ce(chunk: int, x, w, tgt):
    # named scope = the kernel ledger's attribution key
    # (profiler/kernel_ledger.py classifies HLO sites by op_name path)
    with trace.scope("chunked_ce_fwd"):
        nll_sum, n_valid, _ = _ce_forward(chunk, x, w, tgt)
    return nll_sum, n_valid


def _chunked_ce_fwd(chunk: int, x, w, tgt):
    with trace.scope("chunked_ce_fwd"):
        nll_sum, n_valid, logz = _ce_forward(chunk, x, w, tgt)
    return (nll_sum, n_valid), (x, w, tgt, logz)


def _chunked_ce_bwd(chunk: int, res, cot):
    """d(nll_sum)/d(logits_c) = (softmax_c - onehot_c) * valid, chunk by
    chunk: recompute the chunk's logits from the saved (x, logz), push
    one chunk of dx and one DISJOINT chunk of dw — dw slots are written
    exactly once, so the accumulator can live in w's dtype with no
    accumulation-order error. n_valid carries no float dependence on
    (x, w); its cotangent is dropped."""
    x, w, tgt, logz = res
    g_nll, _g_nv = cot
    with trace.scope("chunked_ce_bwd"):
        return _chunked_ce_bwd_impl(chunk, x, w, tgt, logz, g_nll)


def _chunked_ce_bwd_impl(chunk: int, x, w, tgt, logz, g_nll):
    v = w.shape[1]
    n_chunks, starts = _chunk_starts(v, chunk)
    w_p = _pad_vocab(w, n_chunks, chunk)
    valid = tgt >= 0
    vf = valid.astype(jnp.float32)
    tgt_c = jnp.where(valid, tgt, 0)
    nd = x.ndim
    lead_axes = tuple(range(nd - 1))
    f32 = jnp.float32
    row_scale = (vf * g_nll.astype(f32))[..., None]

    def body(carry, start):
        dx, dw = carry
        logits, w_c = _chunk_logits(x, w_p, start, chunk, v)
        p = jnp.exp(logits - logz[..., None])  # padded cols: exp(-inf)=0
        local = tgt_c - start
        in_chunk = (local >= 0) & (local < chunk)
        # one_hot maps the out-of-range sentinel (-1) to an all-zero row
        onehot = jax.nn.one_hot(
            jnp.where(in_chunk, local, -1), chunk, dtype=f32
        )
        q = ((p - onehot) * row_scale).astype(x.dtype)
        dx = dx + lax.dot_general(
            q, w_c.astype(x.dtype),
            (((nd - 1,), (1,)), ((), ())),
            preferred_element_type=f32,
        )
        dw_c = lax.dot_general(
            x, q,
            ((lead_axes, lead_axes), ((), ())),
            preferred_element_type=f32,
        )
        dw = lax.dynamic_update_slice_in_dim(
            dw, dw_c.astype(dw.dtype), start, axis=1
        )
        return (dx, dw), None

    init = (
        jnp.zeros(x.shape, f32),  # dx sums over chunks: f32 accumulator
        jnp.zeros((w.shape[0], n_chunks * chunk), w.dtype),
    )
    (dx, dw), _ = lax.scan(body, init, starts)
    dx = dx.astype(x.dtype)
    dw = dw[:, :v]
    # integer targets take a symbolic-zero cotangent
    dtgt = np.zeros(tgt.shape, jax.dtypes.float0)
    return dx, dw, dtgt


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)
