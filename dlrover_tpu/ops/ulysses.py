"""Ulysses-style (all-to-all) sequence parallelism.

The second first-class long-context strategy next to ring attention
(``ops/ring_attention.py``). Green-field relative to the reference,
which delegates sequence parallelism to the frameworks it launches
(SURVEY.md §5 "long-context — absent"); the pattern is the
DeepSpeed-Ulysses one (arXiv:2309.14509), re-done with XLA collectives.

Mechanics over an ``sp`` mesh axis of size P:

    in : (b, s/P, h,   d)  sequence-sharded (how the rest of the model
                           computes: norms/mlp are pointwise in s)
    a2a: (b, s,   h/P, d)  head-sharded — each rank now owns the FULL
                           sequence for h/P heads
    attention (any single-device kernel — the Pallas flash kernel here)
    a2a: (b, s/P, h,   d)  back to sequence-sharded

Communication is two all-to-alls moving activations once each
(O(b·s·h·d / P) per rank), versus ring's P-1 ppermute hops of K/V —
cheaper when heads divide P well and seq is only moderately long; ring
wins when s/P is large enough to hide K/V hops behind per-chunk
compute. Both ride ICI; pick per workload (``attn_impl`` in the model
configs).

Causality is preserved exactly: heads are independent in attention, so
re-partitioning heads while un-sharding the sequence computes the same
math as single-device causal attention per head.

GQA: P must divide the K/V head count too. With fewer KV heads than P,
when KV heads don't divide sp, each kv head is replicated by
sp/gcd(hkv, sp) (the DeepSpeed-Ulysses GQA treatment) so the scatter
divides — exact, at the cost of a proportionally larger kv all-to-all;
shapes where even replication can't produce a valid GQA grouping
(h % lcm(hkv, sp) != 0) raise with a pointer to ring attention.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from dlrover_tpu.ops.attention import flash_attention
from dlrover_tpu.ops.ring_attention import _one_head_width


def _a2a_scatter_heads(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """(b, s/P, h, d) -> (b, s, h/P, d): scatter heads, gather seq."""
    return lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def _a2a_gather_heads(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """(b, s, h/P, d) -> (b, s/P, h, d): gather heads, scatter seq."""
    return lax.all_to_all(
        x, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jnp.ndarray,  # (b, s_local, h, d)
    k: jnp.ndarray,  # (b, s_local, hkv, d)
    v: jnp.ndarray,  # (b, s_local, hkv, d)
    axis_name: str,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Call under ``shard_map`` with q/k/v sequence-sharded over
    ``axis_name``; returns the output in the same layout. Differentiable
    end to end (all_to_all is linear; the flash kernel carries its own
    VJP)."""
    _one_head_width(q, v, "ulysses_attention")
    sp = lax.axis_size(axis_name)
    if sp == 1:
        return flash_attention(q, k, v, causal=causal,
                               block_q=block_q, block_k=block_k)
    h, hkv = q.shape[2], k.shape[2]
    if h % sp:
        raise ValueError(
            f"ulysses needs query heads divisible by sp: h={h} sp={sp}"
            " (use ring attention otherwise)"
        )
    if hkv % sp:
        # GQA with fewer (or indivisible) KV heads than sp: replicate
        # each kv head so the head-scatter divides (DeepSpeed-Ulysses
        # GQA treatment). jnp.repeat keeps the q->kv group mapping of
        # the flash kernel intact ([k0,k0,k1,k1,...] with the ratio
        # halved per replica), and backward sums replica grads — exact.
        # Cost: kv all-to-all volume grows by the replication factor;
        # kv is the small side, and this unlocks ulysses for e.g.
        # 8-kv-head models on sp=16.
        import math

        rep = sp // math.gcd(hkv, sp)
        if h % (hkv * rep):
            raise ValueError(
                f"ulysses GQA replication needs h % lcm(hkv, sp) == 0: "
                f"h={h} hkv={hkv} sp={sp} (lcm={hkv * rep}); use ring "
                "attention for this shape"
            )
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        hkv *= rep
    # NB: comm attribution for the all-to-alls is recorded at the MODEL
    # layer (models/llama.py), which knows the per-step multiplicity
    # (n_layers x microbatches); this body traces once per layer scan.
    qg = _a2a_scatter_heads(q, axis_name)
    kg = _a2a_scatter_heads(k, axis_name)
    vg = _a2a_scatter_heads(v, axis_name)
    out = flash_attention(qg, kg, vg, causal=causal,
                          block_q=block_q, block_k=block_k)
    return _a2a_gather_heads(out, axis_name)
