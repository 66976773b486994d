"""Rotary position embeddings (RoPE), llama-3 style.

Frequencies are computed once per (seq_len, head_dim) and closed over by the
jitted step — static shapes, no per-step host work. ``positions`` is passed
explicitly so sequence-parallel shards (ring attention) can rotate with
their *global* positions.

A token of an interleaved document has **three** positions (time, height,
width: the Qwen2-VL rule): `mrope_tables` forms the angles' cosines and
sines once from the three rows and ``mrope_section`` (pair ``i`` turns
by row ``c(i)``'s position), `turn` applies them, `apply_mrope` is the
two in one call.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 500000.0) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def yarn_frequencies(
    rot_dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> jnp.ndarray:
    """Yarn's inverse frequencies (arXiv 2309.00071, as ``deepseek_v3``
    computes them), shape (rot_dim // 2,), float32: pair ``i`` keeps its
    plain frequency ``theta^(-2i/rot_dim)`` where it turns more than
    ``beta_fast`` times over the original context, takes that frequency
    over ``factor`` where it turns less than ``beta_slow`` times, and a
    linear blend of the two between (``low``, ``high``: the pairs at
    which it turns exactly so often, floor and ceiling)."""
    def pair_turning(beta):
        return (rot_dim * math.log(original_max / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), rot_dim - 1)
    plain = rope_frequencies(rot_dim, theta)
    ramp = (jnp.arange(rot_dim // 2, dtype=jnp.float32) - low) / max(
        high - low, 0.001)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """Yarn's attention-temperature factor ``0.1 m ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(
    x: jnp.ndarray,          # (..., seq, n_heads, head_dim)
    positions: jnp.ndarray,  # (..., seq) int32 global positions
    inv_freq: jnp.ndarray,   # (rotary_dim // 2,)
    magnitude: float = 1.0,
) -> jnp.ndarray:
    """Rotary on a head's first ``2 len(inv_freq)`` channels, their first
    half against their second; the whole head where that is its width,
    the channels past it unrotated (a partial rotary: Qwen3-Next turns a
    quarter of a 256-wide head). ``magnitude`` multiplies cos and sin, so
    the turned channels alone (yarn's ``attention_factor`` where a config
    states it as a number: Laguna's full layers)."""
    rotary_dim = 2 * inv_freq.shape[0]
    if rotary_dim < x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, inv_freq,
                            magnitude)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (...,s,d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return turn(x, cos, sin)


def turn(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """``x (..., seq, n_heads, d)`` turned by the angles whose ``cos``,
    ``sin`` are ``(..., seq, d / 2)``: the first half of the channels
    against the second, in float32."""
    cos = cos[..., :, None, :]  # broadcast over heads
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_tables(
    positions: jnp.ndarray,     # (3, ..., seq) int32: time, height, width
    inv_freq: jnp.ndarray,      # (d / 2,)
    sections: Sequence[int],    # pairs a row, in order; sums to d / 2
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(cos, sin)``, each ``(..., seq, d / 2)`` float32, of the angles
    ``positions[c(i)] * inv_freq[i]``: pair ``i`` turns by the row whose
    section holds it (``mrope_section``, chunked: the first
    ``sections[0]`` pairs by row 0, the next ``sections[1]`` by row 1,
    the rest by row 2)."""
    if len(sections) != positions.shape[0] or sum(sections) != (
            inv_freq.shape[0]):
        raise ValueError(
            f"sections {tuple(sections)} do not deal the {inv_freq.shape[0]} "
            f"pairs out to the {positions.shape[0]} rows of positions")
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    edges = [sum(sections[:i]) for i in range(len(sections) + 1)]
    angles = jnp.concatenate(
        [angles[row, ..., lo:hi]
         for row, (lo, hi) in enumerate(zip(edges, edges[1:]))], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_mrope(
    x: jnp.ndarray,          # (..., seq, n_heads, head_dim)
    positions: jnp.ndarray,  # (3, ..., seq) int32
    inv_freq: jnp.ndarray,   # (head_dim // 2,)
    sections: Sequence[int],
) -> jnp.ndarray:
    """Rotary on the whole head with a position a row of ``positions``
    (`mrope_tables`); three equal rows give `apply_rope`'s result."""
    return turn(x, *mrope_tables(positions, inv_freq, sections))
