"""Normalization ops.

Pure jnp — XLA fuses the reduction + rescale into the surrounding matmuls'
epilogues on TPU, so a Pallas kernel buys nothing here (HBM-bound elementwise
work is exactly what the XLA fuser exists for). Computation is done in
float32 regardless of input dtype for numerical parity with the usual
bfloat16 training recipe.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)
