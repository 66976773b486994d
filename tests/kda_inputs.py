"""What the three files of ``ops/kda.py``'s tests share (``test_kda.py``: the
per-channel form; ``test_kda_per_head.py``: the per-head form;
``test_kda_passes.py``: the elementwise passes around the kernels): the
tiny sizes, the inputs as the layer makes them, and the comparison."""

import jax
import jax.numpy as jnp


H, DK, DV = 2, 16, 8
FORMS = ["xla", "kernels"]


def _inputs(seq, decay, step, seed=0):
    """Normalised q and k as the layer makes them; ``decay``: "strong"
    is g = -5 a token and channel (G = -320 over a chunk of 64),
    "weak" within 0.01 of zero, "init" the layer's own range at init;
    ``step``: beta near 0, near 1, or across (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (2, seq, H, DK))
    k = jax.random.normal(ks[1], (2, seq, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, seq, H, DV))
    u = jax.random.uniform(ks[3], (2, seq, H, DK), minval=0.5, maxval=1.0)
    g = {"strong": jnp.full_like(u, -5.0), "weak": -0.01 * u,
         "init": -1.6 * u}[decay]
    shift = {"small": -6.0, "large": 6.0, "mid": 0.0}[step]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, H)) + shift)
    return q, k, v, g, beta


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


# ---------------------------------------------------------------------------
# The elementwise passes around the kernels
# ---------------------------------------------------------------------------

D = 128                                # a head's channels: whole lanes
SCALES = (D ** -0.5, 1.0, None)        # q, k, v as the layer asks


def _io_inputs(b, s, h, dtype, d=D, seed=0):
    ks = jax.random.split(jax.random.key(seed), 9)
    xs = tuple(jax.random.normal(k, (b, s, h * d)).astype(dtype)
               for k in ks[:3])
    taps = tuple(jax.random.uniform(k, (h * d, 4), minval=-0.5, maxval=0.5)
                 for k in ks[3:6])
    weights = tuple(jax.random.normal(k, (b, s, h, d)) for k in ks[6:])
    return xs, taps, weights


def _out_inputs(b, s, h, dtype, d=D, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    o = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    gate = (2.0 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
    return o, gate, 1.0 + 0.3 * jax.random.normal(ks[2], (d,))
