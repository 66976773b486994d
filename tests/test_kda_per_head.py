"""``ops/kda.py``'s per-head form (one decay a head over grouped value
heads) in both forms, the XLA ops and the Pallas kernels in interpret
mode, against the token-by-token recurrence of
``benchmarks/families/qwen3_next.py`` (see ``test_kda.py``)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmarks.families.qwen3_next import ref_delta_rule as ref_gdn_rule
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import kda
from tests.kda_inputs import DK, DV, FORMS, _close


# ---------------------------------------------------------------------------
# The per-head form (one decay a head over grouped value heads): both
# forms against the token-by-token recurrence of
# ``benchmarks/families/qwen3_next.py``, at decays the channel form's
# bound does not admit.
# ---------------------------------------------------------------------------

GDN_DECAYS = [-0.1, -5.0, -21.0]


@functools.lru_cache(maxsize=None)
def _gdn_form(form, chunk):
    def out(*a):
        return kda.chunk_gdn(*a, chunk=chunk, interpret=form == "kernels")

    def loss(weight, *a):
        return jnp.sum(out(*a).astype(jnp.float32) * weight)

    return jax.jit(out), jax.jit(jax.grad(loss, argnums=range(1, 6)))


_gdn_recurrent_grads = jax.jit(jax.grad(
    lambda weight, *a: jnp.sum(ref_gdn_rule(*a) * weight),
    argnums=range(1, 6)))


def _gdn_inputs(seq, decay, hk=2, r=2, seed=0, dtype=jnp.float32):
    """``g`` between ``0.2 decay`` and ``decay`` a token, a number a
    value head; ``r`` value heads a key head."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (2, seq, hk, DK))
    k = jax.random.normal(ks[1], (2, seq, hk, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, seq, hk * r, DV))
    g = decay * jax.random.uniform(ks[3], (2, seq, hk * r), minval=0.2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, hk * r)))
    weight = jax.random.normal(ks[5], v.shape)
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta), weight


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("decay", GDN_DECAYS)
# 300 rows of 16: 19 chunks, so two of the XLA form's segments, the
# second padded
@pytest.mark.parametrize("seq,chunk", [(300, 16), (128, 32), (192, 64)])
def test_per_head_form_matches_the_recurrence(seq, chunk, decay, form):
    """Outputs and the five gradients, two value heads a key head; at
    ``g = -21`` a token (the public initialisation's reach) as at
    -0.1: the mask ``e^(G_i - G_j)`` is at most 1 whatever ``g`` is."""
    args, weight = _gdn_inputs(seq, decay)
    forward, grads = _gdn_form(form, chunk)
    with jax.default_matmul_precision("highest"):
        out = forward(*args)
        want = ref_gdn_rule(*args)
        got = grads(weight, *args)
        want_grads = _gdn_recurrent_grads(weight, *args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, want, 5e-6)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want_grads):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a)))
        # dg at -21 is the small difference of terms a thousand times it
        _close(a, b, 2e-4 if name == "g" else 2e-5)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("hk,r", [(1, 1), (1, 4), (4, 1), (2, 8)])
def test_per_head_form_at_other_groupings(hk, r, form):
    """One value head a key head (no sharing), one key head under all,
    and more value heads a key head than a grid step's chains."""
    args, weight = _gdn_inputs(96, -3.0, hk=hk, r=r, seed=3)
    forward, grads = _gdn_form(form, 32)
    with jax.default_matmul_precision("highest"):
        _close(forward(*args), ref_gdn_rule(*args), 5e-6)
        for a, b in zip(grads(weight, *args),
                        _gdn_recurrent_grads(weight, *args)):
            _close(a, b, 5e-5)


def test_per_head_kernels_with_bfloat16_operands():
    """bf16 q, k, v: the kernels against the XLA form on the same
    operands (both round the state's products to bf16; the solve, the
    decays and the state are float32 in both)."""
    args, weight = _gdn_inputs(256, -2.0, dtype=jnp.bfloat16)
    (fx, gx), (fk, gk) = _gdn_form("xla", 64), _gdn_form("kernels", 64)
    out = fk(*args)
    assert out.dtype == jnp.bfloat16
    _close(out.astype(jnp.float32), fx(*args).astype(jnp.float32), 2e-2)
    for a, b in zip(gk(weight, *args), gx(weight, *args)):
        assert a.dtype == b.dtype
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 3e-2)


def test_per_head_form_says_which_form_ran_and_refuses_ragged_groups():
    args, _ = _gdn_inputs(64, -1.0)
    kda.chunk_gdn(*args, chunk=16, interpret=True)
    assert trace.gauges()["attn.gdn_kernel"] == 1
    kda.chunk_gdn(*args, chunk=16)                # off the TPU: XLA ops
    assert trace.gauges()["attn.gdn_kernel"] == 0
    kda.chunk_gdn(*args, chunk=8, interpret=True)     # no kernel admits it
    assert trace.gauges()["attn.gdn_kernel"] == 0
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="value heads over"):
        kda.chunk_gdn(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
