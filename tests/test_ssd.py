"""``ops/ssd.py`` on the CPU: the chunked XLA form and the two Pallas
kernels (``interpret``) against the token-by-token recurrence, values and
every gradient, at heads of 64 and one group, at a length the chunk does
not divide, from ``dt A`` of -1e-3 to -200 a token; what the forward rule
names; which sizes the kernels take."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import ssd

NAMES = ("x", "dt", "A", "B", "C", "D")
# |A| from .. to: with dt in [0.01, 2] the decay's exponent dt A a token
DECAYS = {"slow": (0.1, 1.0), "fast": (1.0, 100.0)}


def _operands(seed, b, s, h, p, n, decay, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 7)
    lo, hi = DECAYS[decay]
    x = jax.random.normal(ks[0], (b, s, h, p)).astype(dtype)
    B = (jax.random.normal(ks[1], (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[2], (b, s, n)) * 0.3).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (b, s, h), minval=np.log(0.01), maxval=np.log(2.0)))
    A = -jnp.exp(jnp.linspace(np.log(lo), np.log(hi), h))
    D = jax.random.normal(ks[5], (h,))
    ct = jax.random.normal(ks[6], (b, s, h, p)).astype(dtype)
    return (x, dt, A, B, C, D), ct


def _rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / (jnp.linalg.norm(want) + 1e-30))


@pytest.fixture(scope="module")
def recurrences():
    """The definition's output and vjp a decay range, once."""
    out = {}
    for decay in DECAYS:
        args, ct = _operands(0, 2, 40, 4, 64, 32, decay)
        want, vjp = jax.vjp(ssd.recurrence, *args)
        out[decay] = (args, ct, want, vjp(ct))
    return out


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("form", ["xla", "kernels"])
def test_both_forms_are_the_recurrence(recurrences, form, decay):
    """Heads of 64, one B and one C for all four heads, 40 tokens in
    chunks of 16 (two whole chunks and a padded one)."""
    args, ct, want, d_want = recurrences[decay]
    assert float(jnp.min(args[1] * args[2])) < (-100 if decay == "fast"
                                                else -1)
    assert float(jnp.max(args[1] * args[2])) > (-0.02 if decay == "slow"
                                                else -0.05)
    got, vjp = jax.vjp(lambda *a: ssd.ssd(
        *a, chunk=16, interpret=form == "kernels"), *args)
    assert trace.gauges()["ssm.kernel"] == (form == "kernels")
    assert _rel(got, want) < 2e-6
    for name, a, b in zip(NAMES, vjp(ct), d_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < 2e-5, name


def test_a_sequence_shorter_than_the_chunk_is_one_padded_chunk():
    args, _ = _operands(1, 1, 11, 2, 64, 16, "slow")
    want = ssd.recurrence(*args)
    for interpret in (False, True):
        assert _rel(ssd.ssd(*args, chunk=256, interpret=interpret),
                    want) < 2e-6
    assert trace.gauges()["ssm.chunk"] == 16


@pytest.mark.parametrize("h,p", [(2, 128), (3, 64), (8, 16)])
def test_the_kernels_take_whole_and_part_lane_tiles_of_heads(h, p):
    """Heads of 128 one at a time, an odd count of heads of 64 (no pair:
    one at a time), eight heads of 16 to a lane tile."""
    args, ct = _operands(2, 1, 32, h, p, 16, "fast")
    want, vjp = jax.vjp(lambda *a: ssd.ssd(*a, chunk=16), *args)
    got, vjp_k = jax.vjp(lambda *a: ssd.ssd(*a, chunk=16, interpret=True),
                         *args)
    assert _rel(got, want) < 2e-6
    for name, a, b in zip(NAMES, vjp_k(ct), vjp(ct)):
        assert _rel(a, b) < 2e-5, name


def test_bfloat16_operands_keep_a_float32_state():
    """The kernels round what XLA's form rounds (the masked scores, dt x,
    the state as an operand) and carry the state in float32: both are
    within bfloat16's rounding of the recurrence, and of each other."""
    args, ct = _operands(3, 1, 64, 2, 64, 32, "slow", jnp.bfloat16)
    want, vjp_r = jax.vjp(ssd.recurrence, *args)
    xla, vjp_x = jax.vjp(lambda *a: ssd.ssd(*a, chunk=16), *args)
    ker, vjp_k = jax.vjp(lambda *a: ssd.ssd(*a, chunk=16, interpret=True),
                         *args)
    assert ker.dtype == xla.dtype == jnp.bfloat16
    assert _rel(xla, want) < 0.01 and _rel(ker, want) < 0.01
    for name, a, b, c in zip(NAMES, vjp_k(ct), vjp_x(ct), vjp_r(
            ct.astype(jnp.float32))):
        assert a.dtype == b.dtype, name
        assert _rel(a, c) < 0.02 and _rel(b, c) < 0.02, name


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels"])
def test_a_checkpoint_that_keeps_the_named_pair_recomputes_no_scan(interpret):
    args, _ = _operands(4, 1, 32, 2, 64, 16, "slow")

    def fn(*a):
        # squared: what follows the scan reads its output, as the gated
        # norm does
        return (ssd.ssd(*a, chunk=16, interpret=interpret) ** 2).sum()

    trace.gauge("ssm.state_kept", 0)
    met = []

    def kept_names(name):
        met.append(name)
        ssd.report_kept(name)

    kept = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(fn, True, ssd.KEPT, kept_names), *args)]
    whole = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(fn, True), *args)]
    # the output (x, which has its shape, is an argument and stays) and
    # a float32 state a chunk and head, in the form's own layout
    out = (1, 32, 2, 64)
    states = (1, 2, 2 * 64, 16) if interpret else (2, 1, 2, 64, 16)
    assert kept.count(out) == whole.count(out) + 1
    assert kept.count(states) == 1 and states not in whole
    assert set(met) == set(ssd.KEPT)
    assert trace.gauges()["ssm.state_kept"] == 1
    want = jax.grad(fn, argnums=(0, 1))(*args)
    got = jax.grad(stack.recompute(fn, True, ssd.KEPT), argnums=(0, 1))(
        *args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("h,p,n,chunk,fits", [
    (64, 64, 128, 256, True),       # the cell's first rung
    (32, 64, 128, 256, True),       # the cell
    (128, 64, 128, 256, True),      # every published head: two grid rows
    (3, 64, 128, 256, False),       # an odd count of half-tile heads
    (32, 64, 64, 256, False),       # a state of half a lane tile
    (32, 64, 128, 64, False),       # a chunk of half a lane tile
])
def test_the_sizes_the_kernels_take_on_the_chip(h, p, n, chunk, fits):
    assert ssd.kernels_fit(h, p, n, chunk) == fits
    if fits:
        assert ssd._heads_a_step(h, p) == min(h, ssd.HEADS_A_STEP)


def test_the_decay_is_taken_in_float32_whatever_it_arrives_in():
    """``dt`` and ``A`` in bfloat16 are widened before the cumulative sum:
    the result is the float32 call's on the same (rounded) numbers."""
    (x, dt, A, B, C, D), _ = _operands(5, 1, 32, 2, 64, 16, "fast")
    dt16, A16 = dt.astype(jnp.bfloat16), A.astype(jnp.bfloat16)
    want = ssd.ssd(x, dt16.astype(jnp.float32), A16.astype(jnp.float32), B,
                   C, D, chunk=16)
    assert _rel(ssd.ssd(x, dt16, A16, B, C, D, chunk=16), want) < 1e-6
