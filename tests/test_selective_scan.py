"""``ops/selective_scan.py`` on the CPU: the chunked XLA form and the two
Pallas kernels (``interpret``) against the token-by-token recurrence,
values and all six gradients, at a length the chunk does not divide, from
``dt A`` of -1e-3 to -60 a token, float32 and bfloat16 ``x``; what the
forward rule names; which sizes the kernels take."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import selective_scan as ss

NAMES = ("x", "dt", "A", "B", "C", "D")
# |A| from .. to, over the states: with dt in [0.01, 2] the decay's
# exponent dt A a token
DECAYS = {"slow": (0.1, 1.0), "fast": (1.0, 30.0)}


def _operands(seed, b, s, c, n, decay, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 8)
    lo, hi = DECAYS[decay]
    x = jax.random.normal(ks[0], (b, s, c)).astype(dtype)
    B = (jax.random.normal(ks[1], (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[2], (b, s, n)) * 0.3).astype(dtype)
    dt = jnp.exp(jax.random.uniform(
        ks[3], (b, s, c), minval=np.log(0.01), maxval=np.log(2.0)))
    # a decay a channel and a state: no two alike
    A = -jnp.exp(jnp.linspace(np.log(lo), np.log(hi), c * n)
                 ).reshape(n, c).T * jnp.exp(
                     0.1 * jax.random.normal(ks[4], (c, n)))
    D = jax.random.normal(ks[5], (c,))
    ct = jax.random.normal(ks[6], (b, s, c)).astype(dtype)
    return (x, dt, A, B, C, D), ct


def _rel(got, want):
    got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
    return float(jnp.linalg.norm(got - want)
                 / (jnp.linalg.norm(want) + 1e-30))


def _both(args, ct, chunk, interpret):
    got, vjp = jax.vjp(lambda *a: ss.selective_scan(
        *a, chunk=chunk, interpret=interpret), *args)
    return got, vjp(ct)


_both_jit = jax.jit(_both, static_argnums=(2, 3))
_definition = jax.jit(lambda args, ct: (
    lambda out: (out[0], out[1](ct.astype(jnp.float32))))(
        jax.vjp(ss.recurrence, *args)))


@pytest.fixture(scope="module")
def recurrences():
    """The definition's output and vjp a decay range, once: 40 tokens of
    256 channels (two lane tiles) of 16 states."""
    out = {}
    for decay in DECAYS:
        args, ct = _operands(0, 2, 40, 256, 16, decay)
        out[decay] = (args, ct) + tuple(_definition(args, ct))
    return out


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("form", ["xla", "kernels"])
def test_both_forms_are_the_recurrence(recurrences, form, chunk, decay):
    """40 tokens in chunks of 8 (five whole chunks) and of 16 (two whole
    chunks and a padded one)."""
    args, ct, want, d_want = recurrences[decay]
    exponents = args[1][..., None] * args[2]
    assert float(jnp.min(exponents)) < (-60 if decay == "fast" else -1)
    assert float(jnp.max(exponents)) > (-2e-3 if decay == "slow" else -0.05)
    got, d_got = _both_jit(args, ct, chunk, form == "kernels")
    assert trace.gauges()["mamba.kernel"] == (form == "kernels")
    assert trace.gauges()["mamba.chunk"] == chunk
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, want) < 2e-6
    for name, a, b in zip(NAMES, d_got, d_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < 2e-5, name


def test_a_sequence_shorter_than_the_chunk_is_one_padded_chunk():
    args, _ = _operands(1, 1, 11, 128, 16, "slow")
    want = ss.recurrence(*args)
    for interpret in (False, True):
        assert _rel(ss.selective_scan(*args, chunk=256, interpret=interpret),
                    want) < 2e-6
    assert trace.gauges()["mamba.chunk"] == 16


@pytest.mark.parametrize("form", ["xla", "kernels"])
def test_bfloat16_operands(form):
    """bfloat16 ``x``, ``B``, ``C`` (``dt``, ``A``, ``D`` float32, as the
    layer hands them over): the state, the sums and the decays stay
    float32 inside, so the output is the recurrence on the rounded
    operands to bfloat16's own rounding, and the gradients to a few of
    them."""
    args, ct = _operands(2, 1, 24, 128, 16, "slow", jnp.bfloat16)
    want, d_want = _definition(args, ct)
    got, d_got = _both_jit(args, ct, 8, form == "kernels")
    assert got.dtype == jnp.bfloat16
    assert _rel(got, want) < 4e-3
    for name, a, b in zip(NAMES, d_got, d_want):
        assert a.dtype == args[NAMES.index(name)].dtype, name
        assert _rel(a, b) < 1e-2, name


def test_exact_where_a_token_forgets_everything():
    """``dt A`` of -1e4 a token: every factor is an ``exp`` of a
    non-positive number, so nothing overflows and the state is the
    token's own input."""
    (x, dt, A, B, C, D), ct = _operands(3, 1, 16, 128, 16, "fast")
    args = (x, dt, A * 1e4, B, C, D)
    for interpret in (False, True):
        got, d_got = _both_jit(args, ct, 8, interpret)
        want = (dt * x) * jnp.einsum("bsn,bsn->bs", B, C)[..., None] + D * x
        assert _rel(got, want) < 1e-6
        assert all(bool(jnp.all(jnp.isfinite(d))) for d in d_got)


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels"])
def test_a_checkpoint_that_keeps_the_named_pair_recomputes_no_scan(interpret):
    args, _ = _operands(4, 1, 32, 128, 16, "slow")

    def fn(*a):
        # squared: what follows the scan reads its output, as the gate does
        return (ss.selective_scan(*a, chunk=8, interpret=interpret) ** 2
                ).sum()

    trace.gauge("mamba.state_kept", 0)
    met = []

    def kept_names(name):
        met.append(name)
        ss.report_kept(name)

    kept = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(fn, True, ss.KEPT, kept_names), *args)]
    whole = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(fn, True), *args)]
    # the output (x and dt, which have its shape, are arguments and stay)
    # and a float32 state a chunk, in the form's own layout
    out = (1, 32, 128)
    states = (1, 4, 16, 128) if interpret else (4, 1, 128, 16)
    assert kept.count(out) == whole.count(out) + 1
    assert kept.count(states) == 1 and states not in whole
    assert set(met) == set(ss.KEPT)
    assert trace.gauges()["mamba.state_kept"] == 1
    want = jax.grad(fn, argnums=(0, 1))(*args)
    got = jax.grad(stack.recompute(fn, True, ss.KEPT), argnums=(0, 1))(*args)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-6


@pytest.mark.parametrize("c, n, chunk, fits", [
    (5120, 16, 256, True), (128, 16, 8, True), (64, 16, 256, False),
    (128, 12, 256, False), (128, 16, 12, False), (128, 256, 256, False),
])
def test_which_sizes_the_kernels_take(c, n, chunk, fits):
    assert ss.kernels_fit(c, n, chunk) == fits


def test_channels_a_grid_step():
    assert ss._channels_a_step(5120) == 512
    assert ss._channels_a_step(128) == 128
    assert ss._channels_a_step(384) == 384
    assert ss._channels_a_step(640) == 128
