"""``ops/kda.py`` at tiny sizes on the CPU: the chunked gated delta rule
against the token-by-token recurrence (``benchmarks/families/
kimi_linear.py ref_delta_rule``), outputs and every gradient, at strong
and weak decay and small and large steps, in both forms: the XLA ops
and the Pallas kernels in interpret mode (their hand-written backward
against the recurrence's autodiff and the XLA form's); the convolution
against a shifted sum. (The per-head form: ``test_kda_per_head.py``; the
elementwise passes around the kernels: ``test_kda_passes.py``; what the
three share: ``kda_inputs.py``.)"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.kimi_linear import ref_delta_rule
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import kda
from tests.kda_inputs import (
    DV, FORMS, H, SCALES, _close, _inputs, _io_inputs, _out_inputs)


@functools.lru_cache(maxsize=None)
def _form(form, chunk):
    """(outputs, the five gradients of ``sum(o * weight)``) of one form,
    jitted once a chunk size: interpret mode compiles for seconds and
    runs in milliseconds."""
    def out(*a):
        return kda.chunk_kda(*a, chunk=chunk, interpret=form == "kernels")

    def loss(weight, *a):
        return jnp.sum(out(*a).astype(jnp.float32) * weight)

    return jax.jit(out), jax.jit(jax.grad(loss, argnums=range(1, 6)))


_recurrent_grads = jax.jit(jax.grad(
    lambda weight, *a: jnp.sum(ref_delta_rule(*a) * weight),
    argnums=range(1, 6)))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("step", ["small", "large", "mid"])
@pytest.mark.parametrize("decay", ["strong", "weak", "init"])
@pytest.mark.parametrize("seq,chunk", [
    (64, 64),      # one chunk
    (192, 64),     # many
    (96, 32),
    (64, 16),      # a chunk of one sub-block
])
def test_chunked_form_matches_the_recurrence(seq, chunk, decay, step, form):
    args = _inputs(seq, decay, step)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form(form, chunk)
    out = forward(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, ref_delta_rule(*args), 5e-6)
    got = grads(weight, *args)
    for a, b in zip(got, _recurrent_grads(weight, *args)):
        # d/dg at g = -5 sums terms 1e5 apart in size: float32's order
        _close(a, b, 2e-4)
    if form == "kernels":
        # the hand-written backward against the XLA form's autodiff
        forward, grads = _form("xla", chunk)
        _close(out, forward(*args), 5e-6)
        for a, b in zip(got, grads(weight, *args)):
            _close(a, b, 2e-4)


@pytest.mark.parametrize("segment", [1, 2, 3])
def test_segments_carry_the_state(segment):
    """A sequence of several segments (padded to whole ones where 3 does
    not divide its 4 chunks) is the sequence of one."""
    args = _inputs(128, "init", "mid", seed=3)
    whole = kda.chunk_kda(*args, chunk=32, segment=16)
    _close(kda.chunk_kda(*args, chunk=32, segment=segment), whole, 5e-6)
    _close(whole, ref_delta_rule(*args), 5e-6)


def test_the_kernels_tiles_carry_the_state():
    """Five tiles (the last one padded): the state passes from a grid
    step to the next through scratch, its cotangent back from the last
    tile to the first, and the backward reads the state every chunk
    started from."""
    seq = 4 * kda.TILE + 70
    args = _inputs(seq, "init", "mid", seed=3)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form("kernels", 64)
    _close(forward(*args), ref_delta_rule(*args), 5e-6)
    for a, b in zip(grads(weight, *args), _recurrent_grads(weight, *args)):
        _close(a, b, 2e-4)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("seq", [40, 70])
def test_a_sequence_that_is_no_multiple_of_the_chunk(seq, form):
    args = _inputs(seq, "init", "mid", seed=5)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form(form, 32)
    out = forward(*args)
    assert out.shape == (2, seq, H, DV)
    _close(out, ref_delta_rule(*args), 5e-6)
    for a, b in zip(grads(weight, *args), _recurrent_grads(weight, *args)):
        assert a.shape == b.shape
        _close(a, b, 2e-4)


@pytest.mark.parametrize("form", FORMS)
def test_bfloat16_operands_float32_state(form):
    args = _inputs(128, "init", "mid", seed=7)
    weight = jax.random.normal(jax.random.key(9), (2, 128, H, DV))
    q, k, v = (a.astype(jnp.bfloat16) for a in args[:3])
    forward, grads = _form(form, 64)
    out = forward(q, k, v, *args[3:])
    assert out.dtype == jnp.bfloat16
    exact = tuple(a.astype(jnp.float32) for a in (q, k, v)) + args[3:]
    _close(out.astype(jnp.float32), ref_delta_rule(*exact), 2e-2)
    got = grads(weight, q, k, v, *args[3:])
    for a, b, like in zip(got, _recurrent_grads(weight, *exact),
                          (q, k, v) + args[3:]):
        assert a.dtype == like.dtype
        _close(a.astype(jnp.float32), b, 2e-2)


@pytest.mark.parametrize("form", FORMS)
def test_the_decay_bound(form):
    """At the stated bound, |g| = 9 a token, every factor is a normal
    float32 and the form still agrees to rounding; the sub-block
    references keep G = -576 over a chunk out of any single exponent."""
    q, k, v, g, beta = _inputs(64, "strong", "mid")
    g = jnp.full_like(g, -9.0)
    weight = jax.random.normal(jax.random.key(9), (2, 64, H, DV))
    forward, grads = _form(form, 64)
    out = forward(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, ref_delta_rule(q, k, v, g, beta), 5e-6)
    got = grads(weight, q, k, v, g, beta)
    want = _recurrent_grads(weight, q, k, v, g, beta)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        # d/dg is 4.5e-5 here, the sum of terms up to 1e4 times larger
        _close(a, b, 5e-3 if name == "g" else 2e-4)


def test_the_gauges_say_which_form_ran():
    """``kda.kernel`` is 1 where the traced call took the kernels, 0
    where the XLA form; the kernels say their grid's block: both heads
    of these inputs a grid step, each an independent chain."""
    args = _inputs(256, "init", "mid")
    kda.chunk_kda(*args, chunk=64, interpret=True)
    gauges = trace.gauges()
    assert gauges["kda.kernel"] == 1
    assert gauges["kda.heads_per_step"] == H
    assert gauges["kda.chunks_per_step"] == 2     # a tile of 128 rows
    kda.chunk_kda(*args, chunk=64)                # off the TPU: XLA ops
    assert trace.gauges()["kda.kernel"] == 0
    kda.chunk_kda(*args, chunk=128, interpret=True)   # no kernel admits it
    assert trace.gauges()["kda.kernel"] == 0
    # the passes around the kernels say theirs, each where it chooses
    xs, taps, _ = _io_inputs(1, 40, 2, jnp.float32)
    o, gate, weight = _out_inputs(1, 40, 2, jnp.float32)
    for fused in (True, False):
        kda.conv_silu_norm(xs, taps, heads=2, scales=SCALES, interpret=fused)
        assert trace.gauges()["kda.io_fused"] == int(fused)
    for fused in (True, False):
        kda.norm_gate(o, gate, weight, 1e-5, interpret=fused)
        assert trace.gauges()["kda.io_fused"] == int(fused)


def test_chunk_must_be_whole_sub_blocks():
    with pytest.raises(ValueError, match="multiple of 16"):
        kda.chunk_kda(*_inputs(48, "init", "mid"), chunk=24)


@pytest.mark.parametrize("taps", [1, 4])
def test_convolution_is_a_causal_shifted_sum(taps):
    x = jax.random.normal(jax.random.key(0), (2, 11, 6))
    w = jax.random.normal(jax.random.key(1), (6, taps))
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[1]):
        for back in range(min(taps, t + 1)):
            want[:, t] += np.asarray(x[:, t - back] * w[:, taps - 1 - back])
    np.testing.assert_allclose(kda.causal_conv(x, w), want, atol=1e-5)
    # nothing of a later token reaches an earlier one
    bumped = kda.causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(bumped[:, :7], kda.causal_conv(x, w)[:, :7])
