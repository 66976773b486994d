"""``ops/rotary.py``'s three-row rotary: three equal rows are `apply_rope`
bit for bit; rows that differ against a rotation written out in float64;
the gradient is the rotation's transpose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import (
    apply_mrope, apply_rope, mrope_tables, rope_frequencies)
from dlrover_tpu.ops.rotary import turn

SECTIONS = {128: (16, 24, 24), 64: (8, 12, 12), 16: (2, 2, 4)}


def _x(b=2, s=48, h=3, d=16, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), (b, s, h, d)).astype(dtype)


def _rows(b=2, s=48, seed=1, high=20000):
    """Three rows that differ on a span, as an image's do."""
    base = np.sort(np.asarray(jax.random.randint(
        jax.random.key(seed), (b, s), 0, high)), axis=1)
    rows = np.stack([base, base, base]).astype(np.int32)
    rows[1, :, 10:30] += np.arange(20) // 5
    rows[2, :, 10:30] += np.arange(20) % 5
    return rows


@pytest.mark.parametrize("d", sorted(SECTIONS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_three_equal_rows_are_apply_rope_bit_for_bit(d, dtype):
    x = _x(d=d, dtype=dtype)
    inv_freq = rope_frequencies(d, 1e7)
    one = jnp.asarray(_rows()[0])
    got = jax.jit(lambda x, p: apply_mrope(
        x, jnp.stack([p, p, p]), inv_freq, SECTIONS[d]))(x, one)
    want = jax.jit(lambda x, p: apply_rope(x, p, inv_freq))(x, one)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("d", sorted(SECTIONS))
def test_rows_that_differ_turn_each_pair_by_its_own_row(d):
    """Pair ``i`` (channels ``i`` and ``i + d / 2``) by row ``c(i)``'s
    position at ``theta^(-2i/d)``, written out in float64."""
    theta, sections = 1e7, SECTIONS[d]
    x, rows = np.asarray(_x(d=d), np.float64), _rows()
    got = apply_mrope(jnp.asarray(x, jnp.float32), jnp.asarray(rows),
                      rope_frequencies(d, theta), sections)
    want = np.empty_like(x)
    for i in range(d // 2):
        row = 0 if i < sections[0] else 1 if i < sections[0] + sections[1] else 2
        angle = rows[row].astype(np.float64) * theta ** (-2.0 * i / d)
        cos, sin = np.cos(angle)[..., None], np.sin(angle)[..., None]
        a, b = x[..., i], x[..., i + d // 2]
        want[..., i], want[..., i + d // 2] = a * cos - b * sin, b * cos + a * sin
    # float32 angles at positions up to 20000: 2e4 x 2^-24 of a radian
    np.testing.assert_allclose(got, want, atol=5e-3)
    assert np.abs(np.asarray(got) - np.asarray(apply_rope(
        jnp.asarray(x, jnp.float32), jnp.asarray(rows[0]),
        rope_frequencies(d, theta)))).max() > 0.1


def test_the_tables_are_formed_once_and_turn_any_number_of_heads():
    d = 16
    rows = jnp.asarray(_rows())
    cos, sin = mrope_tables(rows, rope_frequencies(d, 1e4), SECTIONS[d])
    assert cos.shape == sin.shape == (2, 48, d // 2)
    assert cos.dtype == jnp.float32
    for h in (1, 5):
        x = _x(h=h, d=d)
        np.testing.assert_array_equal(
            turn(x, cos, sin),
            apply_mrope(x, rows, rope_frequencies(d, 1e4), SECTIONS[d]))


def test_the_gradient_is_the_rotations_transpose():
    """A rotation's transpose is the rotation by the opposite angles: the
    cotangent comes back turned the other way, and norms are kept."""
    d = 16
    x, g = _x(d=d), _x(d=d, seed=5)
    rows = jnp.asarray(_rows())
    cos, sin = mrope_tables(rows, rope_frequencies(d, 1e4), SECTIONS[d])
    out, vjp = jax.vjp(lambda x: turn(x, cos, sin), x)
    got, = vjp(g)
    np.testing.assert_allclose(got, turn(g, cos, -sin), atol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # <R x, g> == <x, R^T g>
    np.testing.assert_allclose(jnp.sum(out * g), jnp.sum(x * got), rtol=1e-4)


@pytest.mark.parametrize("sections", [(16, 24), (16, 24, 25), (8, 8, 8)])
def test_sections_that_do_not_deal_out_the_pairs_are_refused(sections):
    with pytest.raises(ValueError, match="sections"):
        mrope_tables(jnp.zeros((3, 1, 4), jnp.int32),
                     rope_frequencies(128, 1e7), sections)
