"""Flash attention with q/k heads wider than v heads and a stated
softmax scale (latent attention: 192 against 128, scale not
``1 / sqrt(d)``), in interpret mode against ``mha_reference``; and the
tile chooser left as it was for the one-width shapes the other cells
run."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import ring_attention, ulysses_attention
from dlrover_tpu.ops.attention import (
    _vmem_bytes,
    choose_tiles,
    flash_attention,
    flash_tiles,
    mha_reference,
)

BF16 = jnp.bfloat16
SCALE = 192 ** -0.5 * 1.4159 ** 2


def _qkv(b=2, s=256, h=4, hkv=4, d=192, dv=128, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, dv), jnp.float32)
    return q, k, v


def _plain(q, k, v, scale, causal=True):
    """Attention by explicit scores, written apart from the program."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("hkv", [4, 2])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_two_widths_and_scale(hkv, causal):
    q, k, v = _qkv(hkv=hkv)
    want = _plain(q, k, v, SCALE, causal)
    ref = mha_reference(q, k, v, causal=causal, scale=SCALE)
    got = flash_attention(q, k, v, causal, 128, 128, interpret=True,
                          scale=SCALE)
    assert got.shape == (2, 256, 4, 128)
    np.testing.assert_allclose(ref, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2])
def test_each_gradient_two_widths_and_scale(wrt):
    q, k, v = _qkv(s=128, hkv=2)
    w = jax.random.normal(jax.random.key(9), (2, 128, 4, 128), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    want = jax.grad(loss(lambda q, k, v: _plain(q, k, v, SCALE)), wrt)(
        q, k, v)
    got = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, True, 64, 64, interpret=True, scale=SCALE)), wrt)(
        q, k, v)
    assert got.shape == (q, k, v)[wrt].shape
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_default_scale_is_of_the_qk_width():
    q, k, v = _qkv(s=128)
    got = flash_attention(q, k, v, True, 64, 64, interpret=True)
    np.testing.assert_allclose(
        got, _plain(q, k, v, 1 / math.sqrt(192)), atol=2e-5, rtol=2e-5)


def test_scale_moves_the_result():
    q, k, v = _qkv(s=128)
    a = flash_attention(q, k, v, True, 64, 64, interpret=True, scale=SCALE)
    b = flash_attention(q, k, v, True, 64, 64, interpret=True)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-2


# the shapes the accepted cells run (seq 4096, bf16): Mistral's group of
# 4 and OLMoE's group of 1 at 128-wide heads. What the chooser gave them
# before it knew of a second width, it gives them now.
@pytest.mark.parametrize("group", [1, 4, 8])
def test_choose_tiles_unchanged_for_one_width(group):
    want = {"fwd": (2048 // group, 512), "dq": (2048 // group, 512),
            "dkv": (1024, 1024)}
    assert flash_tiles(4096, 4096, 128, group, BF16) == want
    assert flash_tiles(4096, 4096, 128, group, BF16, 128) == want
    for kernel, (bq, bk) in want.items():
        assert _vmem_bytes(kernel, bq, bk, 128, group, 2) == _vmem_bytes(
            kernel, bq, bk, 128, group, 2, 128)


def test_vmem_count_sees_both_widths():
    # 192 pads to 256 lanes, 128 stays: between the two one-width counts
    for kernel in ("fwd", "dq", "dkv"):
        narrow = _vmem_bytes(kernel, 512, 512, 128, 1, 2)
        both = _vmem_bytes(kernel, 512, 512, 192, 1, 2, 128)
        wide = _vmem_bytes(kernel, 512, 512, 192, 1, 2)
        assert narrow < both < wide
    assert choose_tiles("fwd", 4096, 4096, 192, 1, BF16, 128) is not None


def test_sequence_parallel_forms_refuse_two_widths():
    q, k, v = _qkv(s=64)
    for fn in (ring_attention, ulysses_attention):
        with pytest.raises(ValueError, match="two head widths"):
            fn(q, k, v, axis_name="sp")
