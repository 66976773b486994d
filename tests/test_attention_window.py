"""Flash attention under a sliding window (``window=``: query ``i`` sees
key ``j`` iff ``0 <= i - j < window``): the three Pallas kernels in
interpret mode against the jnp reference with the same mask, forward and
gradients, ``lse``'s cotangent too; the band's block walk; the tiles a
window gets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import (
    _band_steps,
    band_work,
    choose_tiles,
    flash_attention,
    flash_attention_with_lse,
    flash_tiles,
    mha_reference,
    mha_reference_with_lse,
)

BF16 = jnp.bfloat16


def _qkv(s=256, h=2, hkv=2, d=32, dv=None, b=1, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, dv or d), jnp.float32)
    return q, k, v


def _plain(q, k, v, window):
    """The band by explicit scores, written apart from the program."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    n = q.shape[1]
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where((i >= j) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _with_lse(fn, q, k, v, w_out, w_lse):
    """Value and the three gradients of a loss that reads out and lse."""
    def loss(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * w_out) + jnp.sum(lse * w_lse)

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


# tiles of 64: a window smaller than, equal to, not a multiple of, a
# multiple of, and larger than the tiles
WINDOWS = [1, 17, 64, 100, 128, 200]


@pytest.mark.parametrize("window", WINDOWS)
def test_forward_matches_the_band(window):
    q, k, v = _qkv()
    want = _plain(q, k, v, window)
    np.testing.assert_allclose(
        mha_reference(q, k, v, window=window), want, atol=2e-5, rtol=2e-5)
    got = flash_attention(q, k, v, True, 64, 64, interpret=True,
                          window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", WINDOWS)
def test_gradients_and_the_lse_cotangent(window):
    q, k, v = _qkv(seed=1)
    keys = jax.random.split(jax.random.key(7), 2)
    w_out = jax.random.normal(keys[0], q.shape)
    w_lse = jax.random.normal(keys[1], (1, 2, 256))
    want = _with_lse(
        lambda q, k, v: mha_reference_with_lse(q, k, v, window=window),
        q, k, v, w_out, w_lse)
    got = _with_lse(
        lambda q, k, v: flash_attention_with_lse(
            q, k, v, True, 64, 64, True, None, window),
        q, k, v, w_out, w_lse)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("group,hkv", [(7, 1), (1, 2)])
@pytest.mark.parametrize("tiles", [(32, 64), (64, 32), (128, 128)])
def test_groups_and_uneven_tiles(group, hkv, tiles):
    q, k, v = _qkv(h=group * hkv, hkv=hkv, seed=2)
    w = jax.random.normal(jax.random.key(3), q.shape)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(
            q, k, v)

    want = grads(lambda q, k, v: _plain(q, k, v, 100))
    got = grads(lambda q, k, v: flash_attention(
        q, k, v, True, *tiles, interpret=True, window=100))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_two_head_widths_with_a_window():
    q, k, v = _qkv(d=48, dv=32, h=4, hkv=2, seed=4)
    w = jax.random.normal(jax.random.key(5), (1, 256, 4, 32))

    def grads(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)

    want = grads(lambda q, k, v: _plain(q, k, v, 72))
    got = grads(lambda q, k, v: flash_attention(
        q, k, v, True, 64, 64, interpret=True, window=72))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [256, 300, 10 ** 6])
def test_a_window_no_shorter_than_the_sequence_is_causal_bit_for_bit(window):
    q, k, v = _qkv(seed=6)
    w = jax.random.normal(jax.random.key(8), q.shape)

    def run(**kw):
        return jax.value_and_grad(
            lambda *a: jnp.sum(flash_attention(
                *a, True, 64, 64, interpret=True, **kw) * w),
            argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(jax.tree.leaves(run(window=window)),
                    jax.tree.leaves(run())):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and on the reference path
    assert np.array_equal(
        np.asarray(mha_reference(q, k, v, window=window)),
        np.asarray(mha_reference(q, k, v)))


def test_a_row_whose_first_fetched_block_is_wholly_masked():
    """q block 1 of 64 rows under a window of 40 starts at k block 0
    (row 64 sees keys 25..64), so rows 104..127 (keys 65.. up) find
    nothing of theirs in the first block they are handed: their running
    max is still the mask's value there, and exp(s - m) must be 0, not
    exp(0)."""
    q, k, v = _qkv(s=128, h=1, hkv=1, seed=9)
    # large values in the masked block: a row that let them in would
    # show it in the output and in lse
    v = v.at[:, :64].mul(1e3)
    out, lse = flash_attention_with_lse(q, k, v, True, 64, 64, True, None, 40)
    want, want_lse = mha_reference_with_lse(q, k, v, window=40)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out, _plain(q, k, v, 40), atol=2e-4, rtol=2e-5)


def test_the_band_walk_is_as_long_as_the_band():
    # 16384 positions, window 4096: a q block of 256 needs at most 9 k
    # blocks of 512, not 32; a k block of 512 at most 9 q blocks of 512
    assert _band_steps("k", 64, 32, 256, 512, 4096) == 9
    assert _band_steps("q", 32, 32, 512, 512, 4096) == 9
    # tiles of 64 over 256 positions, window 64: the diagonal block and
    # the one before it
    assert _band_steps("k", 4, 4, 64, 64, 64) == 2
    assert _band_steps("q", 4, 4, 64, 64, 64) == 2
    # window 1: the diagonal block alone
    assert _band_steps("k", 4, 4, 64, 64, 1) == 1
    # uneven tiles
    assert _band_steps("k", 8, 2, 32, 128, 100) == 2
    assert _band_steps("q", 8, 2, 32, 128, 100) == 8


@pytest.mark.parametrize("seq,bq,bk,window", [
    (16384, 256, 512, 4096), (16384, 1024, 1024, 4096), (512, 64, 128, 100),
    (512, 128, 64, 1), (256, 64, 64, 256), (384, 128, 64, 130),
    # the tiles a narrow window gets (PR 61): at the cells' lengths and at
    # the length the kernels are held to the reference below
    (16384, 256, 256, 512), (16384, 512, 512, 512), (8192, 512, 512, 513),
    (2048, 256, 256, 512), (2048, 512, 512, 512), (2048, 512, 512, 513)])
def test_the_band_walk_covers_every_blocks_edges(seq, bq, bk, window):
    """`_band_steps` (Python ints: a grid's length is static) against the
    four edges the kernels and the index maps compute: no block needs
    more steps than the grid has, and one needs them all; `band_work`
    counts the same blocks."""
    n_q, n_k = seq // bq, seq // bk
    qi, ki = np.arange(n_q), np.arange(n_k)
    k_need = (np.asarray(attention._last_k_block(qi, bq, bk, n_k))
              - np.asarray(attention._first_k_block(qi, bq, bk, window)) + 1)
    q_need = (np.asarray(attention._last_q_block(ki, bq, bk, n_q, window))
              - np.asarray(attention._first_q_block(ki, bq, bk, n_q)) + 1)
    assert _band_steps("k", n_q, n_k, bq, bk, window) == k_need.max()
    assert _band_steps("q", n_q, n_k, bq, bk, window) == q_need.max()
    assert k_need.min() >= 1 and q_need.min() >= 1
    band = sum(min(i + 1, window) for i in range(seq))
    for kernel, need, steps in (("fwd", k_need, n_q * k_need.max()),
                                ("dkv", q_need, n_k * 3 * q_need.max())):
        assert band_work(kernel, seq, bq, bk, 3, window) == {
            "computed": 3 * int(need.sum()) * bq * bk, "band": 3 * band,
            "steps": steps}


@pytest.mark.parametrize("window,tiles,d,dv", [
    (512, (512, 512), 32, 32), (512, (256, 256), 32, 32),
    (513, (512, 512), 64, 32)])
def test_the_tiles_of_a_narrow_window_against_the_reference(
        window, tiles, d, dv):
    """The pairs `choose_tiles` gives a band of 512 (PR 61), pinned here
    at a length tier-1 has time for: out, lse, dq, dk, dv and the lse
    cotangent, at one head width and at two."""
    q, k, v = _qkv(s=2048, h=2, hkv=1, d=d, dv=dv, seed=12)
    keys = jax.random.split(jax.random.key(13), 2)
    w_out = jax.random.normal(keys[0], (1, 2048, 2, dv))
    w_lse = jax.random.normal(keys[1], (1, 2, 2048))
    want = _with_lse(
        lambda q, k, v: mha_reference_with_lse(q, k, v, window=window),
        q, k, v, w_out, w_lse)
    got = _with_lse(
        lambda q, k, v: flash_attention_with_lse(
            q, k, v, True, *tiles, True, None, window),
        q, k, v, w_out, w_lse)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_the_kernels_are_named_apart(monkeypatch):
    names = []
    real = attention.pl.pallas_call

    def spy(*args, **kw):
        names.append(kw["name"])
        return real(*args, **kw)

    monkeypatch.setattr(attention.pl, "pallas_call", spy)
    q, k, v = _qkv(s=128)
    for window in (None, 32):
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, True, 64, 64, interpret=True, window=window)))(q)
    assert names == [
        "attention_fwd", "attention_bwd_dq", "attention_bwd_dkv",
        "attention_fwd_swa", "attention_bwd_dq_swa", "attention_bwd_dkv_swa"]


#: (seq, q/k width, v width, group, window): the cells that pass a window
#: and the pairs the chooser gives them (docs/design/kernels.md 1b, PR 61)
CELLS = {
    # a band of eight tiles: the pairs of largest area, as without one
    "smallthinker": ((16384, 128, 128, 7, 4096), {
        "fwd": (256, 512), "dq": (256, 512), "dkv": (1024, 1024)}),
    "laguna": ((16384, 128, 128, 8, 512), {
        "fwd": (256, 256), "dq": (256, 512), "dkv": (512, 512)}),
    "dots3": ((8192, 256, 128, 1, 513), dict.fromkeys(
        ("fwd", "dq", "dkv"), (512, 512))),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_window_enters_the_tile_choice(cell, kernel):
    (s, d, dv, group, window), tiles = CELLS[cell]
    bq, bk = choose_tiles(kernel, s, s, d, group, BF16, dv, window)
    assert (bq, bk) == tiles[kernel]
    assert s % bq == 0 and s % bk == 0
    assert attention._vmem_bytes(
        kernel, bq, bk, d, group, 2, dv) <= attention._VMEM_BUDGET
    assert flash_tiles(s, s, d, group, BF16, dv, window)[kernel] == (bq, bk)
    # no window, or one no shorter than the sequence: the causal call's
    rows = (2048, 512) if group == 1 else (256, 512)
    causal = {"fwd": rows, "dq": rows, "dkv": (1024, 1024)}[kernel]
    assert choose_tiles(kernel, s, s, d, group, BF16, dv) == causal
    assert choose_tiles(kernel, s, s, d, group, BF16, dv, s) == causal


@pytest.mark.parametrize("s,window", [(128, 16), (64, 9), (256, 1)])
def test_a_window_narrower_than_every_side_takes_what_is_offered(s, window):
    # the tiny CPU configurations: one side is offered, or the step a
    # second block would cost outweighs the pairs it spares
    assert flash_tiles(s, s, 32, 2, jnp.float32, window=window) == (
        dict.fromkeys(("fwd", "dq", "dkv"), (s, s)))


def test_tiles_and_their_gauges_under_a_window():
    # the smallthinker cell's layers, window or none: group 7, 16384
    # positions (at a window of 4096 the pairs of largest area cost
    # least: kernels.md, PRs 37 and 61)
    for window in (None, 4096):
        assert flash_tiles(16384, 16384, 128, 7, BF16, window=window) == {
            "fwd": (256, 512), "dq": (256, 512), "dkv": (1024, 1024)}
    # a window call reports its tiles under names of its own
    q, k, v = _qkv(s=256)
    trace.gauge("attn.block_q", -1)
    flash_attention(q, k, v, True, interpret=True, window=64)
    g = trace.gauges()
    assert (g["attn.window_block_q"], g["attn.window_block_k"]) == (256, 256)
    assert (g["attn.window_dkv_block_q"],
            g["attn.window_dkv_block_k"]) == (256, 256)
    # one block of 256 x 256 a kernel for a band of 64
    assert g["attn.window_band_pct"] == round(
        100 * (64 * 65 // 2 + 192 * 64) / 256 ** 2, 1)
    assert g["attn.block_q"] == -1
    flash_attention(q, k, v, True, interpret=True)
    assert trace.gauges()["attn.block_q"] == 256


@pytest.mark.parametrize("tiles,pct", [
    # Laguna's window layers at the causal call's tiles, and at theirs
    (CELLS["smallthinker"][1], 35.0), (CELLS["laguna"][1], 52.9)])
def test_the_gauge_of_the_pairs_under_the_band(tiles, pct):
    attention._report_tiles(tiles, 16384, 512)
    g = trace.gauges()
    assert g["attn.window_band_pct"] == pct
    assert (g["attn.window_dkv_block_q"],
            g["attn.window_dkv_block_k"]) == tiles["dkv"]
    assert (g["attn.window_block_q"],
            g["attn.window_block_k"]) == tiles["fwd"]


@pytest.mark.parametrize("kw", [
    dict(causal=False, window=32), dict(causal=True, window=0)])
def test_a_window_is_causal_and_positive(kw):
    q, k, v = _qkv(s=64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, kw["causal"], 64, 64, interpret=True,
                        window=kw["window"])


def test_a_window_on_cross_attention_is_refused():
    q, k, v = _qkv(s=64)
    with pytest.raises(ValueError, match="self-attention"):
        flash_attention(q[:, :32], k, v, True, 32, 64, interpret=True,
                        window=16)
