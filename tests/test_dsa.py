"""``ops/dsa.py``: the indexer's score kernels in interpret mode against
the XLA form and against the definition written apart, forward and the
backward ``L_I`` needs; the exact top-k threshold against ``lax.top_k``,
ties included; the head-summed probabilities; the KL and where its
gradient goes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import dsa
from dlrover_tpu.ops.attention import mha_reference_with_lse

S = 256


@pytest.fixture
def small_tiles(monkeypatch):
    """Several blocks a side at 256 positions."""
    monkeypatch.setattr(dsa, "_MAX_TILE", {
        "fwd": (128, 128), "dq": (128, 128),
        "probs": (128, 128)})


def _operands(b=2, s=S, h=4, d=32, seed=0):
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, d), jnp.float32),
            jax.random.normal(kw, (b, s, h), jnp.float32))


def _causal(s=S):
    return jnp.tril(jnp.ones((s, s), bool))


def _plain_scores(q, k, w):
    """The definition in one expression."""
    dots = jnp.einsum("bthd,bsd->bths", q, k)
    return jnp.sum(w[..., None] * jnp.maximum(dots, 0.0), axis=2)


@pytest.mark.parametrize("interpret", [False, True])
def test_index_scores_match_the_definition(interpret, small_tiles):
    q, k, w = _operands()
    got = dsa.index_scores(q, k, w, interpret=interpret)
    want = _plain_scores(q, k, w)
    np.testing.assert_allclose(
        jnp.where(_causal(), got, 0.0), jnp.where(_causal(), want, 0.0),
        atol=2e-4, rtol=2e-5)
    assert trace.gauges()["dsa.kernel"] == float(interpret)


def _index_grads(fn, q, k, w, g):
    return jax.grad(
        lambda q, k, w: jnp.sum(fn(q, k, w) * g), argnums=(0, 1, 2))(q, k, w)


@pytest.mark.parametrize("interpret", [False, True])
def test_index_scores_gradients(interpret, small_tiles):
    """The backward ``L_I`` needs: a cotangent that lives on causal
    entries only, as the KL's does."""
    q, k, w = _operands(seed=1)
    g = jax.random.normal(jax.random.key(9), (2, S, S)) * _causal()
    want = _index_grads(_plain_scores, q, k, w, g)
    got = _index_grads(lambda q, k, w: dsa.index_scores(
        q, k, w, interpret=interpret), q, k, w, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-4)


def test_kernels_write_zeros_above_the_diagonal_blocks(small_tiles):
    q, k, w = _operands(b=1)
    got = dsa.index_scores(q, k, w, interpret=True)
    assert not np.asarray(got[0, :128, 128:]).any()


def test_the_kernels_are_two_and_named(monkeypatch, small_tiles):
    names = []
    real = dsa.pl.pallas_call

    def spy(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **kw)

    monkeypatch.setattr(dsa.pl, "pallas_call", spy)
    q, k, w = _operands(b=1)
    jax.grad(lambda q: jnp.sum(dsa.index_scores(q, k, w, interpret=True)))(q)
    assert names == ["dsa_index_fwd", "dsa_index_bwd"]


@pytest.mark.parametrize("interpret", [False, True])
def test_the_gauge_counts_the_backward_kernels(interpret, small_tiles):
    dsa.index_scores(*_operands(b=1), interpret=interpret)
    assert trace.gauges()["dsa.index_bwd_kernels"] == float(interpret)


def test_dk_sums_over_the_q_blocks_of_each_batch_row(monkeypatch):
    """Four q blocks feed the first key block and two the second, in two
    batch rows: the key's gradient is summed across a row's q blocks in
    the kernel's scratch and zeroed where the next row begins."""
    monkeypatch.setattr(dsa, "_MAX_TILE", dict(dsa._MAX_TILE, dq=(128, 256)))
    s = 512
    q, k, w = _operands(b=2, s=s, seed=3)
    g = jax.random.normal(jax.random.key(4), (2, s, s)) * _causal(s)
    # the second row's cotangent alone must not see the first row's sums
    g = g.at[1].multiply(1e-3)
    want = _index_grads(_plain_scores, q, k, w, g)[1]
    got = _index_grads(
        lambda *a: dsa.index_scores(*a, interpret=True), q, k, w, g)[1]
    np.testing.assert_allclose(got[0], want[0], atol=2e-3, rtol=2e-4)
    np.testing.assert_allclose(got[1], want[1], atol=2e-6, rtol=2e-4)


@pytest.mark.parametrize("h,d", [(16, 64), (4, 128)])
def test_the_fused_backward_at_the_cells_head_shapes(h, d, small_tiles):
    """d``q``, d``k``, d``w`` at sixteen heads of 64 (keye-vl: half a
    lane tile) and at heads of 128 (dots3) against the definition's
    autodiff."""
    q, k, w = _operands(b=1, h=h, d=d, seed=5)
    g = jax.random.normal(jax.random.key(6), (1, S, S)) * _causal()
    want = _index_grads(_plain_scores, q, k, w, g)
    got = _index_grads(
        lambda *a: dsa.index_scores(*a, interpret=True), q, k, w, g)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-4)


# -- the selection ---------------------------------------------------------

def _top_k_mask(scores, topk):
    """``lax.top_k`` over the causal entries, scattered to a mask."""
    b, s, _ = scores.shape
    causal = np.asarray(_causal(s))
    _, idx = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    want = np.zeros((b, s, s), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    return want & causal


SCORES = {
    "normal": lambda key: jax.random.normal(key, (2, S, S)),
    # thirds: a dozen ties at every threshold
    "ties": lambda key: jnp.round(jax.random.normal(key, (2, S, S)) * 3) / 3,
    "all equal": lambda key: jnp.zeros((2, S, S)),
    "signed zeros and extremes": lambda key: jnp.where(
        jax.random.bernoulli(key, 0.5, (2, S, S)), -0.0, 0.0).at[
            :, :, ::7].set(3e38).at[:, :, 3::11].set(-3e38),
    "tiny and denormal": lambda key: jax.random.normal(
        key, (2, S, S)) * 1e-40,
}


@pytest.fixture
def small_select(monkeypatch):
    """`dsa_select` at 256 positions as it stands at 16384: four row
    blocks of 64, two sub-blocks a block, two trips a row of keys."""
    monkeypatch.setattr(dsa, "_SELECT_BUDGET", 64 * S * 14)
    monkeypatch.setattr(dsa, "_SELECT_SUB", 32)
    monkeypatch.setattr(dsa, "_SELECT_TRIP", 128)


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("topk", [1, 32, 100, 255])
@pytest.mark.parametrize("kind", sorted(SCORES))
def test_selection_is_lax_top_k_ties_to_the_lower_position(
        kind, topk, kernel, small_select):
    scores = SCORES[kind](jax.random.key(2))
    got = np.asarray(dsa.selection_mask(scores, topk, interpret=kernel))
    assert trace.gauges()["dsa.select_kernel"] == float(kernel)
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got != 0, _top_k_mask(scores, topk))
    # exactly min(t + 1, topk) keys a row: the count the roofline credits
    np.testing.assert_array_equal(
        got.sum(-1)[0], np.minimum(np.arange(S) + 1, topk))


@pytest.mark.parametrize("shape,topk", [
    # topk inside the second row block of four: the first writes the
    # causal mask and runs no pass, the second holds rows on both sides
    ((1, S, S), 90),
    # the last row block alone holds a row with more than topk keys
    ((2, S, S), 200),
    ((2, S, S), 64),
    # 128 does not divide the sequence: one block, one tile
    ((2, 96, 96), 20),
    ((1, 200, 200), 77),
    # 128 divides it and 256 does not: three tiles of keys a row
    ((1, 384, 384), 130),
])
@pytest.mark.parametrize("kind", ["normal", "ties"])
def test_the_kernel_is_the_xla_form_byte_for_byte(
        shape, topk, kind, small_select):
    scores = jax.random.normal(jax.random.key(11), shape)
    if kind == "ties":
        scores = jnp.round(scores * 2) / 2
    want = np.asarray(dsa.selection_mask(scores, topk))
    got = np.asarray(dsa.selection_mask(scores, topk, interpret=True))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        want != 0, _top_k_mask(scores, topk))


ABOVE = {
    "nan": lambda key, shape: jnp.full(shape, jnp.nan),
    "inf": lambda key, shape: jnp.full(shape, jnp.inf),
    "garbage": lambda key, shape: lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint32), jnp.float32),
}


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("above", sorted(ABOVE))
def test_what_lies_above_the_diagonal_is_never_counted(
        above, kernel, small_select):
    """`index_scores` leaves those entries unspecified."""
    scores = jnp.round(jax.random.normal(jax.random.key(12), (2, S, S)) * 4)
    want = dsa.selection_mask(jnp.where(_causal(), scores, 0.0), 70)
    got = dsa.selection_mask(
        jnp.where(_causal(), scores,
                  ABOVE[above](jax.random.key(13), scores.shape)),
        70, interpret=kernel)
    np.testing.assert_array_equal(got, want)


def test_a_block_of_rows_is_sized_from_the_sequence():
    # 14 bytes a key a row: the float32 block and the int8 block twice
    # (the pipeline's two buffers) and the int32 key copy
    assert dsa._select_rows(16384) == 128
    assert dsa._select_rows(8192) == 256
    assert dsa._select_rows(4096) == 512
    assert dsa._select_rows(128) == 128
    assert dsa._select_rows(96) == 96
    # 6144 = 48 x 128: the largest multiple of 32 that divides it and fits
    assert dsa._select_rows(6144) == 384
    # nothing fits: the XLA form
    assert dsa._select_rows(2**20) is None
    assert dsa._select_rows(2**11 * 3 + 8) is None


def test_a_sequence_no_block_fits_takes_the_xla_form(monkeypatch):
    monkeypatch.setattr(dsa, "_SELECT_BUDGET", 1024)
    scores = jax.random.normal(jax.random.key(3), (1, 64, 64))
    got = dsa.selection_mask(scores, 10, interpret=True)
    assert trace.gauges()["dsa.select_kernel"] == 0
    np.testing.assert_array_equal(got != 0, _top_k_mask(scores, 10))


def test_the_selection_is_one_kernel_and_no_array_of_bits(
        monkeypatch, small_tiles, small_select):
    """`selected_attention`'s lowered step: the threshold is the kernel
    `dsa_select`, and no ``(s, s)`` uint32 array is left of the XLA
    form."""
    names = []
    real = dsa.pl.pallas_call

    def spy(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **kw)

    monkeypatch.setattr(dsa.pl, "pallas_call", spy)
    q, k, _, _, scale = _grouped_attention()
    v = jax.random.normal(jax.random.key(3), k.shape)
    iq, ik, iw = _operands(h=4, d=32, seed=4)

    def step(interpret):
        return jax.jit(lambda *a: dsa.selected_attention(
            *a, 40, scale, interpret=interpret)[:2]).lower(
                q, k, v, iq, ik, iw).as_text()

    text = step(True)
    assert names.count("dsa_select") == 1
    assert f"tensor<2x{S}x{S}xui32>" not in text
    assert f"tensor<2x{S}x{S}xui32>" in step(False)
    assert names.count("dsa_select") == 1


def test_a_topk_no_shorter_than_the_sequence_selects_every_causal_key():
    scores = jax.random.normal(jax.random.key(3), (1, 64, 64))
    for topk in (64, 2048):
        np.testing.assert_array_equal(
            np.asarray(dsa.selection_mask(scores, topk))[0] != 0,
            np.asarray(_causal(64)))


def test_threshold_is_the_kth_largest_of_the_causal_row():
    scores = jax.random.normal(jax.random.key(4), (1, S, S))
    tau, _ = dsa.select_threshold(scores, 16)
    row = 200
    want = np.sort(np.asarray(scores)[0, row, :row + 1])[-16]
    assert np.asarray(tau)[0, row] == np.asarray(
        dsa._ordered_bits(jnp.float32(want)))
    assert np.asarray(tau)[0, 10] == 0      # 11 causal entries: all


def test_ordered_bits_order_as_the_floats_do():
    x = jnp.asarray([-3e38, -1.0, -1e-40, -0.0, 0.0, 1e-40, 1.0, 3e38],
                    jnp.float32)
    bits = np.asarray(dsa._ordered_bits(x)).astype(np.uint64)
    assert (np.diff(bits.astype(np.int64)) > 0).all()


# -- what the indexer learns from ----------------------------------------------

def _grouped_attention(h=8, hkv=2, d=24, seed=6):
    kq, kk, kv, ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(kq, (2, S, h, d))
    k = jax.random.normal(kk, (2, S, hkv, d))
    v = jax.random.normal(kv, (2, S, hkv, 16))
    mask = dsa.selection_mask(jax.random.normal(ks, (2, S, S)), 40)
    _, lse = mha_reference_with_lse(q, k, v, select=mask)
    return q, k, lse, mask, d ** -0.5


def _attention(seed=5, h=3, d=24):
    return _grouped_attention(h, h, d, seed)


# (heads, key heads): one head a key head, groups of 4 and 3 (a head
# count the trip does not divide), the keye-vl cell's 32 on 4
@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2), (6, 2), (32, 4)])
@pytest.mark.parametrize("interpret", [False, True])
def test_head_summed_probs(interpret, h, hkv, small_tiles):
    """The definition, query head ``j`` reading key head ``j // group``
    where it lies: the same numbers as the XLA form on ``k`` repeated a
    group's times."""
    q, k, lse, mask, scale = _grouped_attention(h, hkv)
    got = dsa.head_summed_probs(q, k, lse, mask, scale, interpret=interpret)
    assert got.shape == (2, S, S) and got.dtype == jnp.float32
    repeated = jnp.repeat(k, h // hkv, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q, repeated) * scale
    want = jnp.sum(jnp.where(
        (mask != 0)[:, None], jnp.exp(logits - lse[..., None]), 0.0), axis=1)
    np.testing.assert_allclose(got, want, atol=2e-6 * h, rtol=2e-5)
    np.testing.assert_allclose(
        got, dsa._probs_xla(q, repeated, lse, mask, scale),
        atol=2e-6, rtol=2e-5)
    # a head's probabilities sum to one over the selection
    np.testing.assert_allclose(got.sum(-1), h, rtol=1e-5)
    assert not np.asarray(got)[np.asarray(mask) == 0].any()


def test_probs_tiles_wholly_above_the_diagonal_are_zeros(small_tiles):
    """A dead tile is written, never computed: under a mask of ones the
    XLA form has every pair, the kernel the tiles a causal row reaches."""
    q, k, lse, _, scale = _grouped_attention()
    ones = jnp.ones((2, S, S), jnp.int8)
    got = np.asarray(
        dsa.head_summed_probs(q, k, lse, ones, scale, interpret=True))
    want = np.asarray(dsa._probs_xla(q, k, lse, ones, scale))
    assert want[:, :128, 128:].all() and not got[:, :128, 128:].any()
    for rows, cols in ((0, 0), (1, 0), (1, 1)):
        tile = np.s_[:, rows * 128:(rows + 1) * 128,
                     cols * 128:(cols + 1) * 128]
        np.testing.assert_allclose(got[tile], want[tile], rtol=2e-5)


def test_the_probabilities_are_one_kernel_over_tiles(monkeypatch, small_tiles):
    """The heads are a loop inside a (batch row, query block, key block)
    grid step, not a grid axis."""
    calls = []
    real = dsa.pl.pallas_call

    def spy(*a, **kw):
        calls.append((kw.get("name"), kw.get("grid")))
        return real(*a, **kw)

    monkeypatch.setattr(dsa.pl, "pallas_call", spy)
    q, k, lse, mask, scale = _grouped_attention()
    dsa.head_summed_probs(q, k, lse, mask, scale, interpret=True)
    assert calls == [("dsa_probs", (2, 2, 2))]


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("h,hkv,a_trip", [(8, 2, 8), (6, 2, 2), (32, 4, 8)])
def test_the_gauge_has_the_heads_a_trip(interpret, h, hkv, a_trip,
                                        small_tiles):
    q, k, lse, mask, scale = _grouped_attention(h, hkv)
    dsa.head_summed_probs(q, k, lse, mask, scale, interpret=interpret)
    assert trace.gauges()["dsa.probs_heads_a_trip"] == (
        a_trip if interpret else 0)


@pytest.mark.parametrize("s,h,hkv,d,tiles", [
    (16384, 32, 4, 128, (512, 512)),      # keye-vl: q 4 MiB a buffer
    (8192, 32, 32, 192, (512, 512)),      # dots3: q and k 8 MiB each
    (8192, 64, 64, 192, (256, 256)),      # twice the heads
    (8192, 128, 128, 192, (128, 128)),
    (256, 4, 4, 32, (256, 256)),
    (200, 4, 2, 32, (200, 200)),          # 128 does not divide it
])
def test_probs_tiles_follow_the_shapes(s, h, hkv, d, tiles):
    assert dsa._probs_tiles(s, h, hkv, d, 2) == tiles


def test_head_summed_probs_are_constants(small_tiles):
    q, k, lse, mask, scale = _attention()
    g = jax.grad(lambda q: jnp.sum(
        dsa.head_summed_probs(q, k, lse, mask, scale) ** 2))(q)
    assert not np.asarray(g).any()


def test_indexer_loss_is_the_kl_and_moves_the_scores_alone():
    q, k, lse, mask, scale = _attention()
    probs = dsa.head_summed_probs(q, k, lse, mask, scale)
    scores = jax.random.normal(jax.random.key(6), (2, S, S))
    seen = np.asarray(mask) != 0
    target = np.asarray(probs) / np.asarray(probs).sum(-1, keepdims=True)
    logits = np.where(seen, np.asarray(scores), -np.inf)
    logq = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    with np.errstate(divide="ignore", invalid="ignore"):
        kl = np.where(seen & (target > 0),
                      target * (np.log(target) - logq), 0.0)
    np.testing.assert_allclose(
        dsa.indexer_loss(scores, probs, mask), kl.sum(), rtol=1e-4)
    d_scores, d_probs = jax.grad(
        lambda s, p: dsa.indexer_loss(s, p, mask), argnums=(0, 1))(
            scores, probs)
    assert not np.asarray(d_probs).any()
    # softmax over the selection less the target, nothing outside it
    want = np.where(seen, np.exp(logq) - target, 0.0)
    np.testing.assert_allclose(d_scores, want, atol=1e-6)


def test_the_kl_of_a_distribution_with_itself_is_zero():
    q, k, lse, mask, scale = _attention(h=1)
    probs = dsa.head_summed_probs(q, k, lse, mask, scale)
    scores = jnp.where(mask != 0, jnp.log(jnp.maximum(probs, 1e-30)), 0.0)
    assert abs(float(dsa.indexer_loss(scores, probs, mask))) < 1e-2


# -- the loss's own backward rule ---------------------------------------------

def _parents_indexer_loss(scores, probs, mask):
    """``indexer_loss`` as it stood before it formed its gradient in the
    forward (PR 40's body, under plain autodiff): the oracle."""
    seen = mask != 0
    target = lax.stop_gradient(probs)
    target = target / jnp.sum(target, axis=-1, keepdims=True)
    logq = jax.nn.log_softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1)
    terms = jnp.where(
        seen & (target > 0.0),
        target * (jnp.log(jnp.where(target > 0.0, target, 1.0)) - logq), 0.0)
    return jnp.sum(terms)


def _loss_operands(kind):
    """``(scores, probs, mask)``: the file's attention, or 64 positions
    under a top-40 (so 39 rows hold fewer than ``topk`` causal keys) with
    every third selected pair's target zero and some rows' targets zero
    on all but the diagonal."""
    if kind == "attention":
        q, k, lse, mask, scale = _attention()
        return (jax.random.normal(jax.random.key(6), (2, S, S)),
                dsa.head_summed_probs(q, k, lse, mask, scale), mask)
    s = 64
    ks, kp = jax.random.split(jax.random.key(7))
    scores = 3.0 * jax.random.normal(ks, (2, s, s))
    mask = dsa.selection_mask(scores, 40)
    rows, cols = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    probs = jnp.where(
        (mask != 0) & ((rows + cols) % 3 != 0),
        jax.random.uniform(kp, (2, s, s), minval=0.1), 0.0)
    probs = jnp.where((rows % 5 == 0) & (rows != cols), 0.0, probs)
    return scores, probs.at[:, jnp.arange(s), jnp.arange(s)].set(0.5), mask


@pytest.mark.parametrize("kind", ["attention", "partly_zero_short_rows"])
def test_the_loss_forms_in_its_forward_the_gradient_autodiff_gave(kind):
    scores, probs, mask = _loss_operands(kind)
    if kind != "attention":
        held = np.asarray((probs > 0) & (mask != 0)).sum(-1)
        seen = np.asarray(mask != 0).sum(-1)
        assert (seen < 40).any() and (held < seen).any() and (held == 1).any()
    got, d_scores = jax.value_and_grad(dsa.indexer_loss)(scores, probs, mask)
    want, want_d = jax.value_and_grad(_parents_indexer_loss)(
        scores, probs, mask)
    assert float(want) > 1.0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert np.isfinite(np.asarray(d_scores)).all()
    largest = float(jnp.max(jnp.abs(want_d)))
    assert float(jnp.max(jnp.abs(d_scores - want_d))) <= 1e-5 * largest
    assert not np.asarray(jnp.where(mask != 0, 0.0, d_scores)).any()
    d_probs = jax.grad(dsa.indexer_loss, argnums=1)(scores, probs, mask)
    assert d_probs.shape == probs.shape and not np.asarray(d_probs).any()


@pytest.mark.parametrize("cotangent", [1.0, -0.37, 2.5e-4])
def test_a_cotangent_scales_the_kept_gradient(cotangent):
    scores, probs, mask = _loss_operands("partly_zero_short_rows")
    loss, kept = dsa._indexer_loss_fwd(scores, probs, mask)
    np.testing.assert_allclose(
        loss, dsa.indexer_loss(scores, probs, mask), rtol=1e-6)
    _, vjp = jax.vjp(lambda s: dsa.indexer_loss(s, probs, mask), scores)
    got, = vjp(jnp.float32(cotangent))
    np.testing.assert_array_equal(got, jnp.float32(cotangent) * kept)
    want = jax.grad(lambda s: cotangent * _parents_indexer_loss(
        s, probs, mask))(scores)
    np.testing.assert_allclose(
        got, want, atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_the_kept_gradient_carries_its_name():
    scores, probs, mask = _loss_operands("partly_zero_short_rows")
    text = str(jax.make_jaxpr(jax.grad(dsa.indexer_loss))(
        scores, probs, mask))
    assert text.count(f"name[name={dsa.LOSS_GRAD}]") == 1


# -- grouped heads: 32 query heads on 4 key heads, 16 index heads of 64 ---------

def test_heads_that_do_not_share_the_key_heads_evenly_are_refused():
    q, k, lse, mask, scale = _grouped_attention()
    with pytest.raises(ValueError, match="evenly"):
        dsa.head_summed_probs(q[:, :, :7], k, lse[:, :7], mask, scale)


def test_index_scores_at_sixteen_heads_of_64(small_tiles):
    """The published indexer's shape a head (half a lane tile, half the
    MXU's depth): the kernels against the XLA form, values and the three
    gradients."""
    q, k, w = _operands(b=1, h=16, d=64, seed=2)
    g = jax.random.normal(jax.random.key(8), (1, S, S)) * _causal()

    def both(interpret):
        return jax.value_and_grad(
            lambda q, k, w: jnp.sum(dsa.index_scores(
                q, k, w, interpret=interpret) * g), argnums=(0, 1, 2))(
                    q, k, w)

    (got, got_grads), (want, want_grads) = both(True), both(False)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    np.testing.assert_allclose(
        jnp.where(_causal(), dsa.index_scores(q, k, w, interpret=True), 0.0),
        jnp.where(_causal(), _plain_scores(q, k, w), 0.0),
        atol=2e-3, rtol=2e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=2e-4)


@pytest.mark.parametrize("interpret", [False, True])
def test_selected_attention_is_its_pieces_in_order(interpret, small_tiles):
    """The one function both families call: index scores -> mask ->
    flash over the selection -> probabilities -> KL."""
    from dlrover_tpu.ops.attention import flash_attention

    q, k, _, _, scale = _grouped_attention()
    v = jax.random.normal(jax.random.key(3), k.shape)
    iq, ik, iw = _operands(h=4, d=32, seed=4)
    out, l_i, mask, scores = dsa.selected_attention(
        q, k, v, iq, ik, iw, 40, scale, interpret=interpret)
    want_scores = dsa.index_scores(iq, ik, iw, interpret=interpret)
    want_mask = dsa.selection_mask(want_scores, 40)
    want_out, lse = flash_attention(
        q, k, v, scale=scale, select=want_mask, interpret=interpret,
        return_lse=True)
    np.testing.assert_array_equal(mask, want_mask)
    np.testing.assert_allclose(out, want_out, atol=1e-6)
    np.testing.assert_allclose(l_i, dsa.indexer_loss(
        want_scores, dsa.head_summed_probs(
            q, k, lse, want_mask, scale, interpret=interpret), want_mask),
        rtol=1e-6)
    np.testing.assert_array_equal(scores, want_scores)
    text = str(jax.make_jaxpr(lambda *a: dsa.selected_attention(
        *a, 40, scale)[0])(q, k, v, iq, ik, iw))
    assert text.count(f"name[name={dsa.SELECT}]") == 1
