"""``ops/lightning.py`` (linear attention with a fixed decay a head),
``ops/blocksel.py`` (a key-value group's choice of blocks) and the flash
kernels' selection by blocks (``ops/attention.py`` ``select_block=``), on
the CPU: XLA's forms and the Pallas kernels in interpret mode against the
definitions written out plainly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import stack
from dlrover_tpu.ops import attention, blocksel, lightning

# the cell's largest and smallest slopes (published layers 1 and 3), and
# no decay at all
SLOPES = (0.813779133403231, 0.0035282648689516126, 0.0)


def _qkv(key, b, s, h, d, kvh=None):
    ks = jax.random.split(jax.random.key(key), 4)
    kvh = h if kvh is None else kvh
    return (jax.random.normal(ks[0], (b, s, h, d)) * 0.5,
            jax.random.normal(ks[1], (b, s, kvh, d)) * 0.5,
            jax.random.normal(ks[2], (b, s, kvh, d)) * 0.5,
            jax.random.normal(ks[3], (b, s, h, d)))


# ---------------------------------------------------------------------------
# The lightning rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("chunk,s", [(16, 64), (32, 32)],
                         ids=["four_chunks", "one_chunk"])
def test_the_chunked_rule_is_the_recurrence(interpret, chunk, s):
    """Forward and gradients, at the largest and the smallest slope and
    with none, across chunk edges."""
    q, k, v, ct = _qkv(0, 2, s, 3, 16)
    slopes = jnp.asarray(SLOPES, jnp.float32)
    want = lightning.recurrence(q, k, v, slopes)

    def rule(q, k, v):
        return lightning.lightning_attention(
            q, k, v, slopes, chunk=chunk, interpret=interpret)

    np.testing.assert_allclose(rule(q, k, v), want, atol=5e-5)
    got = jax.grad(lambda *a: jnp.sum(rule(*a) * ct), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(
        lightning.recurrence(*a, slopes) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_a_chunk_edge_hands_the_state_on():
    """A token right after an edge reads what the chunks before it wrote:
    with keys only in the first chunk, every later output is the decayed
    first state."""
    b, s, h, d, chunk = 1, 48, 2, 8, 16
    q, k, v, _ = _qkv(1, b, s, h, d)
    k = k.at[:, chunk:].set(0.0)
    slopes = jnp.asarray([0.3, 0.01], jnp.float32)
    want = lightning.recurrence(q, k, v, slopes)
    assert float(jnp.abs(want[:, chunk:]).max()) > 1e-3
    for interpret in (False, True):
        got = lightning.lightning_attention(q, k, v, slopes, chunk=chunk,
                                            interpret=interpret)
        np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("slope", [0.0, 0.0035, 0.8138, 50.0])
def test_every_decay_factor_is_at_most_one(slope):
    """No bound on the slope: the tables hold ``exp`` of nothing above 0,
    so a fast head underflows to 0 and nothing overflows."""
    tables = lightning.decay_tables(jnp.asarray([slope], jnp.float32), 256)
    for t in tables:
        assert np.isfinite(np.asarray(t)).all()
        assert float(t.max()) <= 1.0 and float(t.min()) >= 0.0
    D = np.asarray(tables[0][0])
    assert (np.triu(D, 1) == 0).all() and (np.diag(D) == 1).all()


def test_the_slopes_take_no_gradient_and_bf16_runs():
    q, k, v, ct = (a.astype(jnp.bfloat16) for a in _qkv(2, 1, 32, 2, 16))
    slopes = jnp.asarray([0.5, 0.01], jnp.float32)
    for interpret in (False, True):
        out, vjp = jax.vjp(lambda q, k, v, sl: lightning.lightning_attention(
            q, k, v, sl, chunk=16, interpret=interpret), q, k, v, slopes)
        assert out.dtype == jnp.bfloat16
        want = lightning.recurrence(q, k, v, slopes)
        assert float(jnp.abs(out.astype(jnp.float32) - want).max()) < 0.05
        if interpret:
            assert float(jnp.abs(vjp(ct)[3]).max()) == 0.0


def test_a_chunk_that_does_not_divide_is_refused():
    q, k, v, _ = _qkv(3, 1, 24, 1, 8)
    with pytest.raises(ValueError, match="does not divide"):
        lightning.lightning_attention(q, k, v, jnp.zeros((1,)), chunk=16)


def test_a_block_that_keeps_the_rules_names_saves_them():
    """``lightning.KEPT`` through ``stack.recompute``: the output and the
    states a chunk are what a keeping block saves of the rule."""
    q, k, v, _ = _qkv(4, 1, 32, 2, 8)
    slopes = jnp.asarray([0.2, 0.02], jnp.float32)

    def fn(q, k, v):
        # squared: what follows the rule reads its output in the backward
        return jnp.square(lightning.lightning_attention(
            q, k, v, slopes, chunk=16, interpret=True))

    def shapes(saved):
        return sorted(tuple(a.shape) for a, _ in saved if a.ndim > 1)

    kept = []
    saved = saved_residuals(
        stack.recompute(fn, True, lightning.KEPT, kept.append), q, k, v)
    # q, k, v (arguments), the rule's output and its states a chunk
    assert shapes(saved) == [(1, 2, 2, 8, 8)] + [(1, 32, 2, 8)] * 4, saved
    assert set(kept) == set(lightning.KEPT)
    nothing = saved_residuals(stack.recompute(fn, True), q, k, v)
    assert shapes(nothing) == [(1, 32, 2, 8)] * 3, nothing


# ---------------------------------------------------------------------------
# The choice of blocks
# ---------------------------------------------------------------------------

SIZES = dict(block=8, kernel=4, stride=2)


def _naive_scores(q, k, block, kernel, stride):
    """The module docstring's ``B``, loop by loop."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, s, h, d = q.shape
    g = k.shape[2]
    n = (s - kernel) // stride + 1
    pooled = np.stack([k[:, stride * j:stride * j + kernel].mean(1)
                       for j in range(n)], 1)
    out = np.zeros((b, g, s, s // block))
    for bi in range(b):
        for gi in range(g):
            for i in range(s):
                seen = [j for j in range(n) if stride * j + kernel - 1 <= i]
                a = np.zeros(n)
                for r in range(h // g):
                    if not seen:
                        break
                    logits = pooled[bi, seen, gi] @ q[
                        bi, i, gi * (h // g) + r] * d ** -0.5
                    p = np.exp(logits - logits.max())
                    a[seen] += p / p.sum()
                for bb in range(s // block):
                    out[bi, gi, i, bb] = max(
                        a[j] for j in range(n)
                        if stride * j < block * bb + block
                        and stride * j + kernel > block * bb)
    return pooled, out


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_block_scores_are_the_pooled_softmaxes_summed_a_group(interpret):
    q, k, _, _ = _qkv(5, 2, 64, 4, 16, kvh=2)
    pooled, want = _naive_scores(q, k, **SIZES)
    c = blocksel.pooled_keys(k, SIZES["kernel"], SIZES["stride"])
    np.testing.assert_allclose(c, pooled, atol=1e-6)
    got = blocksel.block_scores(q, c, scale=16 ** -0.5, interpret=interpret,
                                **SIZES)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # a query with no whole window behind it scores nothing
    assert float(jnp.abs(got[:, :, :SIZES["kernel"] - 1]).max()) == 0.0
    # the two groups score apart
    assert float(jnp.abs(got[:, 0] - got[:, 1]).max()) > 1e-3


def test_windows_that_are_not_two_strides_go_to_xla():
    q, k, _, _ = _qkv(6, 1, 32, 2, 8, kvh=1)
    c = blocksel.pooled_keys(k, 6, 2)
    got = blocksel.block_scores(q, c, block=8, kernel=6, stride=2,
                                scale=1.0, interpret=True)
    _, want = _naive_scores(q * 8 ** 0.5, k, 8, 6, 2)
    np.testing.assert_allclose(got, want, atol=1e-6)


def _naive_choice(scores, block, topk, init_blocks, window):
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, bool)
    s, nb = scores.shape[-2:]
    for idx in np.ndindex(scores.shape[:-2]):
        for i in range(s):
            eligible = [b for b in range(nb) if block * b <= i]
            forced = [b for b in eligible if b < init_blocks
                      or block * b + block - 1 >= i - (window - 1)]
            rest = sorted((b for b in eligible if b not in forced),
                          key=lambda b: (-scores[idx][i, b], b))
            for b in forced + rest[:max(topk - len(forced), 0)]:
                out[idx][i, b] = True
    return out


@pytest.mark.parametrize("ties", [False, True], ids=["scores", "ties"])
def test_the_choice_is_the_forced_blocks_and_the_best_of_the_rest(ties):
    b, g, s, block, topk, init, window = 2, 2, 128, 8, 6, 1, 16
    scores = jax.random.uniform(jax.random.key(7), (b, g, s, s // block))
    if ties:    # few distinct values: the cut falls inside a run of equals
        scores = jnp.round(scores * 3) / 3
    got = np.asarray(blocksel.pick_blocks(
        scores, block=block, topk=topk, init_blocks=init, window=window))
    assert got.dtype == np.int8
    want = _naive_choice(scores, block, topk, init, window)
    np.testing.assert_array_equal(got != 0, want)
    eligible, forced = (np.asarray(a) for a in blocksel.forced_blocks(
        s, block, init, window))
    # forced blocks always in, nothing above the diagonal, exactly topk
    # wherever as many blocks exist
    assert (got[..., forced] == 1).all() and (got[..., ~eligible] == 0).all()
    np.testing.assert_array_equal(
        got.sum(-1), np.broadcast_to(
            np.minimum(eligible.sum(-1), topk), got.shape[:-1]))
    # the groups choose apart
    assert (got[:, 0] != got[:, 1]).any()


def test_ties_go_to_the_lower_block():
    s, block = 64, 8
    scores = jnp.ones((1, 1, s, s // block))
    got = np.asarray(blocksel.pick_blocks(
        scores, block=block, topk=4, init_blocks=1, window=8))[0, 0]
    # the last query: block 0 and its own are forced, two more by score,
    # all equal: blocks 1 and 2
    assert got[-1].tolist() == [1, 1, 1, 0, 0, 0, 0, 1]


def test_a_topk_of_every_block_is_causal_attention_by_blocks():
    scores = jnp.zeros((1, 2, 32, 4))
    got = np.asarray(blocksel.pick_blocks(
        scores, block=8, topk=4, init_blocks=1, window=8))
    want = np.asarray(blocksel.forced_blocks(32, 8, 0, 0)[0])
    np.testing.assert_array_equal(got[0, 0] != 0, want)


def test_the_pairs_a_choice_selects_do_not_depend_on_the_weights():
    # the cell: 16384 positions, 64 blocks of 64 a query
    assert blocksel.selected_pairs(16384, 64, 64) == 58335232
    assert 16384 * 16385 // 2 == 134225920
    scores = jax.random.uniform(jax.random.key(8), (1, 2, 128, 16))
    chosen = blocksel.pick_blocks(scores, block=8, topk=6, init_blocks=1,
                                  window=16)
    seen = attention.select_by_keys(chosen, 8)
    assert int(seen[0, 0].sum()) == int(seen[0, 1].sum()) == (
        blocksel.selected_pairs(128, 8, 6))


def test_live_tiles_counts_the_visits_some_row_chose():
    s, block = 64, 8
    eligible = np.asarray(blocksel.forced_blocks(s, block, 0, 0)[0])
    everything = jnp.asarray(eligible[None, None], jnp.int8)
    # 16 x 16 tiles over 64 positions: 4 + 3 + 2 + 1 causal visits
    assert int(blocksel.live_tiles(everything, block, 16, 16)) == 10
    # only the diagonal block of every query: the four diagonal tiles
    own = np.zeros((1, 2, s, s // block), np.int8)
    own[:, :, np.arange(s), np.arange(s) // block] = 1
    assert int(blocksel.live_tiles(jnp.asarray(own), block, 16, 16)) == 4
    # one row of one group choosing block 0 makes its tile live
    own[0, 1, s - 1, 0] = 1
    assert int(blocksel.live_tiles(jnp.asarray(own), block, 16, 16)) == 5


# ---------------------------------------------------------------------------
# The flash kernels under a selection by blocks
# ---------------------------------------------------------------------------

def _choice(key, b, g, s, block, topk=6):
    scores = jax.random.uniform(jax.random.key(key), (b, g, s, s // block))
    return blocksel.pick_blocks(scores, block=block, topk=topk,
                                init_blocks=1, window=2 * block)


@pytest.mark.parametrize("tiles,h,g", [
    ((32, 32), 4, 2), ((16, 64), 2, 2), ((64, 16), 4, 2),
    ((128, 128), 2, 2)], ids=["square_group2", "wide_k_group1",
                              "wide_q_group2", "one_tile_group1"])
def test_the_blk_kernels_are_attention_under_the_widened_mask(tiles, h, g):
    b, s, d, block = 2, 128, 16, 8
    q, k, v, ct = _qkv(9, b, s, h, d, kvh=g)
    chosen = _choice(10, b, g, s, block)

    def ref(q, k, v):
        return attention.mha_reference_with_lse(
            q, k, v, causal=True,
            select=attention.select_by_keys(chosen, block))[0]

    def kernels(q, k, v):
        return attention.flash_attention(
            q, k, v, select=chosen, select_block=block, interpret=True,
            block_q=tiles[0], block_k=tiles[1])

    np.testing.assert_allclose(kernels(q, k, v), ref(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * ct), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5)


def test_over_a_mesh_a_device_reads_its_rows_and_its_heads_choice():
    """Over dp x tp the kernels run under ``shard_map``: a device holds
    its batch rows, its query heads, their key-value heads and **those
    heads' choice** (the selection by blocks is cut over tp with k and
    v; the selection by keys, one a row, is not)."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=2).resolve(4), devices=jax.devices()[:4])
    b, s, h, g, d, block = 2, 64, 4, 2, 16, 8
    q, k, v, ct = _qkv(15, b, s, h, d, kvh=g)
    chosen = _choice(16, b, g, s, block, topk=5)
    assert bool(jnp.any(chosen[:, 0] != chosen[:, 1]))     # they differ

    def attend(mesh):
        return lambda q, k, v: attention.flash_attention(
            q, k, v, select=chosen, select_block=block, interpret=True,
            block_q=32, block_k=32, mesh=mesh)

    np.testing.assert_allclose(jax.jit(attend(mesh))(q, k, v),
                               attend(None)(q, k, v), atol=2e-6)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(mesh)(*a) * ct), (0, 1, 2)))(q, k, v)
    want = jax.grad(
        lambda *a: jnp.sum(attend(None)(*a) * ct), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-5)


def test_off_the_tpu_a_selection_by_blocks_takes_the_reference():
    b, s, h, g, d, block = 1, 64, 4, 2, 8, 8
    q, k, v, _ = _qkv(11, b, s, h, d, kvh=g)
    chosen = _choice(12, b, g, s, block, topk=3)
    out, lse = attention.flash_attention(
        q, k, v, select=chosen, select_block=block, return_lse=True)
    want, want_lse = attention.mha_reference_with_lse(
        q, k, v, causal=True, select=attention.select_by_keys(chosen, block))
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(lse, want_lse)
    # the groups' masks differ, and so do the heads that read them
    whole = attention.mha_reference(q, k, v, causal=True)
    assert float(jnp.abs(out - whole).max()) > 1e-3


def test_every_block_chosen_is_plain_causal_attention():
    b, s, h, g, d, block = 1, 64, 4, 2, 8, 8
    q, k, v, _ = _qkv(13, b, s, h, d, kvh=g)
    everything = jnp.broadcast_to(
        blocksel.forced_blocks(s, block, 0, 0)[0], (b, g, s, s // block)
    ).astype(jnp.int8)
    got = attention.flash_attention(
        q, k, v, select=everything, select_block=block, interpret=True,
        block_q=32, block_k=32)
    want = attention.flash_attention(q, k, v, causal=True, interpret=True,
                                     block_q=32, block_k=32)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("select,select_block,why", [
    (jnp.zeros((1, 64, 64), jnp.int8), 8, "kv heads"),
    (jnp.zeros((1, 2, 64, 8), jnp.int8), 0, "batch, seq, seq"),
    (jnp.zeros((1, 2, 64, 8), jnp.float32), 8, "int8"),
    (jnp.zeros((1, 2, 64, 4), jnp.int8), 8, "seq / select_block"),
])
def test_a_selection_of_the_wrong_form_is_refused_in_words(
        select, select_block, why):
    q, k, v, _ = _qkv(14, 1, 64, 4, 8, kvh=2)
    with pytest.raises(ValueError, match=why):
        attention.flash_attention(q, k, v, select=select,
                                  select_block=select_block)


def test_a_k_block_that_is_no_whole_blocks_is_refused():
    q, k, v, ct = _qkv(15, 1, 96, 2, 8, kvh=2)
    chosen = _choice(16, 1, 2, 96, 32, topk=2)
    with pytest.raises(ValueError, match="no whole blocks"):
        jax.grad(lambda q: jnp.sum(attention.flash_attention(
            q, k, v, select=chosen, select_block=32, interpret=True,
            block_q=48, block_k=48) * ct))(q)


def test_dots3s_selection_by_keys_is_what_it_was():
    """The ``(b, s, s)`` mask for every head of a row: the ``_sel``
    kernels, the same numbers as the reference under that mask."""
    b, s, h, g, d = 2, 64, 4, 2, 16
    q, k, v, _ = _qkv(17, b, s, h, d, kvh=g)
    from dlrover_tpu.ops import dsa

    mask = dsa.selection_mask(
        jax.random.normal(jax.random.key(18), (b, s, s)), 12)

    def kernels(q, k, v):
        return attention.flash_attention(q, k, v, select=mask, interpret=True,
                                         block_q=32, block_k=32)

    def ref(q, k, v):
        return attention.mha_reference_with_lse(
            q, k, v, causal=True, select=mask)[0]

    # (its gradients: tests/test_attention_select.py)
    np.testing.assert_allclose(kernels(q, k, v), ref(q, k, v), atol=2e-6)
    text = str(jax.make_jaxpr(kernels)(q, k, v))
    assert "attention_fwd_sel" in text and "_blk" not in text
