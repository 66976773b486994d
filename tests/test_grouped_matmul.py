"""The grouped-matmul Pallas kernels (ops/grouped_matmul.py) in
interpreter mode against ``lax.ragged_dot`` and its autodiff: forward,
d-lhs and d-rhs, over group sizes that straddle row tiles, leave groups
empty, pile every row on one group, or leave a tail of rows in none
(which the forward and d-lhs walks zero without reading or multiplying,
or, on the caller's word that nothing reads them, do not visit at all)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dlrover_tpu.ops import grouped_matmul as gm

M, K, N, G = 512, 128, 256, 6

GROUPS = {
    "even": [96, 80, 88, 72, 96, 80],          # straddles every tile edge
    "aligned": [256, 0, 256, 0, 0, 0],         # whole tiles, empty groups
    "one_takes_all": [0, 0, 512, 0, 0, 0],
    "tail": [100, 3, 0, 61, 40, 9],            # 299 rows of no group
    "all_tail": [0, 0, 0, 0, 0, 0],
    "tail_on_a_tile_edge": [100, 28, 0, 64, 60, 4],   # 256 live rows
    "tiny_groups": [1, 2, 3, 500, 5, 1],
    "tail_inside_a_tile": [120, 0, 70, 30, 80, 30],   # 330 = 2 tiles + 74
}


def _operands(dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    lhs = jax.random.normal(k1, (M, K), dtype)
    rhs = jax.random.normal(k2, (G, K, N), dtype) / np.sqrt(K)
    return lhs, rhs


def _reference(lhs, rhs, sizes):
    return lax.ragged_dot(lhs, rhs, sizes, precision=lax.Precision.HIGHEST)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_forward_matches_ragged_dot(name):
    lhs, rhs = _operands()
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = gm.grouped_matmul(lhs, rhs, sizes, interpret=True)
    want = _reference(lhs, rhs, sizes)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    total = int(sizes.sum())
    assert not np.asarray(out[total:]).any()   # rows of no group: zero


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_gradients_match_ragged_dot(name):
    lhs, rhs = _operands(seed=1)
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    weight = jax.random.normal(jax.random.key(2), (M, N))

    def loss(fn):
        return lambda lhs, rhs: jnp.sum(fn(lhs, rhs, sizes) * weight)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda a, b, s: gm.grouped_matmul(
            a, b, s, interpret=True)), argnums=(0, 1))(lhs, rhs)
        want = jax.grad(loss(_reference), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    # an empty group's block is written, and is zero
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)


def test_bf16_operands_accumulate_in_f32():
    lhs, rhs = _operands(jnp.bfloat16)
    sizes = jnp.asarray(GROUPS["even"], jnp.int32)
    out = gm.grouped_matmul(lhs, rhs, sizes, interpret=True)
    assert out.dtype == jnp.bfloat16
    want = _reference(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    # one rounding of an f32 sum to bf16: 2^-8 of the value
    np.testing.assert_allclose(out.astype(jnp.float32), want,
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_visits_cover_every_row_once(name):
    sizes = np.asarray(GROUPS[name])
    block = 128
    offsets, groups, tiles, num = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes, jnp.int32), M, block, tail=True, empty=False))
    num = int(num[0])
    assert len(groups) == M // block + G + 1 and 0 < num <= len(groups)
    seen = np.zeros(M, int)
    for grp, tile in zip(groups[:num], tiles[:num]):
        lo = max(offsets[grp], tile * block)
        hi = min(offsets[grp + 1], (tile + 1) * block)
        assert hi > lo           # no visit without rows of its group
        seen[lo:hi] += 1
    assert (seen == 1).all()
    assert (np.diff(tiles[:num]) >= 0).all()      # in row order
    # the d-rhs walk: no tail, one visit for an empty group
    _, groups, _, num = (np.asarray(a) for a in gm._visits(
        jnp.asarray(sizes, jnp.int32), M, block, tail=False, empty=True))
    assert set(groups[: int(num[0])]) == set(range(G))


TAILS = ["tail", "all_tail", "tail_on_a_tile_edge", "tail_inside_a_tile"]
NO_TAIL = {   # the (group, tile) visits at 128 rows a tile, as PR 27 walked
    "even": [(0, 0), (1, 0), (1, 1), (2, 1), (2, 2), (3, 2), (4, 2),
             (4, 3), (5, 3)],
    "aligned": [(0, 0), (0, 1), (2, 2), (2, 3)],
    "one_takes_all": [(2, 0), (2, 1), (2, 2), (2, 3)],
}


@pytest.mark.parametrize("name", TAILS)
def test_tail_rows_are_zeroed_unread(name):
    # NaN in the rows of no group, operand and cotangent: a walk that
    # multiplied them into a kept row, or passed them on, would show it
    lhs, rhs = _operands(seed=3)
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    total = int(sizes.sum())
    ct = jax.random.normal(jax.random.key(4), (M, N))
    poison = lambda a: a.at[total:].set(jnp.nan)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda a: gm.grouped_matmul(a, rhs, sizes, interpret=True),
            poison(lhs))
        d_lhs, = vjp(poison(ct))
        want, want_vjp = jax.vjp(lambda a: _reference(a, rhs, sizes), lhs)
        want_d_lhs, = want_vjp(ct)
    for got, ref in ((out, want), (d_lhs, want_d_lhs)):
        assert not np.asarray(got[total:]).any()
        np.testing.assert_allclose(got[:total], ref[:total],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_tail_visits_ask_for_no_operand_block(name):
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    block = 128
    offsets, groups, tiles, num = gm._visits(
        sizes, M, block, tail=True, empty=False)
    lhs_tiles = np.asarray(gm._lhs_tile(offsets, tiles, G, block))
    groups, tiles, num = np.asarray(groups), np.asarray(tiles), int(num[0])
    real = groups[:num] < G                 # the visits that form a product
    live = int(real.sum())
    assert real[:live].all()                # the tail's come last
    np.testing.assert_array_equal(lhs_tiles[:live], tiles[:live])
    # past the last real visit the lhs block never changes, whatever
    # output tile the visit zeroes (rhs's stays on the last group's panel)
    assert (lhs_tiles[live:] == (lhs_tiles[live - 1] if live else 0)).all()
    _, _, _, no_tail = gm._visits(sizes, M, block, tail=False, empty=False)
    assert live == int(no_tail[0])


@pytest.mark.parametrize("name", sorted(NO_TAIL))
def test_walk_without_a_tail_is_what_it_was(name):
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    offsets, groups, tiles, num = gm._visits(
        sizes, M, 128, tail=True, empty=False)
    num = int(num[0])
    walk = list(zip(np.asarray(groups)[:num].tolist(),
                    np.asarray(tiles)[:num].tolist()))
    assert walk == NO_TAIL[name]            # no visit of group G
    np.testing.assert_array_equal(gm._lhs_tile(offsets, tiles, G, 128), tiles)


def test_tiles_come_from_the_shapes():
    # the listed cell: 65536 rows between 2048 and 1024, bf16
    assert gm.choose_tiles(65536, 2048, 1024, jnp.bfloat16) == (
        256, 1024, 1024)
    assert gm.choose_tiles(65536, 1024, 2048, jnp.bfloat16) == (
        256, 1024, 1024)
    # Mixtral's expert: 8192 rows between 4096 and 14336 (= 112 x 128)
    bm, bn, bk = gm.choose_tiles(8192, 4096, 14336, jnp.bfloat16)
    assert 8192 % bm == 0 and 14336 % bn == 0 and 4096 % bk == 0
    assert bn % 128 == 0 and bk % 128 == 0
    # widths that are no multiple of 128 go to lax.ragged_dot
    assert gm.choose_tiles(96, 64, 128, jnp.float32) is None


def test_off_tpu_the_path_is_ragged_dot():
    lhs, rhs = _operands()
    sizes = jnp.asarray(GROUPS["tail"], jnp.int32)
    out = gm.grouped_matmul(lhs, rhs, sizes)     # CPU, no interpret
    np.testing.assert_allclose(out, _reference(lhs, rhs, sizes),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the walk bound by the live count (``tail_unread``): no visit to a tile
# of the tail, whose rows of the results stay unwritten (NaN in
# interpreter mode)
# ---------------------------------------------------------------------------

def _vjps(fn, sizes, lhs, rhs, ct):
    out, vjp = jax.vjp(lambda a, b: fn(a, b, sizes), lhs, rhs)
    return (out,) + vjp(ct)


def _bounded(lhs, rhs, sizes):
    return gm.grouped_matmul(lhs, rhs, sizes, tail_unread=True,
                             interpret=True)


@pytest.mark.parametrize("poisoned", [False, True])
@pytest.mark.parametrize("name", TAILS)
def test_bounded_walk_matches_ragged_dot_below_the_count(name, poisoned):
    """Forward and d-lhs below the count and the whole of d-rhs; with
    the operands' and the cotangent's rows past the count NaN, which is
    what an unwritten row of a producer may hold, the same."""
    lhs, rhs = _operands(seed=5)
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    total = int(sizes.sum())
    ct = jax.random.normal(jax.random.key(6), (M, N))
    poison = (lambda a: a.at[total:].set(jnp.nan)) if poisoned else (
        lambda a: a)
    with jax.default_matmul_precision("highest"):
        got = _vjps(_bounded, sizes, poison(lhs), rhs, poison(ct))
        want = _vjps(_reference, sizes, lhs, rhs, ct)
    for g, w in zip(got[:2], (want[0], want[1])):
        assert np.isfinite(np.asarray(g[:total])).all()
        np.testing.assert_allclose(g[:total], w[:total],
                                   rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got[2])).all()
    np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_bounded_walk_names_no_tile_past_the_live_rows(name):
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    block = 128
    total = int(sizes.sum())
    offsets, groups, tiles, num = (np.asarray(a) for a in gm._visits(
        sizes, M, block, tail=False, empty=False))
    num = int(num[0])
    assert (num == 0) == (total == 0)
    assert (groups[:num] < G).all()
    assert (tiles[:num] <= max(total - 1, 0) // block).all()
    # every entry, real visit or not, names a block that exists
    assert ((0 <= groups) & (groups < G)).all()
    assert ((0 <= tiles) & (tiles < M // block)).all()
    # the live rows are covered once, as the zeroing walk covers them
    seen = np.zeros(M, int)
    for grp, tile in zip(groups[:num], tiles[:num]):
        seen[max(offsets[grp], tile * block):
             min(offsets[grp + 1], (tile + 1) * block)] += 1
    assert (seen[:total] == 1).all() and not seen[total:].any()
    assert int(gm._grid_steps((offsets, groups, tiles,
                               jnp.asarray([num])))) == max(num, 1)


@pytest.mark.parametrize("tail_unread", [False, True])
@pytest.mark.parametrize("name", ["even", "tail", "all_tail",
                                  "tail_inside_a_tile"])
def test_products_that_share_their_rows_sum_d_lhs_in_place(
        name, tail_unread):
    lhs, rhs = _operands(seed=7)
    _, other = _operands(seed=8)
    sizes = jnp.asarray(GROUPS[name], jnp.int32)
    total = int(sizes.sum())
    cts = [jax.random.normal(jax.random.key(9 + i), (M, N)) for i in (0, 1)]

    def loss(fn):
        def inner(lhs, a, b):
            outs = fn(lhs, (a, b), sizes)
            below = (jnp.arange(M) < total)[:, None]
            return sum(jnp.sum(jnp.where(below, o * ct, 0.0))
                       for o, ct in zip(outs, cts))
        return inner

    pair = lambda l, ws, s: gm.grouped_matmuls(
        l, ws, s, tail_unread=tail_unread, interpret=True)
    ref = lambda l, ws, s: tuple(_reference(l, w, s) for w in ws)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(pair), argnums=(0, 1, 2))(lhs, rhs, other)
        want = jax.grad(loss(ref), argnums=(0, 1, 2))(lhs, rhs, other)
    edge = M if not tail_unread else total
    np.testing.assert_allclose(got[0][:edge], want[0][:edge],
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_tiles_count_the_tile_a_d_lhs_adds_onto():
    # up's d-lhs fetches gate's result, one more (block_m, block_k) tile
    # a visit: counted, it leaves the cells the tiles they had (PR 40's)
    for (m, k, n), want in (((98304, 2560, 768), (256, 768, 640)),
                            ((65536, 5120, 1536), (256, 768, 1024)),
                            ((65536, 2304, 1024), (256, 1024, 768)),
                            ((32768, 3584, 1024), (256, 1024, 896)),
                            ((65536, 2048, 1024), (256, 1024, 1024))):
        assert gm.choose_tiles(m, k, n, jnp.bfloat16) == want
        bm, _, bk = want
        walk = 2 * (bm * n + bk * n + 2 * bm * bk) * 2 + bm * bk * 4
        assert walk + n * bk * 4 <= gm._VMEM_BUDGET
