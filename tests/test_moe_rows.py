"""The expert layer's row movements bound by the live count
(ops/moe_rows.py through models/moe.py's ``dispatch_rows`` and
``combine_rows``), the kernels in interpreter mode against XLA's gathers:
dispatch's forward, combine's forward, dispatch's backward and combine's
two cotangents, at live counts on and around a block's edge, under
skewed routing, and with everything the kernels must not read, and
everything they do not write, poisoned with NaN.

Rows, weights and cotangents are small integers (or bfloat16-valued),
so every product and every sum is exact in float32: XLA's CPU backend
contracts a multiply and an add into one FMA where it fuses them, and
neither that nor the order of a sum is what these tests are about. The
last test runs unrounded operands under a tolerance.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import grouped_matmul as gm
from dlrover_tpu.ops import moe_rows

D = 128
SORTED_BLOCK, TOKEN_BLOCK = 32, 8
#: tokens, choices a token, experts, experts held
SHAPES = [(64, 2, 8, 8), (96, 6, 64, 16), (128, 8, 256, 32), (40, 4, 64, 8)]
LIVE = {
    "none": lambda n: 0,
    "one": lambda n: 1,
    "under_an_edge": lambda n: 2 * SORTED_BLOCK - 1,
    "on_an_edge": lambda n: 2 * SORTED_BLOCK,
    "over_an_edge": lambda n: 2 * SORTED_BLOCK + 1,
    "all": lambda n: n,
}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several blocks at these sizes."""
    monkeypatch.setattr(moe_rows, "_MAX_SORTED_BLOCK", SORTED_BLOCK)
    monkeypatch.setattr(moe_rows, "_MAX_TOKEN_BLOCK", TOKEN_BLOCK)


def _routing(t, k, e, held, live, seed=0):
    """``top_e (t, k)`` with exactly ``live`` pairs on held experts."""
    rng = np.random.default_rng(seed)
    n = t * k
    absent = rng.integers(held, e, n) if held < e else np.zeros(n, np.int64)
    chosen = np.where(rng.permutation(n) < live,
                      rng.integers(0, held, n), absent)
    return jnp.asarray(chosen.reshape(t, k), jnp.int32)


def _integers(key, shape, dtype, top=8):
    return jax.random.randint(key, shape, -top, top + 1).astype(dtype)


def _operands(t, k, dtype, seed=1, d=D):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = t * k
    return dict(
        yt=_integers(ks[0], (t, d), dtype),
        rows=_integers(ks[1], (n, d), dtype),
        weights=_integers(ks[2], (t, k), jnp.float32, top=4) / 4,
        g_tokens=_integers(ks[3], (t, d), dtype),
        g_rows=_integers(ks[4], (n, d), dtype),
    )


def _below(n, live):
    return (jnp.arange(n) < live)[:, None]


@functools.partial(jax.jit, static_argnames=("k", "kernels"))
def _movements(ops, order, inverse, live, *, k, kernels):
    """The four movements' results. With ``kernels``, rows the kernels
    must not read are NaN; without, they are the zeros the grouped
    products leave there."""
    n = order.shape[0]
    fill = jnp.nan if kernels else 0.0
    count = live if kernels else None
    rows = jnp.where(_below(n, live), ops["rows"], fill).astype(
        ops["rows"].dtype)
    g_rows = jnp.where(_below(n, live), ops["g_rows"], fill).astype(
        ops["g_rows"].dtype)
    dispatch = lambda y: moe.dispatch_rows(
        y, order, inverse, k, count, interpret=kernels)
    combine = lambda r, w: moe.combine_rows(
        r, w, order, inverse, count, interpret=kernels)
    xs, dispatch_vjp = jax.vjp(dispatch, ops["yt"])
    out, combine_vjp = jax.vjp(combine, rows, ops["weights"])
    d_rows, d_weights = combine_vjp(ops["g_tokens"])
    return dict(xs=xs, d_yt=dispatch_vjp(g_rows)[0], out=out,
                d_rows=d_rows, d_weights=d_weights)


def _f32(x):
    return np.asarray(jnp.asarray(x, jnp.float32))


def _edge(n, live, block):
    """The end of the block of ``block`` rows that holds row ``live``."""
    return min(n, (live // block + 1) * block)


def _assert_same(got, want, live, inverse, k):
    """Bit for bit wherever a result is defined; ``xs`` and ``d_rows``
    are defined below the live count, ``xs`` finite (real rows) and
    ``d_rows`` zero to the end of the block that holds row ``live``,
    and nothing is said of either past it; a tail pair's ``d_weights``
    is exactly 0."""
    n = inverse.shape[0]
    for name in ("d_yt", "out"):
        np.testing.assert_array_equal(_f32(got[name]), _f32(want[name]), name)
    for name in ("xs", "d_rows"):
        np.testing.assert_array_equal(
            _f32(got[name])[:live], _f32(want[name])[:live], name)
    written = _edge(n, live, SORTED_BLOCK)
    np.testing.assert_array_equal(
        _f32(got["xs"])[live:written], _f32(want["xs"])[live:written])
    assert not _f32(got["d_rows"])[live:written].any()
    live_pair = np.asarray(inverse < live).reshape(-1, k)
    np.testing.assert_array_equal(
        _f32(got["d_weights"])[live_pair], _f32(want["d_weights"])[live_pair])
    assert not _f32(got["d_weights"])[~live_pair].any()


def _check(top_e, held, dtype, d=D):
    t, k = top_e.shape
    order, inverse, sizes = moe.sort_pairs(top_e, held)
    live = jnp.sum(sizes)
    ops = _operands(t, k, dtype, d=d)
    assert moe_rows.row_blocks(t, k, d, dtype, interpret=True) == (
        SORTED_BLOCK, TOKEN_BLOCK)
    got = _movements(ops, order, inverse, live, k=k, kernels=True)
    want = _movements(ops, order, inverse, live, k=k, kernels=False)
    _assert_same(got, want, int(live), inverse, k)
    return int(live)


# every expert held: no pair can sort into a tail, the count is t x k
CASES = [(shape, count) for shape in SHAPES for count in sorted(LIVE)
         if shape[2] > shape[3] or count == "all"]


@pytest.mark.parametrize("shape,count", CASES)
def test_movements_match_xla_at_a_live_count(shape, count):
    t, k, e, held = shape
    live = LIVE[count](t * k)
    assert _check(_routing(t, k, e, held, live), held, jnp.bfloat16) == live


@pytest.mark.parametrize("count", ["over_an_edge", "all"])
def test_movements_match_xla_in_float32(count):
    t, k, e, held = SHAPES[1]
    _check(_routing(t, k, e, held, LIVE[count](t * k)), held, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_movements_match_xla_over_several_lane_blocks(dtype):
    """The cotangent kernel walks a row's width 128 lanes at a time."""
    t, k, e, held = SHAPES[3]
    live = LIVE["over_an_edge"](t * k)
    _check(_routing(t, k, e, held, live), held, dtype, d=3 * D)


GATHER_LIVE = {
    "none": lambda n: 0,
    "one": lambda n: 1,
    "under_a_block": lambda n: SORTED_BLOCK - 1,
    "a_block": lambda n: SORTED_BLOCK,
    "all_but_one": lambda n: n - 1,
    "all": lambda n: n,
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("count", list(GATHER_LIVE))
def test_dispatch_gathers_to_the_end_of_the_block_that_holds_the_count(
        count, dtype):
    """Dispatch's forward alone: XLA's gather bit for bit below the count
    and on to the end of the block that holds row ``live`` (real rows,
    which the products mask by position); what is left keeps what the
    buffer held, NaN in interpreter mode."""
    t, k, e, held = SHAPES[1]
    n = t * k
    live = GATHER_LIVE[count](n)
    order, inverse, sizes = moe.sort_pairs(_routing(t, k, e, held, live),
                                           held)
    assert int(jnp.sum(sizes)) == live
    yt = jax.random.normal(jax.random.key(2), (t, D)).astype(dtype)
    got = moe.dispatch_rows(yt, order, inverse, k, jnp.int32(live),
                            interpret=True)
    written = _edge(n, live, SORTED_BLOCK)
    assert got.shape == (n, D) and got.dtype == dtype
    np.testing.assert_array_equal(
        _f32(got)[:written], _f32(yt[order // k])[:written])
    assert np.isnan(_f32(got)[written:]).all()      # never written


def test_one_expert_takes_every_live_pair():
    t, k, e, held = SHAPES[1]
    top_e = np.asarray(_routing(t, k, e, held, 200)).copy()
    top_e[top_e < held] = 3
    assert _check(jnp.asarray(top_e), held, jnp.bfloat16) == 200


def test_tokens_with_no_live_pair_and_with_all_of_them():
    t, k, e, held = SHAPES[2]
    top_e = np.asarray(_routing(t, k, e, held, 300)).copy()
    top_e[5] = held + np.arange(k)          # none of token 5's is held
    top_e[6] = np.arange(k)                 # all of token 6's are
    top_e[TOKEN_BLOCK:2 * TOKEN_BLOCK] = e - 1    # a whole block of none
    live = _check(jnp.asarray(top_e), held, jnp.bfloat16)
    order, inverse, _ = moe.sort_pairs(jnp.asarray(top_e), held)
    ops = _operands(t, k, jnp.bfloat16)
    out = moe.combine_rows(ops["rows"], ops["weights"], order, inverse,
                           jnp.int32(live), interpret=True)
    assert not _f32(out)[5].any()
    assert not _f32(out)[TOKEN_BLOCK:2 * TOKEN_BLOCK].any()
    assert _f32(out)[6].any()


def test_live_pairs_are_compacted_in_token_order():
    inverse = jnp.asarray([7, 0, 5, 2, 1, 6, 3, 4], jnp.int32)
    which, count = moe_rows._live_pairs(inverse, jnp.int32(3), 4)
    np.testing.assert_array_equal(count, [2, 1])
    np.testing.assert_array_equal(which, [[1, 3, 0, 0], [0, 0, 0, 0]])


# ---------------------------------------------------------------------------
# through the grouped products: a NaN in a row nothing visits reaches
# neither the loss nor a gradient
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _poison_cotangent_tail(x, live):
    return x


def _poison_fwd(x, live):
    return x, live


def _poison_bwd(live, g):
    return jnp.where(_below(g.shape[0], live), g, jnp.nan), None


_poison_cotangent_tail.defvjp(_poison_fwd, _poison_bwd)


def _poison_tail(x, live):
    """Rows from ``live`` on are NaN: what `dispatch_rows` gathers past
    the count (to its block's end) no product may use, and what it does
    not write nothing may read."""
    return jnp.where(_below(x.shape[0], live), x, jnp.nan)


@jax.custom_vjp
def _poison_both_tails(x, live):
    """Forward: rows from ``live`` on are NaN (what `combine_rows` must
    not read). Backward: the cotangent's rows past the block that holds
    row ``live`` are NaN (what the cotangent kernel does not write)."""
    return _poison_tail(x, live)


def _poison_both_fwd(x, live):
    return _poison_both_tails(x, live), live


def _poison_both_bwd(live, g):
    written = (live // SORTED_BLOCK + 1) * SORTED_BLOCK
    return jnp.where(_below(g.shape[0], written), g, jnp.nan), None


_poison_both_tails.defvjp(_poison_both_fwd, _poison_both_bwd)


def _expert_layer(params, yt, top_p, top_e, held, kernels):
    k = top_e.shape[1]
    order, inverse, sizes = moe.sort_pairs(top_e, held)
    live = jnp.sum(sizes) if kernels else None
    # with the row kernels the products walk no tile of the tail: what
    # they leave unwritten there is NaN in interpreter mode
    products = functools.partial(gm.grouped_matmul, group_sizes=sizes,
                                 tail_unread=kernels, interpret=True)
    xs = moe.dispatch_rows(yt, order, inverse, k, live, interpret=kernels)
    if kernels:
        # the value's tail after the cotangent's: a `where`'s own
        # backward would zero the NaN on its way to dispatch's
        xs = _poison_tail(_poison_cotangent_tail(xs, live), live)
    hidden = jax.nn.silu(products(xs, params["w_gate"])) * products(
        xs, params["w_up"])
    rows = products(hidden, params["w_down"])
    if kernels:
        rows = _poison_both_tails(rows, live)
    out = moe.combine_rows(rows, top_p, order, inverse, live,
                           interpret=kernels)
    return jnp.sum(out.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("live", [0, 70, 2 * SORTED_BLOCK])
def test_nan_in_unvisited_rows_reaches_nothing(live):
    t, k, e, held = 64, 4, 32, 4
    f = 128
    ks = jax.random.split(jax.random.key(3), 5)
    bf = jnp.bfloat16
    params = {
        "w_gate": (jax.random.normal(ks[0], (held, D, f)) * D ** -0.5),
        "w_up": (jax.random.normal(ks[1], (held, D, f)) * D ** -0.5),
        "w_down": (jax.random.normal(ks[2], (held, f, D)) * f ** -0.5),
    }
    params = jax.tree.map(lambda w: w.astype(bf), params)
    yt = jax.random.normal(ks[3], (t, D)).astype(bf)
    top_p = jax.random.uniform(ks[4], (t, k)).astype(bf).astype(jnp.float32)
    top_e = _routing(t, k, e, held, live, seed=4)
    grad = jax.jit(jax.value_and_grad(_expert_layer, argnums=(0, 1, 2)),
                   static_argnums=(4, 5))
    loss, grads = grad(params, yt, top_p, top_e, held, True)
    want_loss, want = grad(params, yt, top_p, top_e, held, False)
    assert np.isfinite(float(loss))
    np.testing.assert_array_equal(_f32(loss), _f32(want_loss))
    for got_leaf, want_leaf in zip(jax.tree.leaves(grads[:2]),
                                   jax.tree.leaves(want[:2])):
        assert np.isfinite(_f32(got_leaf)).all()
        np.testing.assert_array_equal(_f32(got_leaf), _f32(want_leaf))
    # the weights' cotangent is a sum of 128 products a pair, which the
    # kernel adds in another order
    assert np.isfinite(_f32(grads[2])).all()
    np.testing.assert_allclose(grads[2], want[2], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# act(gate) x up as a pass bound by the count, alone and in the layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("count", sorted(LIVE))
def test_gated_pass_matches_jnp_below_the_count(count, act):
    """Forward and both cotangents against autodiff of the ``jnp``
    expression, float32; NaN in every operand's rows from the count on,
    which the pass turns into zeros up to its block's end and leaves
    unwritten after it."""
    n, f = 6 * SORTED_BLOCK, 2 * D
    live = LIVE[count](n)
    ks = jax.random.split(jax.random.key(11), 3)
    gate, up, ct = (jax.random.normal(k, (n, f)) for k in ks)
    poison = lambda a: jnp.where(_below(n, live), a, jnp.nan)
    got, vjp = jax.vjp(
        lambda g, u: moe.gated_rows(g, u, act, jnp.int32(live),
                                    interpret=True), poison(gate), poison(up))
    want, want_vjp = jax.vjp(
        lambda g, u: moe.gated_rows(g, u, act), gate, up)
    edge = _edge(n, live, SORTED_BLOCK)
    for g, w in zip((got,) + vjp(poison(ct)), (want,) + want_vjp(ct)):
        np.testing.assert_allclose(g[:live], w[:live], rtol=2e-6, atol=2e-6)
        assert not _f32(g)[live:edge].any()
        assert np.isnan(_f32(g)[edge:]).all()      # never written


def _int_layer(t, k, held, f, seed=5):
    ks = jax.random.split(jax.random.key(seed), 5)
    bf = jnp.bfloat16
    lp = {"w_gate": _integers(ks[0], (held, D, f), bf, top=1),
          "w_up": _integers(ks[1], (held, D, f), bf, top=1),
          "w_down": _integers(ks[2], (held, f, D), bf, top=1)}
    yt = _integers(ks[3], (t, D), bf, top=2)
    top_p = _integers(ks[4], (t, k), jnp.float32, top=4) / 4
    return lp, yt, top_p


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("count", ["none", "over_an_edge", "all"])
def test_experts_that_skip_the_tail_match_the_zeroing_walk(
        monkeypatch, count, act):
    """`moe._experts` with a tail (the products walk no tile of it, the
    pass between them and the row movements stop at the count) against
    the same layer without that word (`blocks` None: the walk that
    zeroes, XLA's gathers, the ``jnp`` expression): the output and every
    gradient. Integer-valued operands: with ``relu`` every product is
    exact and a sum rounds once, in either form."""
    monkeypatch.setattr(gm, "_MAX_BLOCK_M", SORTED_BLOCK)
    t, k, e, held, f = 64, 4, 32, 4, 128
    live = LIVE[count](t * k)
    top_e = _routing(t, k, e, held, live, seed=6)
    lp, yt, top_p = _int_layer(t, k, held, f)

    def loss(tail):
        def fn(lp, yt, top_p):
            out = moe._experts(lp, yt, top_p, top_e, held, 0, act, tail,
                               interpret=True)
            return jnp.sum(out.astype(jnp.float32) * (1 + jnp.arange(D) % 3)
                           ), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss(True)(lp, yt, top_p)
    gauges = dict(trace.gauges())
    (_, want_out), want = loss(False)(lp, yt, top_p)
    assert gauges["moe.tail_skipped"] == gauges["moe.rows_kernel"] == 1
    assert trace.gauges()["moe.tail_skipped"] == 0
    same = np.testing.assert_array_equal if act == "relu" else (
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=2e-2, atol=2e-2 * (1 + np.abs(b).max())))
    same(_f32(out), _f32(want_out))
    for got_leaf, want_leaf in zip(jax.tree.leaves(grads[:2]),
                                   jax.tree.leaves(want[:2])):
        assert np.isfinite(_f32(got_leaf)).all()
        same(_f32(got_leaf), _f32(want_leaf))
    # d top_p: a sum of 128 products a pair, added in another order
    np.testing.assert_allclose(
        grads[2], want[2], rtol=1e-5 if act == "relu" else 2e-2,
        atol=1e-5 if act == "relu" else 2e-2 * float(
            1 + jnp.abs(want[2]).max()))
    if count == "none":
        assert not _f32(out).any()


def test_unrounded_operands_agree_to_rounding():
    t, k, e, held = SHAPES[1]
    top_e = _routing(t, k, e, held, 150)
    order, inverse, sizes = moe.sort_pairs(top_e, held)
    live = jnp.sum(sizes)
    ks = jax.random.split(jax.random.key(9), 3)
    rows = jnp.where(_below(t * k, live),
                     jax.random.normal(ks[0], (t * k, D)), 0.0)
    weights = jax.random.uniform(ks[1], (t, k))
    g = jax.random.normal(ks[2], (t, D))

    def both(count, interpret):
        out, vjp = jax.vjp(
            lambda r, w: moe.combine_rows(r, w, order, inverse, count,
                                          interpret=interpret),
            rows, weights)
        d_rows, d_weights = vjp(g)
        return out, jnp.where(_below(t * k, live), d_rows, 0.0), d_weights

    for got, want in zip(both(live, True), both(None, False)):
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_moe_mlp_with_a_tail_matches_the_path_that_names_no_count(
        monkeypatch):
    """`moe.moe_mlp` over a held share, the bounded forms (the gather
    that stops at the count and is made again for the backward, the row
    kernels, the products that walk no tile of the tail: what they do
    not write reads NaN here) against XLA's ops over every
    row: the loss and every gradient. Float32, ReGLU: the forms differ
    in the order of a token's ``k`` and of a product's sum. Two of 32
    experts held: a share at which the gather stops at the count too."""
    monkeypatch.setattr(gm, "_MAX_BLOCK_M", SORTED_BLOCK)
    cfg = moe.MoeConfig.tiny(dim=D, ffn_dim=D, n_experts=32,
                             experts_per_token=4, experts_held=2,
                             first_expert=3, expert_act="relu")
    layers = moe.init_params(cfg, jax.random.key(12))["layers"]
    lp = {name: layers[name][0]
          for name in ("router", "w_gate", "w_up", "w_down")}
    # weights large enough for outputs that a wrong row would move
    lp = {name: w * (1 if name == "router" else 20) for name, w in lp.items()}
    y = jax.random.normal(jax.random.key(13), (2, 48, D))

    def loss(lp, y):
        out, aux = moe.moe_mlp(cfg, lp, y)
        return jnp.sum(out ** 2) + aux

    grad = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(lp, y)
    want_loss, want = grad()
    assert trace.gauges()["moe.dispatch_bounded"] == 0
    monkeypatch.setattr(moe, "_experts", functools.partial(
        moe._experts, interpret=True))
    got_loss, got = grad()
    gauges = trace.gauges()
    assert gauges["moe.dispatch_bounded"] == gauges["moe.rows_kernel"] == 1
    assert float(want_loss) > 100.0     # the aux term alone is 1
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for got_leaf, want_leaf in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)):
        assert np.isfinite(_f32(got_leaf)).all()
        np.testing.assert_allclose(
            got_leaf, want_leaf, rtol=1e-4,
            atol=1e-5 * float(jnp.abs(want_leaf).max()))
