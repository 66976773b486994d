"""Flash attention over a selection the caller hands in (``select=``: an
int8 mask, data and not a function of the positions): the three Pallas
kernels in interpret mode against the jnp oracle under the same mask,
forward and both backwards, ``lse``'s cotangent too; rows that select all
their keys and rows that select none of a block; the window kernels at
the odd edges of a window that is no multiple of a tile, at 256 / 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import (
    flash_attention,
    flash_attention_select_with_lse,
    flash_attention_with_lse,
    mha_reference,
    mha_reference_with_lse,
)

S = 256


def _qkv(s=S, h=2, hkv=2, d=48, dv=32, b=2, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, hkv, d), jnp.float32),
            jax.random.normal(kv, (b, s, hkv, dv), jnp.float32))


def _causal(s=S):
    return np.tril(np.ones((s, s), bool))


def _mask(kind: str, b=2, s=S, seed=3):
    """Selections the kernels must survive, all under the causal mask and
    with at least one key a row."""
    rng = np.random.default_rng(seed)
    causal = _causal(s)
    eye = np.eye(s, dtype=bool)
    if kind == "all":                # every row selects all its keys
        mask = np.broadcast_to(causal, (b, s, s)).copy()
    elif kind == "random":
        mask = (rng.random((b, s, s)) < 0.4) & causal | eye
    elif kind == "self":             # every row selects itself alone
        mask = np.broadcast_to(eye, (b, s, s)).copy()
    elif kind == "none of block 0":  # late rows select nothing of block 0
        mask = (rng.random((b, s, s)) < 0.5) & causal | eye
        mask[:, 128:, :64] = False
    elif kind == "none of the diagonal block":
        mask = np.broadcast_to(causal, (b, s, s)).copy()
        for start in range(64, s, 64):
            mask[:, start:start + 64, start:start + 64] = False
    elif kind == "top-k":            # as ops/dsa.py makes it
        from dlrover_tpu.ops import dsa
        scores = jax.random.normal(jax.random.key(seed), (b, s, s))
        return dsa.selection_mask(scores, 48)
    else:
        raise KeyError(kind)
    return jnp.asarray(mask, jnp.int8)


def _plain(q, k, v, mask):
    """Softmax over the selected keys by explicit scores, written apart
    from the program."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where((mask != 0)[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _with_lse(fn, q, k, v, w_out, w_lse):
    def loss(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * w_out) + jnp.sum(lse * w_lse)

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


KINDS = ["all", "random", "self", "none of block 0",
         "none of the diagonal block", "top-k"]


@pytest.mark.parametrize("kind", KINDS)
def test_forward_matches_softmax_over_the_selection(kind):
    q, k, v = _qkv()
    mask = _mask(kind)
    want = _plain(q, k, v, mask)
    np.testing.assert_allclose(
        mha_reference_with_lse(q, k, v, select=mask)[0], want,
        atol=2e-5, rtol=2e-5)
    got = flash_attention(q, k, v, True, 64, 64, interpret=True, select=mask)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind", KINDS)
def test_gradients_and_the_lse_cotangent(kind):
    q, k, v = _qkv(seed=1)
    mask = _mask(kind)
    keys = jax.random.split(jax.random.key(7), 2)
    w_out = jax.random.normal(keys[0], v.shape[:2] + (2, 32))
    w_lse = jax.random.normal(keys[1], (2, 2, S))
    want = _with_lse(
        lambda q, k, v: mha_reference_with_lse(q, k, v, select=mask),
        q, k, v, w_out, w_lse)
    got = _with_lse(
        lambda q, k, v: flash_attention_select_with_lse(
            q, k, v, mask, 64, 64, True),
        q, k, v, w_out, w_lse)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("group,hkv", [(3, 1), (2, 2)])
@pytest.mark.parametrize("tiles", [(32, 64), (64, 32), (128, 128)])
def test_groups_and_uneven_tiles(group, hkv, tiles):
    q, k, v = _qkv(h=group * hkv, hkv=hkv, seed=2)
    mask = _mask("random")
    w = jax.random.normal(jax.random.key(5), v.shape[:2] + (group * hkv, 32))

    def grads(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))(
                q, k, v)

    want = grads(lambda q, k, v: mha_reference_with_lse(
        q, k, v, select=mask)[0])
    got = grads(lambda q, k, v: flash_attention(
        q, k, v, True, *tiles, interpret=True, select=mask))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=5e-5, rtol=5e-5)


def test_selecting_every_causal_key_is_the_causal_call():
    q, k, v = _qkv(seed=4)
    got = flash_attention(q, k, v, True, 64, 64, interpret=True,
                          select=_mask("all"))
    want = flash_attention(q, k, v, True, 64, 64, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_the_selection_gets_no_gradient_and_the_kernels_their_names(
        monkeypatch):
    q, k, v = _qkv(b=1)
    mask = _mask("random", b=1)
    names = []
    real = attention.pl.pallas_call

    def spy(*a, **kw):
        names.append(kw.get("name"))
        return real(*a, **kw)

    monkeypatch.setattr(attention.pl, "pallas_call", spy)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, 64, 64, interpret=True, select=mask)))(q)
    assert names == ["attention_fwd_sel", "attention_bwd_dq_sel",
                     "attention_bwd_dkv_sel"]
    names.clear()
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, 64, 64, interpret=True)))(q)
    assert names == ["attention_fwd", "attention_bwd_dq",
                     "attention_bwd_dkv"]


def test_a_call_without_a_selection_traces_as_it_did():
    """The operand is absent from the older callers' calls, not passed as
    "all": their jaxpr names no selection kernel and takes three
    operands."""
    q, k, v = _qkv(b=1)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, True, 64, 64, interpret=True))(q, k, v))
    assert "_sel" not in text and "i8[" not in text


def test_a_mask_closed_over_as_a_numpy_array_is_taken():
    """A host array captured by a jitted caller reaches the backward as a
    constant that is no `jax.Array`: the transposed copy for dk/dv must
    not lean on an array method."""
    q, k, v = _qkv(b=1)
    mask = np.asarray(_mask("random", b=1))

    @jax.jit
    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, 64, 64, interpret=True, select=mask)),
            argnums=(0, 1, 2))(q, k, v)

    want = jax.grad(lambda q, k, v: jnp.sum(mha_reference_with_lse(
        q, k, v, select=mask)[0]), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads(q, k, v), want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("bad", [
    dict(causal=False), dict(window=16)])
def test_a_selection_is_the_whole_mask(bad):
    q, k, v = _qkv(b=1)
    kw = dict(causal=True, select=_mask("all", b=1))
    kw.update(bad)
    with pytest.raises(ValueError, match="select"):
        flash_attention(q, k, v, interpret=True, **kw)


@pytest.mark.parametrize("mask", [
    jnp.ones((1, S, S), jnp.int32), jnp.ones((1, S, S // 2), jnp.int8)])
def test_a_selection_is_one_int8_square_a_batch_row(mask):
    q, k, v = _qkv(b=1)
    with pytest.raises(ValueError, match="int8"):
        flash_attention(q, k, v, interpret=True, select=mask)


# -- the window kernels where this family runs them: 256 / 128, a window
# that is no multiple of a tile ------------------------------------------

@pytest.mark.parametrize("window", [33, 65, 129])
@pytest.mark.parametrize("tiles", [(64, 64), (128, 32)])
def test_window_edges_at_two_head_widths(window, tiles):
    """A window of 2^n + 1 (the published 513's shape): the band's lower
    edge falls one key into a block."""
    q, k, v = _qkv(h=2, hkv=2, d=64, dv=32, b=1, seed=6)
    w = jax.random.normal(jax.random.key(8), v.shape)

    def both(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))(
                q, k, v)

    want = both(lambda q, k, v: mha_reference(q, k, v, window=window))
    got = both(lambda q, k, v: flash_attention_with_lse(
        q, k, v, True, *tiles, True, None, window)[0])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-4)
    for g, w_ in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w_, atol=5e-5, rtol=5e-5)
