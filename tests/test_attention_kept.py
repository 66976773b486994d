"""The flash forward's output and ``lse`` as named residuals
(``attention.KEPT``): a block recomputed through ``stack.recompute(fn, True,
attention.KEPT)`` keeps those two and none of q, k, v, so its backward runs
the forward kernel once where ``nothing_saveable`` runs it twice; the same
arrays reach the same backward, so loss and gradients are the un-kept
block's bit for bit; without a keeper a name is an identity. The causal,
windowed and selected forms, on the kernel path (interpret mode) and the
reference path."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention

B, S, H, HKV, D, DV = 2, 128, 2, 1, 48, 32
# every array of a block has a shape of its own, so a residual's shape
# says which it is
Q, K, V = (B, S, H, D), (B, S, HKV, D), (B, S, HKV, DV)
OUT, LSE = (B, S, H, DV), (B, H, S)

FORMS = ["causal", "window", "select"]
PATHS = ["kernels", "reference"]


def _operands(seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    dim = H * DV
    ws = tuple(0.2 * jax.random.normal(key, (dim, heads * width))
               for key, (_, _, heads, width) in zip(keys, (Q, K, V)))
    x = jax.random.normal(keys[3], (B, S, dim))
    mask = (jax.random.uniform(keys[4], (B, S, S)) < 0.4) | jnp.eye(
        S, dtype=bool)
    return ws, x, (mask & jnp.tril(jnp.ones((S, S), bool))).astype(jnp.int8)


def _block(form: str, path: str, mask):
    """A layer as the families write one: q, k, v from the input by
    products (so they are residuals of the block, not its arguments), the
    flash call, the residual add."""
    kwargs = {"causal": {}, "window": {"window": 40},
              "select": {"select": mask}}[form]

    def block(ws, x):
        q, k, v = ((x @ w).reshape(shape) for w, shape in zip(ws, (Q, K, V)))
        out = attention.flash_attention(
            q, k, v, interpret=path == "kernels", **kwargs)
        return x + out.reshape(x.shape)

    return block


def _two_in_line(fn):
    return lambda ws, x: jnp.sum(fn(ws, fn(ws, x)) ** 2)


def _forward_calls(jaxpr) -> int:
    return str(jaxpr).count("name=attention_fwd")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("form", FORMS)
def test_a_keeping_block_saves_the_output_and_lse_and_none_of_q_k_v(
        form, path):
    ws, x, mask = _operands()
    block = _block(form, path, mask)
    met = []
    kept = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(block, True, attention.KEPT, met.append), ws, x)]
    assert OUT in kept and LSE in kept
    assert not {Q, K, V} & set(kept)
    assert set(met) == set(attention.KEPT)
    whole = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(block, True), ws, x)]
    # nothing but the block's arguments (and what the selection closes
    # over) without a keeper
    assert not {Q, K, V, OUT, LSE} & set(whole)
    assert len(kept) == len(whole) + 2


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("form", FORMS)
def test_loss_and_gradients_are_the_unkept_blocks_bit_for_bit(form, path):
    ws, x, mask = _operands(1)
    block = _block(form, path, mask)

    def value_and_grads(keep):
        fn = _two_in_line(stack.recompute(block, True, keep))
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(ws, x)

    want, got = value_and_grads(()), value_and_grads(attention.KEPT)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(got[1][1]).any()


@pytest.mark.parametrize("form", FORMS)
def test_a_kept_forward_kernel_runs_once_a_block(form):
    """Two blocks in line, so the first one's output is wanted: four
    forward calls under ``nothing_saveable`` (each block's own and its
    recomputed one), two where the blocks keep the pair; the backward
    kernels as they were."""
    ws, x, mask = _operands()
    block = _block(form, "kernels", mask)

    def grad_jaxpr(keep):
        return jax.make_jaxpr(jax.grad(_two_in_line(
            stack.recompute(block, True, keep))))(ws, x)

    whole, kept = grad_jaxpr(()), grad_jaxpr(attention.KEPT)
    assert (_forward_calls(whole), _forward_calls(kept)) == (4, 2)
    for name in ("name=attention_bwd_dq", "name=attention_bwd_dkv"):
        assert str(whole).count(name) == str(kept).count(name) == 2


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("form", FORMS)
def test_without_a_keeper_a_name_is_an_identity(form, path, monkeypatch):
    """Under ``nothing_saveable``, and outside any checkpoint (the
    reference path: an interpreted kernel is slow to lower), the program
    lowered with the names is the program lowered without them, text for
    text."""
    ws, x, mask = _operands()

    def lowered(names: bool):
        block = _block(form, path, mask)  # traced anew: no cached jaxpr
        assert ("attn_out" in str(jax.make_jaxpr(block)(ws, x))) == names
        fns = (stack.recompute(block, True),) + (block,) * (
            path == "reference")
        # a private function's name ends in a count of the lowerings so far
        return [re.sub(r"(@\w+?)_\d+\b", r"\1", jax.jit(jax.grad(
            _two_in_line(fn))).lower(ws, x).as_text()) for fn in fns]

    named = lowered(True)
    monkeypatch.setattr(attention, "_named", lambda out, lse: (out, lse))
    assert named == lowered(False)


def test_the_gauge_says_that_an_output_was_kept():
    ws, x, mask = _operands()
    block = _block("causal", "reference", mask)
    trace.gauge("attn.out_kept", 0)
    jax.make_jaxpr(jax.grad(_two_in_line(stack.recompute(
        block, True, (), attention.report_kept))))(ws, x)
    assert trace.gauges()["attn.out_kept"] == 0
    # a forward alone keeps nothing
    jax.make_jaxpr(stack.recompute(
        block, True, attention.KEPT, attention.report_kept))(ws, x)
    assert trace.gauges()["attn.out_kept"] == 0
    jax.make_jaxpr(jax.grad(_two_in_line(stack.recompute(
        block, True, attention.KEPT, attention.report_kept))))(ws, x)
    assert trace.gauges()["attn.out_kept"] == 1
