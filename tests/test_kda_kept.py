"""The delta rule's output and the states its chunks started from as named
residuals (``kda.KEPT``): a block recomputed through ``stack.recompute(fn,
True, kda.KEPT)`` keeps those two and none of q, k, v, g, beta, so its
backward runs the forward kernel once where ``nothing_saveable`` runs it
twice; the same arrays reach the same backward kernel, so loss and
gradients are the un-kept block's bit for bit; without a keeper a name is
an identity. Both forms of the rule (a decay a channel, ``chunk_kda``; a
decay a head over grouped value heads, ``chunk_gdn``), their kernels in
interpret mode: the XLA form has no forward rule of its own and names
nothing."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from dlrover_tpu.models import stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import kda

B, S, HK, DK, DV, CHUNK = 2, 128, 2, 16, 8, 64
FORMS = ["kda", "gdn"]
# value heads a form: the per-head form groups two over a key head
HV = {"kda": HK, "gdn": 2 * HK}


def _shapes(form):
    """(q and k, v and the output, g, the states) of a form: v and the
    output share a shape, every other array has its own."""
    hv = HV[form]
    return ((B, S, HK, DK), (B, S, hv, DV),
            (B, S, HK, DK) if form == "kda" else (B, S, hv),
            (B, hv, S // CHUNK, DV, DK))


def _operands(form, seed=0):
    hv = HV[form]
    dim = hv * DV
    widths = (HK * DK, HK * DK, dim, HK * DK if form == "kda" else hv, hv)
    keys = jax.random.split(jax.random.key(seed), 6)
    ws = tuple(0.3 * jax.random.normal(key, (dim, width))
               for key, width in zip(keys, widths))
    return ws, jax.random.normal(keys[5], (B, S, dim))


def _block(form: str, interpret: bool = True):
    """A layer as the families write one: q, k, v, g, beta from the input
    by products (so they are residuals of the block, not its arguments,
    and each weight's gradient is one of the rule's five), the rule, the
    layer's norm and gate (whose backward reads the rule's output), the
    residual add."""
    qk, vo, gs, _ = _shapes(form)
    rule = kda.chunk_kda if form == "kda" else kda.chunk_gdn

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    def block(ws, x):
        q, k, v, g, beta = (x @ w for w in ws)
        o = rule(unit(q.reshape(qk)) * DK ** -0.5, unit(k.reshape(qk)),
                 v.reshape(vo), -jax.nn.softplus(g.reshape(gs)),
                 jax.nn.sigmoid(beta.reshape(vo[:3])), chunk=CHUNK,
                 interpret=interpret)
        return x + kda.norm_gate(o, x.reshape(vo), jnp.ones(DV), 1e-6)

    return block


def _two_in_line(fn):
    return lambda ws, x: jnp.sum(fn(ws, fn(ws, x)) ** 2)


@pytest.mark.parametrize("form", FORMS)
def test_a_keeping_block_saves_the_output_and_the_states_and_no_input(form):
    ws, x = _operands(form)
    block = _block(form)
    qk, vo, gs, states = _shapes(form)
    met = []
    kept = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(block, True, kda.KEPT, met.append), ws, x)]
    whole = [tuple(aval.shape) for aval, _ in saved_residuals(
        stack.recompute(block, True), ws, x)]
    # nothing but the block's arguments (and a constant of the kernels')
    # without a keeper; with one, the output (v, which has its shape,
    # stays recomputed) and the states
    assert not {qk, vo, gs, vo[:3], states} & set(whole)
    assert kept.count(vo) == 1 and kept.count(states) == 1
    assert set(kept) - set(whole) == {vo, states}
    assert set(met) == set(kda.KEPT)


@pytest.mark.parametrize("form", FORMS)
def test_loss_and_the_five_gradients_are_the_unkept_blocks_bit_for_bit(form):
    ws, x = _operands(form, 1)
    block = _block(form)

    def value_and_grads(keep):
        fn = _two_in_line(stack.recompute(block, True, keep))
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(ws, x)

    want, got = value_and_grads(()), value_and_grads(kda.KEPT)
    assert len(got[1][0]) == 5
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(b).any()


@pytest.mark.parametrize("form", FORMS)
def test_a_kept_forward_kernel_runs_once_a_block(form):
    """Two blocks in line, so the first one's output is wanted: four
    forward calls under ``nothing_saveable`` (each block's own and its
    recomputed one), two where the blocks keep the pair; the backward
    kernel as it was."""
    ws, x = _operands(form)
    block = _block(form)

    def grad_jaxpr(keep):
        return str(jax.make_jaxpr(jax.grad(_two_in_line(
            stack.recompute(block, True, keep))))(ws, x))

    whole, kept = grad_jaxpr(()), grad_jaxpr(kda.KEPT)
    fwd, bwd = f"name={form}_fwd", f"name={form}_bwd"
    assert (whole.count(fwd), kept.count(fwd)) == (4, 2)
    assert whole.count(bwd) == kept.count(bwd) == 2


@pytest.mark.parametrize("form", FORMS)
def test_without_a_keeper_a_name_is_an_identity(form, monkeypatch):
    """Under ``nothing_saveable`` the program lowered with the names is
    the program lowered without them (the parent's), text for text."""
    ws, x = _operands(form)

    def lowered(names: bool):
        block = _block(form)  # traced anew: no cached jaxpr
        jaxpr = str(jax.make_jaxpr(jax.grad(_two_in_line(block)))(ws, x))
        assert all((name in jaxpr) == names for name in kda.KEPT)
        # a private function's name ends in a count of the lowerings so far
        return re.sub(r"(@\w+?)_\d+\b", r"\1", jax.jit(jax.grad(
            _two_in_line(stack.recompute(block, True)))).lower(
                ws, x).as_text())

    named = lowered(True)
    monkeypatch.setattr(kda, "checkpoint_name", lambda x, name: x)
    assert named == lowered(False)


def test_the_gauge_says_that_the_states_were_kept():
    ws, x = _operands("kda")
    block = _block("kda")

    def traced(fn, keep):
        jax.make_jaxpr(fn(stack.recompute(
            block, True, keep, kda.report_kept)))(ws, x)
        return trace.gauges()["kda.state_kept"]

    def grad(fn):
        return jax.grad(_two_in_line(fn))

    trace.gauge("kda.state_kept", 0)
    assert traced(grad, ()) == 0
    # a forward alone keeps nothing; the output's name alone is not the
    # states'
    assert traced(lambda fn: fn, kda.KEPT) == 0
    assert traced(grad, kda.KEPT[:1]) == 0
    assert traced(grad, kda.KEPT) == 1
    # the XLA form has no forward rule of its own: nothing to keep
    trace.gauge("kda.state_kept", 0)
    jax.make_jaxpr(grad(stack.recompute(
        _block("kda", interpret=False), True, kda.KEPT,
        kda.report_kept)))(ws, x)
    assert trace.gauges()["kda.state_kept"] == 0
