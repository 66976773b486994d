"""What the Llama and the ``models/moe.py`` blocks keep of a recomputed
layer (``llama._maybe_remat``): the flash pair (``attention.KEPT``) and
q, k, v where the attention's backward begins to read them
(``llama.QKV_KEPT``: after rotary; before the norm of q and k where the
block has one). The recomputed forward then forms no q / k / v product
and runs no flash forward; the same arrays reach the same backward, so
the gradient is the unrecomputed block's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama, moe, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention

CASES = ["llama", "moe", "moe_qk_norm"]
KEPT = ("attn.out_kept", "attn.qkv_kept")


def _loss(case: str, **kw):
    """``(loss(params, tokens), params)`` of the tiny ``case``."""
    if case == "llama":
        cfg, family = llama.LlamaConfig.tiny(**kw), llama
    else:
        cfg = moe.MoeConfig.tiny(qk_norm=case == "moe_qk_norm", **kw)
        family = moe
    params = family.init_params(cfg, jax.random.key(0))
    return (lambda p, t: family.loss_fn(p, t, cfg)), params


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.key(1), (2, 32), 0, 256)


def _grad_text(case, toks, **kw):
    loss, params = _loss(case, remat=True, **kw)
    return str(jax.make_jaxpr(jax.grad(loss))(params, toks))


def _kept_and_whole(case, toks, monkeypatch):
    """The gradient's text as the block is, and as a block that keeps
    nothing (the parent's ``nothing_saveable``) would have it."""
    kept = _grad_text(case, toks)
    with monkeypatch.context() as m:
        m.setattr(llama, "_maybe_remat", lambda cfg, fn: stack.recompute(
            fn, cfg.remat))
        return kept, _grad_text(case, toks)


@pytest.mark.parametrize("case", CASES)
def test_the_gradient_is_the_unrecomputed_blocks(case, toks):
    (want_loss, want), (got_loss, got) = (
        jax.value_and_grad(loss)(params, toks)
        for loss, params in (_loss(case, remat=False),
                             _loss(case, remat=True)))
    np.testing.assert_allclose(float(want_loss), float(got_loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert all(np.asarray(leaf).any() for leaf in jax.tree.leaves(got))


@pytest.mark.parametrize("case", CASES)
def test_the_recomputed_forward_forms_no_q_k_v_product(
        case, toks, monkeypatch):
    """The reference path (products in the open): five products fewer
    than a block that keeps nothing forms, the three projections and the
    attention's two. A q or k named after its norm would spare three."""
    kept, whole = _kept_and_whole(case, toks, monkeypatch)
    assert whole.count("dot_general") - kept.count("dot_general") == 5


@pytest.mark.parametrize("case", CASES)
def test_a_flash_forward_runs_once_a_layer(case, toks, monkeypatch):
    """The kernel path, traced and not run: the layers are one scan, so
    one call site a pass; the recomputed pass has none."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    kept, whole = _kept_and_whole(case, toks, monkeypatch)
    assert (whole.count("name=attention_fwd"),
            kept.count("name=attention_fwd")) == (2, 1)
    for name in ("name=attention_bwd_dq", "name=attention_bwd_dkv"):
        assert whole.count(name) == kept.count(name) == 1


@pytest.mark.parametrize("case", CASES)
def test_the_gauges_say_what_a_traced_backward_kept(case, toks):
    for remat in (True, False):
        loss, params = _loss(case, remat=remat)
        for name in KEPT:
            trace.gauge(name, -1)
        jax.make_jaxpr(loss)(params, toks)  # a forward alone keeps nothing
        assert [trace.gauges()[name] for name in KEPT] == [0, 0]
        jax.make_jaxpr(jax.grad(loss))(params, toks)
        assert [trace.gauges()[name] for name in KEPT] == [int(remat)] * 2


def test_the_sp_forms_keep_q_k_v_alone(toks):
    """The reference attention (as ring and ulysses) names no output: the
    three stay and the gauge of the flash pair reads 0."""
    loss, params = _loss("llama", remat=True, attn_impl="reference")
    jax.make_jaxpr(jax.grad(loss))(params, toks)
    assert [trace.gauges()[name] for name in KEPT] == [0, 1]


@pytest.mark.parametrize("policy", ["all", "mlp"])
def test_the_pp_stages_keep_what_the_scan_keeps(policy, toks, monkeypatch):
    asked = []
    recompute = stack.recompute

    def recording(fn, remat, keep=(), kept=None):
        asked.append((remat, tuple(keep), kept))
        return recompute(fn, remat, keep, kept)

    monkeypatch.setattr(stack, "recompute", recording)
    cfg = llama.LlamaConfig.tiny(remat=True, remat_policy=policy)
    params = llama.init_params(cfg, jax.random.key(0))
    jax.make_jaxpr(lambda p: llama.forward_hidden(p, toks, cfg))(params)
    stage = llama._stage_layer_fn(cfg, mb=2, s_local=32, sp_size=1)
    moe_cfg = moe.MoeConfig.tiny(remat=True)
    jax.make_jaxpr(lambda p: moe.forward_hidden(p, toks, moe_cfg))(
        moe.init_params(moe_cfg, jax.random.key(0)))
    scan, pp, experts, gathered = asked
    assert scan == pp
    wide = ("ffn_gate", "ffn_up") if policy == "mlp" else ()
    assert scan == (True, attention.KEPT + llama.QKV_KEPT + wide,
                    llama.report_kept)
    assert experts == (True, attention.KEPT + llama.QKV_KEPT,
                       llama.report_kept)
    # ``moe._experts``' own call, inside the layer's: the live rows are
    # gathered again where a row kernel gathered them, and none runs at
    # the tiny configuration, where every expert is held
    assert gathered == (False, (), None)
    # the stage's block is the scan's: the same gradient a layer
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.dim))
    inv_freq = llama.rope_frequencies(cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))

    def grads(fn):
        return jax.grad(lambda lp, x: jnp.sum(fn(lp, x) ** 2),
                        argnums=(0, 1))(lp, x)

    for a, b in zip(
            jax.tree.leaves(grads(stage)),
            jax.tree.leaves(grads(lambda lp, x: llama._decoder_layer(
                cfg, None, inv_freq, positions, lp, x)))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
