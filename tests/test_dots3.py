"""The dots3 family (``models/dots3.py``) at a tiny size on the CPU against
the plain form of its equations (``benchmarks/families/dots3.py``:
explicit scores, ``lax.top_k``, a loop over the experts), with the
selection and the window both shorter than the sequence: the two parts
of the loss and where each one's gradient goes; the shares of heads and
experts tied to the uncut layer; the kernels in interpret mode and what
a recomputed block keeps. (The layout read from ``layer_types``:
``test_dots3_layout.py``; the meshes: ``test_dots3_mesh.py``; what the
three share: ``dots3_family.py``.)"""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import dots3 as family
from dlrover_tpu.models import dots3, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention, dsa
from tests.dots3_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _is_indexer, _terms, _weighty, built, config, mesh)


@pytest.mark.parametrize("part", [0, 1], ids=["CE", "L_I"])
def test_each_part_of_the_loss_and_its_gradient_match_the_plain_form(
        built, config, part):
    """fFSSS, top-16 and a window of 9 under 48 positions."""
    fam, params, tokens = built
    assert fam.cfg.pattern_string == "fFSSS"
    assert fam.cfg.index_topk == 16 and fam.cfg.window == 9
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: _terms(fam)(p, tokens)[part]))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)[part]))(params)
    assert abs(float(got) - float(want)) < 2e-5 * max(1.0, abs(float(want)))
    assert float(want) > 1e-3
    _assert_grads_agree(grads, want_grads, tol=1e-3)


def test_the_loss_is_the_sum_of_its_parts(built):
    fam, params, tokens = built
    ce, l_i = jax.jit(_terms(fam))(params, tokens)
    total = jax.jit(fam.loss_fn)(params, tokens)
    np.testing.assert_allclose(total, ce + l_i, rtol=1e-6)


def test_the_two_parts_move_disjoint_parameters(built):
    """``L_I``'s gradient reaches the indexer's parameters alone and CE's
    none of them, exactly (the stop-gradients, not a tolerance)."""
    fam, params, tokens = built
    for part, own in ((0, False), (1, True)):
        grads = jax.jit(jax.grad(
            lambda p: _terms(fam)(p, tokens)[part]))(params)
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
            moved = bool(np.asarray(leaf).any())
            name = jax.tree_util.keystr(path)
            if _is_indexer(path) != own:
                assert not moved, (part, name)
            elif "router_bias" not in name:
                assert moved, (part, name)


# -- the shares ---------------------------------------------------------------

def _head_share(lp, shape_all, first, held):
    """The leaves of heads ``first .. first + held - 1``: columns of
    ``w_qb``, ``w_kvb``, ``w_g``, rows of ``w_o``; the rest as it is."""
    h = shape_all.n_heads
    dn, dr, dv = (shape_all.qk_nope_dim, shape_all.qk_rope_dim,
                  shape_all.v_head_dim)
    take = slice(first, first + held)

    def columns(w, width):
        return w.reshape(w.shape[0], h, width)[:, take].reshape(
            w.shape[0], held * width)

    return dict(
        lp, w_qb=columns(lp["w_qb"], dn + dr),
        w_kvb=columns(lp["w_kvb"], dn + dv), w_g=lp["w_g"][:, take],
        w_o=lp["w_o"].reshape(h, dv, -1)[take].reshape(held * dv, -1))


@pytest.mark.parametrize("layer,kind", [(1, "F"), (2, "S")])
def test_the_head_shares_add_up_to_the_uncut_layers_attention(
        config, mesh, layer, kind):
    """Four chips split a layer's heads; ``W_qa``, ``W_kva``, the norms
    and the indexer are what each computes alike, so every share selects
    the same keys. The shares' outputs sum to the uncut reference's
    attention, and their ``p`` to the uncut ``p``."""
    whole_cfg = dict(config, num_attention_heads=4,
                     swa_num_attention_heads=4,
                     published_swa_num_attention_heads=4)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = dots3.layer_params(whole.cfg, params, layer)
    y = jax.random.normal(jax.random.key(2), (2, 40, whole.cfg.dim))
    want = jax.jit(lambda lp: family._ref_attention(
        y, lp, whole_cfg, kind))(lp)
    positions = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))

    total = jnp.zeros_like(want["attn"])
    for first in range(4):
        held = {"heads_held": 1, "first_head": first} if kind == "F" else {
            "swa_heads_held": 1, "swa_first_head": first}
        cfg = dataclasses.replace(whole.cfg, **held)
        share = _head_share(lp, whole.cfg.latent(kind), first, 1)
        out = jax.jit(lambda share: dots3.attention(
            cfg, None, kind, positions, share, y)[0])(share)
        assert float(jnp.max(jnp.abs(out))) > 1e-3     # each share weighs
        total = total + out
    np.testing.assert_allclose(total, want["attn"], atol=3e-5, rtol=3e-5)


def test_the_expert_shares_add_up_with_the_shared_expert_once(config, mesh):
    """Four chips share a layer's 8 experts, two each; the shared expert
    is counted once. The parts sum to the uncut plain form's layer."""
    whole_cfg = dict(config, n_routed_experts=8)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = dots3.layer_params(whole.cfg, params, 1)
    u = jax.random.normal(jax.random.key(3), (2, 24, whole.cfg.dim))
    want = jax.jit(lambda lp: family._ref_expert_layer(
        u, lp, whole_cfg)[0])(lp)

    shared = {k: lp[k] for k in ("ws_gate", "ws_up", "ws_down")}
    total = family._swiglu(u, *shared.values())
    for first in range(0, 8, 2):
        share = {k: v for k, v in lp.items() if k not in shared}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out = jax.jit(lambda share: moe.moe_mlp(cfg, share, u)[0])(share)
        assert float(jnp.max(jnp.abs(out))) > 1e-3
        total = total + out
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)


def test_the_shares_p_add_up(config, mesh):
    """What an absent head would add to ``p`` is left out: two shares'
    head-summed probabilities sum to the uncut layer's."""
    q, k = (jax.random.normal(jax.random.key(i), (1, 32, 4, 24))
            for i in (0, 1))
    mask = dsa.selection_mask(
        jax.random.normal(jax.random.key(2), (1, 32, 32)), 8)
    from dlrover_tpu.ops.attention import mha_reference_with_lse
    _, lse = mha_reference_with_lse(q, k, q, select=mask)
    whole = dsa.head_summed_probs(q, k, lse, mask, 24 ** -0.5)
    halves = sum(dsa.head_summed_probs(
        q[:, :, h], k[:, :, h], lse[:, h], mask, 24 ** -0.5)
        for h in (slice(0, 2), slice(2, 4)))
    np.testing.assert_allclose(halves, whole, rtol=1e-5, atol=1e-7)


# -- the kernels' path on the CPU, the counters, the meshes -------------------

def test_a_full_layer_through_the_kernels_in_interpret_mode(built):
    fam, params, tokens = built
    cfg = fam.cfg
    lp = dots3.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))

    def run(interpret):
        return jax.jit(jax.value_and_grad(lambda lp: sum(
            jnp.sum(x) for x in dots3.attention(
                cfg, None, "F", positions, lp, y, interpret=interpret))))(lp)

    (got, got_grads), (want, want_grads) = run(True), run(False)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_grads_agree(got_grads, want_grads, tol=1e-3)


def test_the_select_kernel_changes_nothing_of_a_full_layer(
        built, monkeypatch):
    """The same layer through the same kernels, the selection by
    `dsa_select` and by the XLA passes: one mask, so the same outputs and
    the same gradients."""
    fam, params, tokens = built
    cfg = fam.cfg
    lp = dots3.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))

    def run():
        return jax.jit(jax.value_and_grad(lambda lp: sum(
            jnp.sum(x) for x in dots3.attention(
                cfg, None, "F", positions, lp, y, interpret=True))))(lp)

    got, got_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 1
    assert trace.gauges()["attn.index_bwd_kernels"] == 1
    monkeypatch.setattr(dsa, "_select_rows", lambda s: None)
    want, want_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 0
    np.testing.assert_array_equal(got, want)
    for name in want_grads:
        np.testing.assert_array_equal(got_grads[name], want_grads[name])


def test_live_rows_count_the_pairs_that_chose_a_held_expert(built, config):
    fam, params, tokens = built
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (4,) and rows.dtype == np.int32
    # the plain form's routers on the plain form's own chain

    @jax.jit
    def counted(params, tokens):
        x = params["embed"][tokens]
        want = []
        for lp, kind in zip(family.layers_of(params, config),
                            family.kinds_of(config)):
            out = family._ref_block(x, lp, config, kind)
            x = out["after"]
            if "top_e" in out:
                want.append(jnp.sum(out["top_e"] < 4))
        return want

    np.testing.assert_array_equal(rows, counted(params, tokens))
    assert 0 < rows.min() and rows.max() < tokens.size * 2


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    # a fresh function: traced again whatever the earlier tests traced
    jax.eval_shape(lambda p, t: fam.loss_fn(p, t), params, tokens)
    g = trace.gauges()
    assert (g["dsa.topk"], g["dsa.index_heads"], g["dsa.kernel"]) == (
        16, 4, 0)
    assert (g["attn.heads_held"], g["attn.heads"]) == (2, 4)
    assert (g["attn.swa_heads_held"], g["attn.swa_heads"]) == (1, 2)
    assert (g["attn.window"], g["attn.window_layers"],
            g["attn.full_layers"]) == (9, 3, 2)
    assert (g["layers.period"], g["layers.dense"]) == (4, 1)
    assert trace.text("layers.pattern") == "fFSSS"
    assert {"dsa_index", "dsa_select", "dsa_loss", "attn_gate",
            "mla_proj"} <= set(trace.scopes())


def _kernel_calls(jaxpr, found=None):
    """The Pallas calls of a jaxpr and of every jaxpr inside it, by the
    kernel's ``name=``."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


@pytest.mark.parametrize("kind,layer,want", [
    ("F", 1, {"dsa_index_fwd": 1, "dsa_select": 1, "dsa_probs": 1,
              "dsa_index_bwd": 1,
              "attention_fwd_sel": 1,
              "attention_bwd_dq_sel": 1, "attention_bwd_dkv_sel": 1}),
    ("S", 2, {"attention_fwd_swa": 1, "attention_bwd_dq_swa": 1,
              "attention_bwd_dkv_swa": 1})])
def test_a_recomputed_full_block_runs_the_loss_and_the_scores_once(
        built, monkeypatch, kind, layer, want):
    """The kernels a block's gradient calls under remat, checkpoint's
    partial evaluation done (it has dropped from the recomputed forward
    what the backward does not read). Until PR 43 a full block called
    ``dsa_index_fwd`` 2 and ``dsa_probs`` 2 times: the KL's autodiff read
    ``log_softmax(scores)`` and ``probs`` again. Until PR 46 the flash
    forward was called twice in either kind of block: its output and
    ``lse`` are the backward's, and the block keeps them now. The
    threshold's kernel (PR 55) runs once: the block keeps its mask."""
    fam, params, _ = built
    cfg = dataclasses.replace(fam.cfg, remat=True)
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))
    x = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    fn = dots3._block_fn(cfg, None, kind, positions)
    trace.gauge("dsa.loss_grad_kept", 0)
    trace.gauge("attn.out_kept", 0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda lp, x: sum(jnp.sum(out) for out in fn(lp, x)),
        argnums=(0, 1)))(dots3.layer_params(cfg, params, layer), x)
    assert dict(_kernel_calls(jaxpr.jaxpr)) == want
    assert trace.gauges()["dsa.loss_grad_kept"] == (kind == "F")
    assert trace.gauges()["attn.out_kept"] == 1


def test_remat_changes_no_gradient_and_the_gauge_says_what_was_kept(built):
    """Every leaf's gradient, the indexer's three included, with the full
    blocks keeping the mask and ``d L_I / d scores`` against the same
    model with nothing recomputed."""
    fam, params, tokens = built

    def value_and_grads(remat):
        cfg = dataclasses.replace(fam.cfg, remat=remat)
        out = jax.jit(jax.value_and_grad(
            lambda p: dots3.loss_fn(p, tokens, cfg, None)))(params)
        return out, trace.gauges()["dsa.loss_grad_kept"]

    (want, want_grads), kept = value_and_grads(False)
    assert kept == 0
    (got, grads), kept = value_and_grads(True)
    assert kept == 1
    np.testing.assert_allclose(got, want, rtol=1e-6)
    _assert_grads_agree(grads, want_grads, tol=1e-5)
    moved = [jax.tree_util.keystr(path) for path, leaf
             in jax.tree_util.tree_flatten_with_path(grads)[0]
             if _is_indexer(path) and np.asarray(leaf).any()]
    assert len(moved) == 2 * len(dots3.INDEXER), moved
    # a forward alone keeps nothing
    jax.eval_shape(lambda p: dots3.loss_fn(
        p, tokens, dataclasses.replace(fam.cfg, remat=True), None), params)
    assert trace.gauges()["dsa.loss_grad_kept"] == 0
