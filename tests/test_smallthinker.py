"""The smallthinker family (``models/smallthinker.py``) at a tiny size on
the CPU against the plain form of its equations (``benchmarks/families/
smallthinker.py``: explicit scores and mask, a loop over the experts);
the layout read from the config's two lists; what the router reads;
ReGLU; the share of the experts tied to the uncut layer. (Sizes, gauges,
meshes and the trainer: ``test_smallthinker_mesh.py``; what the two
share: ``smallthinker_family.py``.)"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import smallthinker as family
from dlrover_tpu.models import moe, smallthinker
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import rms_norm
from tests.smallthinker_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _built, _plain_loss, _weighty, built, config, mesh)


def test_loss_and_gradients_match_the_plain_form(built, config):
    """Two periods FWWW, a window of 16 under 48 positions."""
    fam, params, tokens = built
    assert fam.cfg.pattern_string == "FWWWFWWW" and fam.cfg.window == 16
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    _assert_grads_agree(grads, want_grads)
    # every layer's every leaf weighs
    for slab in grads["layers"].values():
        for name, leaf in slab.items():
            leaf = np.asarray(leaf)
            assert np.abs(leaf).reshape(len(leaf), -1).max(-1).min() > 0.0, (
                name)


@pytest.mark.parametrize("key,value", [
    ("sliding_window_size", 8),                         # another band
    ("sliding_window_size", 64),                        # none that bites
    ("sliding_window_layout", [0] * 8),                 # a full mask
    ("rope_layout", [1] * 8),                           # rotary on NoPE
    ("rope_theta", 100.0), ("norm_topk_prob", False),
    ("rms_norm_eps", 0.1),
])
def test_each_config_term_moves_the_plain_form_and_the_program(
        built, config, mesh, key, value):
    """The plain form under a changed term is another loss, and the
    program built from the changed configuration follows it."""
    fam, params, tokens = built
    changed = dict(config, **{key: value})
    base = _plain_loss(params, tokens, config)
    want = _plain_loss(params, tokens, changed)
    assert abs(want - base) > 1e-4, (key, base, want)
    got = float(jax.jit(family.build(changed, mesh).loss_fn)(params, tokens))
    assert abs(got - want) < 2e-5


@pytest.mark.parametrize("rope,window", [
    ([0, 1, 1, 1, 0, 1, 1, 1], [0, 0, 1, 1, 0, 0, 1, 1]),   # they differ
    ([1, 0] * 4, [0, 1] * 4),                                # period 2
    ([0, 1, 1, 1, 1, 1, 1, 0], [0, 1, 1, 0, 1, 1, 1, 1]),   # no period
])
def test_the_layout_is_read_from_the_two_lists(config, mesh, rope, window):
    changed = dict(config, rope_layout=rope, sliding_window_layout=window)
    fam, params, tokens = _built(changed, mesh)
    assert fam.cfg.kinds == tuple(
        (bool(r), 16 if w else None) for r, w in zip(rope, window))
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, changed)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    _assert_grads_agree(grads, want_grads)


def test_pattern_period_and_letters():
    cfg = smallthinker.SmallThinkerConfig()
    assert cfg.period == 4 and cfg.pattern_string == "FWWW" * 13
    assert cfg.kinds[0] == (False, None) and cfg.kinds[1] == (True, 4096)
    tiny = smallthinker.SmallThinkerConfig.tiny
    assert tiny().period == 4
    mixed = tiny(rope_layout=(0, 1, 0, 1) * 2,
                 window_layout=(0, 0, 1, 1) * 2)
    assert mixed.pattern_string == "FRVW" * 2 and mixed.period == 4
    assert tiny(rope_layout=(1,) * 8, window_layout=(1,) * 8).period == 1
    assert tiny(rope_layout=(0,) + (1,) * 7).period == 8
    with pytest.raises(ValueError, match="rope_layout"):
        tiny(rope_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="window_layout"):
        tiny(window_layout=(0, 2, 1, 1) * 2)


def test_params_are_dealt_out_to_the_periods_positions(built):
    fam, params, _ = built
    assert sorted(params["layers"]) == ["pos0", "pos1", "pos2", "pos3"]
    whole = moe.init_params(fam.cfg.as_moe(), jax.random.key(3))["layers"]
    fresh = fam.init_params(jax.random.key(3))
    for layer in (0, 3, 5):
        lp = smallthinker.layer_params(fam.cfg, fresh, layer)
        for name, leaf in lp.items():
            # (one is made under jit, the other eagerly: an ulp apart)
            np.testing.assert_allclose(
                leaf, whole[name][layer], rtol=1e-5, atol=1e-8)
    # and the reference reads them in layer order
    for layer, lp in enumerate(family.layers_of(fresh)):
        np.testing.assert_allclose(
            lp["wq"], whole["wq"][layer], rtol=1e-5, atol=1e-8)


def _one_layer(built):
    fam, params, tokens = built
    lp = smallthinker.layer_params(fam.cfg, params, 1)
    x = params["embed"][tokens]
    return fam.cfg, lp, x


def test_the_router_reads_the_attentions_input(built, config):
    """With ``wo`` zero attention adds nothing, so ``attn_norm`` reaches
    the loss through the router alone: its gradient is there, and is the
    plain form's; ``mlp_norm``'s is the experts' alone, as if the
    router's weights were constants."""
    cfg, lp, x = _one_layer(built)
    lp = dict(lp, wo=jnp.zeros_like(lp["wo"]))
    w = jax.random.normal(jax.random.key(6), x.shape)

    def program(lp):
        return jnp.sum(smallthinker.block(cfg, None, True, 16, lp, x) * w)

    def plain(lp, frozen_router=False):
        eps = float(config["rms_norm_eps"])
        y = family._rms_norm(x, lp["attn_norm"], eps)
        u = family._rms_norm(x, lp["mlp_norm"], eps)
        if frozen_router:
            y = jax.lax.stop_gradient(y)
        return jnp.sum((x + family._ref_expert_layer(y, u, lp, config)[0]) * w)

    got = jax.jit(jax.grad(program))(lp)
    want = jax.jit(jax.grad(plain))(lp)
    frozen = jax.jit(jax.grad(lambda lp: plain(lp, True)))(lp)
    assert float(jnp.max(jnp.abs(got["attn_norm"]))) > 1e-3
    np.testing.assert_allclose(got["attn_norm"], want["attn_norm"],
                               atol=1e-5, rtol=1e-4)
    assert float(jnp.max(jnp.abs(frozen["attn_norm"]))) == 0.0
    np.testing.assert_allclose(got["mlp_norm"], frozen["mlp_norm"],
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(got["router"], want["router"],
                               atol=1e-5, rtol=1e-4)
    # routing on u, as the other families do, is another function
    @jax.jit
    def routed(lp):
        u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        return (moe.moe_mlp(cfg.as_moe(), lp, u, route_on=y)[0],
                moe.moe_mlp(cfg.as_moe(), lp, u)[0],
                family._ref_expert_layer(y, u, lp, config)[0])

    on_y, on_u, ref = routed(lp)
    assert float(jnp.max(jnp.abs(on_y - on_u))) > 1e-2
    np.testing.assert_allclose(on_y, ref, atol=2e-5)


def test_the_experts_are_reglu(built, config):
    cfg, lp, x = _one_layer(built)
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    mcfg = cfg.as_moe()
    assert mcfg.expert_act == "relu"
    relu, silu, ref = jax.jit(lambda lp, y: (
        moe.moe_mlp(mcfg, lp, y)[0],
        moe.moe_mlp(dataclasses.replace(mcfg, expert_act="silu"), lp, y)[0],
        family._ref_expert_layer(y, y, lp, config)[0]))(lp, y)
    np.testing.assert_allclose(relu, ref, atol=2e-5)
    assert float(jnp.max(jnp.abs(relu - silu))) > 1e-2
    with pytest.raises(ValueError, match="expert_act"):
        moe.moe_mlp(dataclasses.replace(mcfg, expert_act="gelu"), lp, y)


@pytest.mark.parametrize("layer,kind", [(0, (False, None)), (1, (True, 16))])
def test_a_recomputed_block_keeps_the_flash_forwards_pair(built, layer, kind):
    """A full and a window block under the family's own recompute keep
    the flash forward's output and ``lse`` and none of q, k, v (gauge
    ``attn.out_kept``); loss and gradients are the block's own."""
    from jax._src.ad_checkpoint import saved_residuals

    fam, params, tokens = built
    cfg = dataclasses.replace(fam.cfg, remat=True)
    assert cfg.kinds[layer] == kind
    lp = smallthinker.layer_params(cfg, params, layer)
    x = params["embed"][tokens]
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    trace.gauge("attn.out_kept", 0)
    fn = smallthinker._block_fn(cfg, None, *kind)
    saved = [tuple(aval.shape) for aval, _ in saved_residuals(fn, lp, x)]
    assert (b, s, h, hd) in saved and (b, h, s) in saved
    assert (b, s, kvh, hd) not in saved and saved.count((b, s, h, hd)) == 1

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda lp, x: jnp.sum(fn(lp, x) ** 2), argnums=(0, 1)))(lp, x)

    got = grads(fn)
    assert trace.gauges()["attn.out_kept"] == 1
    want = grads(functools.partial(smallthinker.block, cfg, None, *kind))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_four_shares_add_up(config, mesh):
    """Four chips share a layer's 16 experts, four each. The routed parts
    the four shares compute are the uncut layer of the plain form."""
    whole_cfg = dict(config, moe_num_primary_experts=16,
                     published_moe_num_primary_experts=16,
                     moe_num_active_primary_experts=6)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = smallthinker.layer_params(whole.cfg, params, 0)
    y = jax.random.normal(jax.random.key(2), (2, 24, whole.cfg.dim))
    u = jax.random.normal(jax.random.key(3), (2, 24, whole.cfg.dim))

    def ref_layer(lp, ref_cfg):
        return jax.jit(lambda lp: family._ref_expert_layer(
            y, u, lp, ref_cfg)[0])(lp)

    want = ref_layer(lp, whole_cfg)

    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = dict(lp)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 4]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=4, first_expert=first).as_moe()
        out = jax.jit(lambda share: moe.moe_mlp(
            share_cfg, share, u, route_on=y)[0])(share)
        total = total + out
        # and one share alone is the plain form's share
        ref_share = ref_layer(share, dict(
            whole_cfg, moe_num_primary_experts=4, first_expert=first))
        np.testing.assert_allclose(out, ref_share, atol=2e-5)
        assert float(jnp.max(jnp.abs(out))) > 1e-2     # each share weighs
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_live_rows_count_the_pairs_that_chose_a_held_expert(built):
    """``live_rows`` against the count layer by layer: the router on the
    attention's input, the residual carried through the whole block."""
    fam, params, tokens = built
    cfg = fam.cfg
    got = np.asarray(jax.jit(
        lambda p, t: smallthinker.live_rows(p, t, cfg))(params, tokens))

    @jax.jit
    def counted(params, tokens):
        x = params["embed"][tokens].astype(cfg.dtype)
        want = []
        for l, kind in enumerate(cfg.kinds):
            lp = smallthinker.layer_params(cfg, params, l)
            y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            top_e = moe.route(cfg.as_moe(), lp["router"],
                              y.reshape(-1, cfg.dim))[2]
            want.append(jnp.sum(top_e < cfg.experts_held))
            x = smallthinker.block(cfg, None, *kind, lp, x)
        return want

    want = [int(n) for n in counted(params, tokens)]
    assert got.tolist() == want
    assert got.dtype == np.int32 and got.shape == (cfg.n_layers,)
    # not the uniform expectation the gauge moe.rows_held gives
    pairs = tokens.size * cfg.experts_per_token
    assert 0 < got.min() and got.max() < pairs and len(set(want)) > 1


def test_first_layers_live_rows_of_the_shares_are_every_pair(built):
    """Each (token, choice) pair of the first layer, whose input is the
    same on every chip that shares it, is live on exactly one of them."""
    fam, params, tokens = built
    first_layer = sum(
        int(jax.jit(functools.partial(
            smallthinker.live_rows, cfg=dataclasses.replace(
                fam.cfg, first_expert=first)))(params, tokens)[0])
        for first in range(0, fam.cfg.n_experts, fam.cfg.experts_held))
    assert first_layer == tokens.size * fam.cfg.experts_per_token
