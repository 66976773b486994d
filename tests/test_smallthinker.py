"""The smallthinker family (``models/smallthinker.py``) at a tiny size on
the CPU against the plain form of its equations (``benchmarks/families/
smallthinker.py``: explicit scores and mask, a loop over the experts);
the layout read from the config's two lists; what the router reads;
ReGLU; the share of the experts tied to the uncut layer; the meshes."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import smallthinker as family
from dlrover_tpu.models import moe, smallthinker
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import rms_norm
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-smallthinker.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one (and the two norms of a layer apart), a
    router that spreads its logits, projections that make attention and
    the experts weigh, so that every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 64))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
        lp["router"] = lp["router"] * 40.0
        lp["wq"] = lp["wq"] * 20.0
        lp["wo"] = lp["wo"] * 40.0
        lp["w_down"] = lp["w_down"] * 120.0
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0,
                layers={k: slab(v) for k, v in params["layers"].items()})


def _built(config, mesh, seq=48):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


@pytest.fixture(scope="module")
def built(config, mesh):
    return _built(config, mesh)


def _assert_grads_agree(grads, want_grads, tol=3e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)   # off their meshes
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        assert err <= tol * scale + 1e-7, (
            jax.tree_util.keystr(path), err, scale)


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
            assert float(jnp.min(jnp.max(jnp.abs(leaf).reshape(
                leaf.shape[0], -1), axis=-1))) > 0.0, name


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
    base = float(family.plain_loss(params, tokens, config))
    want = float(family.plain_loss(params, tokens, changed))
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

    got = jax.grad(program)(lp)
    want = jax.grad(plain)(lp)
    frozen = jax.grad(lambda lp: plain(lp, True))(lp)
    assert float(jnp.max(jnp.abs(got["attn_norm"]))) > 1e-3
    np.testing.assert_allclose(got["attn_norm"], want["attn_norm"],
                               atol=1e-5, rtol=1e-4)
    assert float(jnp.max(jnp.abs(frozen["attn_norm"]))) == 0.0
    np.testing.assert_allclose(got["mlp_norm"], frozen["mlp_norm"],
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(got["router"], want["router"],
                               atol=1e-5, rtol=1e-4)
    # routing on u, as the other families do, is another function
    u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    on_y = moe.moe_mlp(cfg.as_moe(), lp, u, route_on=y)[0]
    on_u = moe.moe_mlp(cfg.as_moe(), lp, u)[0]
    assert float(jnp.max(jnp.abs(on_y - on_u))) > 1e-2
    np.testing.assert_allclose(
        on_y, family._ref_expert_layer(y, u, lp, config)[0], atol=2e-5)


def test_the_experts_are_reglu(built, config):
    cfg, lp, x = _one_layer(built)
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    mcfg = cfg.as_moe()
    assert mcfg.expert_act == "relu"
    relu = moe.moe_mlp(mcfg, lp, y)[0]
    silu = moe.moe_mlp(dataclasses.replace(mcfg, expert_act="silu"), lp, y)[0]
    np.testing.assert_allclose(
        relu, family._ref_expert_layer(y, y, lp, config)[0], atol=2e-5)
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
    want, _ = family._ref_expert_layer(y, u, lp, whole_cfg)

    total = jnp.zeros_like(want)
    for first in range(0, 16, 4):
        share = dict(lp)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 4]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=4, first_expert=first).as_moe()
        out, _ = moe.moe_mlp(share_cfg, share, u, route_on=y)
        total = total + out
        # and one share alone is the plain form's share
        ref_share, _ = family._ref_expert_layer(
            y, u, share, dict(whole_cfg, moe_num_primary_experts=4,
                              first_expert=first))
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
    x = params["embed"][tokens].astype(cfg.dtype)
    want = []
    for l, kind in enumerate(cfg.kinds):
        lp = smallthinker.layer_params(cfg, params, l)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        top_e = moe.route(cfg.as_moe(), lp["router"],
                          y.reshape(-1, cfg.dim))[2]
        want.append(int(jnp.sum(top_e < cfg.experts_held)))
        x = smallthinker.block(cfg, None, *kind, lp, x)
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
        int(smallthinker.live_rows(params, tokens, dataclasses.replace(
            fam.cfg, first_expert=first))[0])
        for first in range(0, fam.cfg.n_experts, fam.cfg.experts_held))
    assert first_layer == tokens.size * fam.cfg.experts_per_token


# ---------------------------------------------------------------------------
# Sizes, gauges, meshes, the trainer
# ---------------------------------------------------------------------------

def test_param_count_of_the_published_model_and_the_cut():
    # ISSUE 37's arithmetic: attention 20.97 M, router 0.164 M, an expert
    # 5.898 M, a layer 398.6 M whole and 115.5 M at 16 held
    layer = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2 * 2560 + 2560 * 64
    assert smallthinker.param_count(smallthinker.SmallThinkerConfig()) == (
        52 * (layer + 64 * 3 * 2560 * 768) + 2 * 151936 * 2560 + 2560)
    cut = smallthinker.SmallThinkerConfig(
        vocab_size=37984, n_layers=8, rope_layout=(0, 1, 1, 1) * 2,
        window_layout=(0, 1, 1, 1) * 2, experts_held=16)
    assert smallthinker.param_count(cut) == (
        8 * (layer + 16 * 3 * 2560 * 768) + 2 * 37984 * 2560 + 2560)
    assert smallthinker.param_count(cut) == pytest.approx(1.1186e9, rel=1e-4)


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["attn.window"] == 16 and g["attn.window_layers"] == 6
    assert g["attn.full_layers"] == 2 and g["attn.rotary_layers"] == 6
    assert g["attn.group"] == 2 and g["layers.period"] == 4
    assert g["attn.out_kept"] == 0  # the tiny build recomputes nothing
    assert g["moe.route_on"] == 1 and g["moe.act"] == 1
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 4
    assert g["moe.rows_held"] == 2 * 48 * 2 * 4 / 8
    assert g["moe.shared_experts"] == 0
    assert trace.text("layers.pattern") == "FWWWFWWW"
    # a family that routes on the experts' input says so
    plain = moe.MoeConfig.tiny()
    lp = jax.tree.map(
        lambda a: a[0], moe.init_params(plain, jax.random.key(0))["layers"])
    moe.moe_mlp(plain, lp, jnp.zeros((1, 8, plain.dim)))
    g = trace.gauges()
    assert g["moe.route_on"] == 0 and g["moe.act"] == 0


def test_the_ep_path_on_cpu_devices(config):
    """The held experts over ep=2 (and fsdp=2 beside it): the loss and
    the gradients of one device."""
    one = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam1, params, _ = _built(config, one)
    tokens = jax.random.randint(jax.random.key(4), (4, 32), 0, 256)
    want, want_grads = jax.jit(jax.value_and_grad(fam1.loss_fn))(
        params, tokens)
    for sizes in (dict(ep=2), dict(ep=2, fsdp=2)):
        n = 2 * sizes.get("fsdp", 1)
        mc = MeshConfig(dp=1, **sizes).resolve(n)
        mesh = build_mesh(mc, devices=jax.devices()[:n])
        fam = family.build(config, mesh)
        placed = jax.device_put(
            params, named_shardings(mesh, fam.param_specs))
        loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(placed, tokens)
        assert abs(float(loss) - float(want)) < 2e-5, sizes
        _assert_grads_agree(grads, want_grads, tol=1e-3)


def test_a_window_over_sp_is_refused():
    cfg = smallthinker.SmallThinkerConfig.tiny()
    mc = MeshConfig(dp=1, fsdp=1, ep=1, sp=2, tp=1).resolve(2)
    mesh = build_mesh(mc, jax.devices()[:2])
    with pytest.raises(ValueError, match="ring and ulysses attention have "
                                         "no window"):
        smallthinker.validate_for_mesh(cfg, mesh, seq_len=32, batch=2)
    # a layout without a window layer is not refused for it
    smallthinker.validate_for_mesh(
        dataclasses.replace(cfg, window_layout=(0,) * 8), mesh, seq_len=32,
        batch=2)


def test_experts_held_must_divide_over_ep():
    cfg = smallthinker.SmallThinkerConfig.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        smallthinker.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), seq_len=32, batch=2)


def test_three_steps_through_the_trainer_with_a_falling_loss(config):
    mc = MeshConfig(dp=-1, fsdp=2).resolve(4)
    mesh = build_mesh(mc, devices=jax.devices()[:4])
    fam = family.build(config, mesh)
    tc = TrainConfig(global_batch_size=4, micro_batch_size=1,
                     learning_rate=3e-3, warmup_steps=1)
    trainer = ElasticTrainer(fam.loss_fn, fam.param_specs, mesh, mc, tc)
    state = trainer.init_state(fam.init_params(jax.random.key(0)))
    accum, per = trainer.step_batch_shape
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (accum, per, 32), 0, 256),
        trainer.batch_sharding)
    losses = []
    for _ in range(3):
        state, loss = trainer.step(state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    # the first update is warm-up's (lr 0): the loss falls from the second
    assert losses[2] < losses[0] - 0.05 and losses[1] <= losses[0], losses
    assert abs(losses[0] - fam.expected_first_loss) < 0.25
