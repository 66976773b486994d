"""The kimi_linear family layer by layer (see ``test_kimi_linear.py``): the
program follows each term of the configuration; the layer pattern; the
two attention kinds against the plain form; the share of the experts
tied to the uncut layer; sizes, gauges, meshes and the trainer."""

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_linear as family
from dlrover_tpu.models import kimi_linear, moe, xing4
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.kimi_linear_family import (  # noqa: F401  (fixtures by import)
    _plain_loss, _weighty, built, config, kda_form, mesh)


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 1), ("moe_renormalize", False),
    ("num_shared_experts", 0), ("first_k_dense_replace", 2),
])
def test_program_follows_each_config_term(config, mesh, key, value):
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0, 256)
    changed = dict(config, **{key: value})
    fam = family.build(changed, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    got = float(jax.jit(fam.loss_fn)(params, tokens))
    want = _plain_loss(params, tokens, changed)
    assert abs(got - want) < 2e-5, key


# ---------------------------------------------------------------------------
# The layer pattern
# ---------------------------------------------------------------------------

def test_pattern_of_the_published_model_and_the_cut():
    full = kimi_linear.KimiLinearConfig()
    assert full.pattern_string == "KKKL" * 6 + "KKL"
    assert full.pattern[0] == ("kda", "dense")
    assert full.pattern[1] == ("kda", "moe")
    assert full.pattern[3] == ("mla", "moe")
    # 27 layers are 15 loops over three bodies
    assert len(full.runs) == 15
    assert sum(n for _, _, n in full.runs) == 27
    assert len({r[:2] for r in full.runs}) == 3
    cut = kimi_linear.KimiLinearConfig.tiny()
    assert cut.pattern_string == "KKKLK"
    assert cut.runs == (("kda", "dense", 1), ("kda", "moe", 2),
                        ("mla", "moe", 1), ("kda", "moe", 1))


@pytest.mark.parametrize("kda_layers,full_attn_layers", [
    ((1, 2, 3), (4,)),            # layer 5 in neither list
    ((1, 2, 3, 4, 5), (4,)),      # layer 4 in both
    ((1, 2, 3, 5), (6,)),         # a layer the model does not have
])
def test_layer_lists_must_name_each_layer_once(kda_layers, full_attn_layers):
    with pytest.raises(ValueError, match="do not name each of the layers"):
        kimi_linear.KimiLinearConfig.tiny(
            kda_layers=kda_layers, full_attn_layers=full_attn_layers)


def test_a_kkklk_model_is_its_five_blocks_by_hand(built):
    """The scans over the runs are the five blocks applied in the
    pattern's order, each with the kinds the pattern gives it."""
    fam, params, tokens = built
    cfg = fam.cfg
    got = jax.jit(functools.partial(
        kimi_linear.forward_layers, cfg=cfg))(params, tokens)

    @jax.jit
    def by_hand(params, tokens):
        x = params["embed"][tokens].astype(cfg.dtype)
        layer = 0
        for i, (attn, ffn, count) in enumerate(cfg.runs):
            slab = params["runs"][kimi_linear.run_name(i)]
            for j in range(count):
                assert cfg.pattern[layer] == (attn, ffn)
                lp = jax.tree.map(lambda a: a[j], slab)
                assert ("a_log" in lp) == (attn == "kda")
                assert ("w_kva" in lp) == (attn == "mla")
                assert ("router" in lp) == (ffn == "moe")
                x = kimi_linear.block(cfg, None, attn, ffn, lp, x)
                layer += 1
        assert layer == cfg.n_layers == 5
        return x

    x = by_hand(params, tokens)
    np.testing.assert_allclose(got, x, atol=1e-5, rtol=1e-5)
    # and the order matters: the latent layer moved to the end is another
    # model
    moved = dataclasses.replace(
        cfg, kda_layers=(1, 2, 3, 4), full_attn_layers=(5,))
    assert moved.pattern_string == "KKKKL"


@pytest.mark.parametrize("pattern", ["LK", "KLLK", "KKKLKKKL"])
def test_other_patterns_run_and_match_the_plain_form(config, mesh, pattern):
    changed = copy.deepcopy(config)
    changed["num_hidden_layers"] = len(pattern)
    changed["linear_attn_config"]["kda_layers"] = [
        i + 1 for i, c in enumerate(pattern) if c == "K"]
    changed["linear_attn_config"]["full_attn_layers"] = [
        i + 1 for i, c in enumerate(pattern) if c == "L"]
    fam = family.build(changed, mesh)
    assert fam.cfg.pattern_string == pattern
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(jax.random.key(4), (2, 32), 0, 256)
    got = float(jax.jit(fam.loss_fn)(params, tokens))
    assert abs(got - _plain_loss(params, tokens, changed)) < 2e-5


# ---------------------------------------------------------------------------
# The two attention kinds
# ---------------------------------------------------------------------------

def test_kda_layer_matches_the_token_by_token_form(built, config, kda_form):
    fam, params, _ = built
    lp = jax.tree.map(lambda a: a[0], params["runs"][kimi_linear.run_name(1)])
    y = jax.random.normal(jax.random.key(8), (2, 48, fam.cfg.dim))
    got = jax.jit(lambda lp, y: kimi_linear.kda_attention(
        fam.cfg, lp, y))(lp, y)
    assert trace.gauges()["kda.io_fused"] == (kda_form == "kernels")
    want = jax.jit(functools.partial(family._ref_kda, config=config))(y, lp)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    # the decay really is per channel and inside (0, 1)
    g = jax.jit(functools.partial(kimi_linear.kda_inputs, fam.cfg))(lp, y)[3]
    assert g.shape == (2, 48, fam.cfg.kda_heads, fam.cfg.kda_head_dim)
    assert float(jnp.max(g)) < 0.0
    assert float(jnp.std(g[0, 0, 0])) > 0.0


def test_a_recomputed_kda_block_keeps_the_rules_output_and_states(
        built, kda_form):
    """The family's own recompute keeps what the rule's forward kernel
    leaves its backward, the float32 state a chunk among it (gauge
    ``kda.state_kept``), where the kernels run; the XLA form names
    nothing and is recomputed whole. (That loss and gradients are the
    un-kept block's bit for bit: ``tests/test_kda_kept.py``.)"""
    from jax._src.ad_checkpoint import saved_residuals

    fam, params, _ = built
    cfg = dataclasses.replace(fam.cfg, remat=True)
    assert cfg.pattern[1] == ("kda", "moe")
    lp = jax.tree.map(lambda a: a[0], params["runs"][kimi_linear.run_name(1)])
    x = jax.random.normal(jax.random.key(8), (1, 32, cfg.dim))
    trace.gauge("kda.state_kept", 0)
    states = [aval for aval, why in saved_residuals(
        kimi_linear._block_fn(cfg, None, "kda", "moe"), lp, x)
        if "named 'delta_states'" in why]
    kernels = kda_form == "kernels"
    assert len(states) == kernels
    assert trace.gauges()["kda.state_kept"] == kernels
    if kernels:  # (b, h, chunks of a padded tile, dv, dk)
        assert states[0].dtype == jnp.float32 and states[0].shape[-2:] == (
            cfg.kda_head_dim, cfg.kda_head_dim)


def test_latent_attention_without_q_rank_or_rotary(built, config):
    """``xing4.latent_attention`` with one q matrix and no rotary is the
    function given a rotary of angle zero, and the plain form."""
    fam, params, tokens = built
    cfg = fam.cfg
    lp = jax.tree.map(lambda a: a[0], params["runs"][kimi_linear.run_name(2)])
    assert "w_qa" not in lp and "w_q" in lp
    y = jax.random.normal(jax.random.key(8), (2, 48, cfg.dim))

    def latent(cfg, positions, inv_freq):
        return jax.jit(lambda lp, y: xing4.latent_attention(
            cfg, None, positions, inv_freq, lp, y))(lp, y)

    bare = latent(cfg, None, None)
    positions = jnp.broadcast_to(jnp.arange(48, dtype=jnp.int32), (2, 48))

    class WithRotary(type(cfg)):
        rope_magnitude = 1.0        # what a rotary reads beside its table

    rotary_cfg = WithRotary(**{
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})
    identity = latent(
        rotary_cfg, positions, jnp.zeros((cfg.qk_rope_dim // 2,)))
    np.testing.assert_allclose(bare, identity, atol=1e-6)
    want = jax.jit(functools.partial(
        family._ref_latent, config=config))(y, lp)
    np.testing.assert_allclose(bare, want, atol=2e-5, rtol=2e-4)
    # a rotary that turns does change it: the 64 channels are read
    turned = latent(
        rotary_cfg, positions, jnp.full((cfg.qk_rope_dim // 2,), 0.3))
    assert float(jnp.max(jnp.abs(turned - bare))) > 1e-3


# ---------------------------------------------------------------------------
# The share tied to the model
# ---------------------------------------------------------------------------

def test_the_eight_shares_add_up(config, mesh):
    """Eight chips share a layer's 16 experts, two each. The routed parts
    the eight shares compute, plus the shared expert once, are the uncut
    layer of the plain form."""
    whole_cfg = dict(config, num_experts=16, published_num_experts=16,
                     num_experts_per_token=4)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = jax.tree.map(lambda a: a[0], params["runs"][kimi_linear.run_name(1)])
    y = jax.random.normal(jax.random.key(2), (2, 24, whole.cfg.dim))

    def ref_layer(lp, ref_cfg):
        return jax.jit(functools.partial(
            family._ref_expert_layer, config=ref_cfg))(y, lp)[0]

    want = ref_layer(lp, whole_cfg)
    shared = jax.jit(moe._shared_expert)(lp, y)
    total = shared
    for first in range(0, 16, 2):
        share = {k: v for k, v in lp.items() if not k.startswith("ws_")}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out, _ = jax.jit(functools.partial(moe.moe_mlp, share_cfg))(share, y)
        total = total + out
        # and one share alone is the plain form's share
        ref_share = ref_layer(
            dict(share, ws_gate=lp["ws_gate"], ws_up=lp["ws_up"],
                 ws_down=lp["ws_down"]),
            dict(whole_cfg, num_experts=2, first_expert=first))
        np.testing.assert_allclose(out + shared, ref_share, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2   # experts weigh


# ---------------------------------------------------------------------------
# Sizes, gauges, meshes, the trainer
# ---------------------------------------------------------------------------

def test_param_count_of_the_published_model_and_the_cut():
    # ISSUE 33's arithmetic: KDA 39.5 M, latent 29.1 M, a KDA expert
    # layer 273.7 M at 32 held, the cut 1.282 B
    assert kimi_linear.param_count(
        kimi_linear.KimiLinearConfig()) == pytest.approx(49.12e9, rel=1e-3)
    cut = dict(vocab_size=20480, n_layers=5, kda_layers=(1, 2, 3, 5),
               full_attn_layers=(4,))
    n32 = kimi_linear.param_count(
        kimi_linear.KimiLinearConfig(experts_held=32, **cut))
    n16 = kimi_linear.param_count(
        kimi_linear.KimiLinearConfig(experts_held=16, **cut))
    assert n32 == pytest.approx(1.2819e9, rel=1e-4)
    assert n32 - n16 == 4 * 16 * 3 * 2304 * 1024


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["kda.layers"] == 4 and g["kda.heads"] == 4
    assert g["kda.head_dim"] == 16 and g["kda.chunk"] == 16
    assert g["kda.conv"] == 4
    assert g["kda.state_kept"] == 0  # the tiny build recomputes nothing
    assert g["mla.rotary"] == 0 and g["mla.q_rank"] == 0
    assert g["mla.qk_head_dim"] == 24 and g["mla.kv_lora_rank"] == 16
    assert g["attn.scale"] == pytest.approx(24 ** -0.5)
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 4
    assert g["moe.rows_held"] == 2 * 48 * 2 * 4 / 8
    assert g["moe.shared_experts"] == 1
    assert trace.text("layers.pattern") == "KKKLK"


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_mesh_axes_it_cannot_run_are_refused(axis):
    cfg = kimi_linear.KimiLinearConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1)
    sizes[axis] = 2
    mc = MeshConfig(**sizes).resolve(2)
    with pytest.raises(ValueError, match="recurrent state"):
        kimi_linear.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


def test_experts_held_must_divide_over_ep():
    cfg = kimi_linear.KimiLinearConfig.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        kimi_linear.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


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
