"""The xing4 family layer by layer (see ``test_xing4.py``): the program
follows each term of the configuration; the share of the experts tied to
the uncut layer; the stream coefficients and their mixing against the
plain form; the yarn frequencies; sizes, gauges, meshes and the
trainer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import xing4 as family
from dlrover_tpu.models import moe, xing4
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import rope_frequencies, yarn_frequencies
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.xing4_family import (  # noqa: F401  (fixtures by import)
    _weighty, built, config, mesh)


def test_program_follows_each_config_term(config, mesh):
    """The same switches reach the program: the scaling factor, the
    clamp, unnormalised weights, a rotary magnitude other than one
    (mscale apart from mscale_all_dim) and a model without its
    multi-token module each give the plain form's loss."""
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0, 256)
    magnitude = dict(config["rope_scaling"], mscale=0.5)
    for key, value in (("routed_scaling_factor", 1),
                       ("mhc_h_res_clamp_max", 0.5),
                       ("norm_topk_prob", False),
                       ("rope_scaling", magnitude),
                       ("num_nextn_predict_layers", 0)):
        changed = dict(config, **{key: value})
        fam = family.build(changed, mesh)
        params = _weighty(fam.init_params(jax.random.key(3)))
        got = float(jax.jit(fam.loss_fn)(params, tokens))
        want = float(family.plain_loss(params, tokens, changed))
        assert abs(got - want) < 2e-5, key


def test_the_shares_add_up(config, mesh):
    """Two chips share the tiny layer's 8 experts. The routed parts the
    two shares compute, plus the shared expert once, are the uncut
    layer of the plain form."""
    cfg = family.build(config, mesh).cfg
    whole_cfg = dict(config, n_routed_experts=8)
    whole = xing4.init_params(
        family.build(whole_cfg, mesh).cfg, jax.random.key(1))
    lp = jax.tree.map(lambda a: a[0], _weighty(whole)["layers"])
    y = jax.random.normal(jax.random.key(2), (2, 24, cfg.dim))

    def ref_layer(lp, ref_cfg):
        return jax.jit(functools.partial(
            family._ref_expert_layer, config=ref_cfg))(y, lp)[0]

    want = ref_layer(lp, whole_cfg)
    shared = jax.jit(moe._shared_expert)(lp, y)
    total = shared
    for first in (0, 4):
        share = {k: v for k, v in lp.items() if not k.startswith("ws_")}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 4]
        share_cfg = dataclasses.replace(cfg, first_expert=first).as_moe()
        out, _ = jax.jit(functools.partial(moe.moe_mlp, share_cfg))(share, y)
        total = total + out
        # and one share alone is the plain form's share
        ref_share = ref_layer(
            dict(share, ws_gate=lp["ws_gate"], ws_up=lp["ws_up"],
                 ws_down=lp["ws_down"]),
            dict(config, first_expert=first))
        np.testing.assert_allclose(out + shared, ref_share, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2   # experts weigh


def test_h_res_is_doubly_stochastic_after_20_rounds():
    cfg = xing4.Xing4Config.tiny()
    n, d = cfg.hc_mult, cfg.dim
    X = jax.random.normal(jax.random.key(0), (n, 2, 16, d))
    phi = jax.random.normal(jax.random.key(1), (n, d, cfg.hc_width)) * 0.05
    bias = jax.random.normal(jax.random.key(2), (cfg.hc_width,))
    coefficients = jax.jit(functools.partial(xing4.hc_coefficients, cfg))
    h_pre, h_post, h_res = coefficients(phi, jnp.ones((3,)), bias, X)
    assert h_res.shape == (n, n, 2, 16)
    assert float(jnp.min(h_res)) > 0.0
    np.testing.assert_allclose(jnp.sum(h_res, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(h_res, axis=0), 1.0, atol=1e-5)
    assert float(jnp.max(h_pre)) < 1.0 and float(jnp.max(h_post)) < 2.0
    # at init (6 I + small) rows are exact; near a permutation the
    # columns close slowly, and 20 rounds are what the config states
    h_res = coefficients(
        phi, jnp.full((3,), 0.01), xing4.hc_bias_init(n), X)[2]
    np.testing.assert_allclose(jnp.sum(h_res, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(h_res, axis=0), 1.0, atol=5e-3)
    assert float(jnp.min(jnp.diagonal(h_res))) > 0.97


def test_coefficients_match_the_plain_form(config, mesh):
    cfg = family.build(config, mesh).cfg
    n, d = cfg.hc_mult, cfg.dim
    X = jax.random.normal(jax.random.key(0), (n, 2, 16, d))
    phi = jax.random.normal(jax.random.key(1), (n, d, cfg.hc_width)) * 0.05
    alpha = jnp.asarray([0.5, 0.8, 1.1])
    bias = jax.random.normal(jax.random.key(2), (cfg.hc_width,))
    h_pre, h_post, h_res = jax.jit(functools.partial(
        xing4.hc_coefficients, cfg))(phi, alpha, bias, X)
    r_pre, r_post, r_res = jax.jit(functools.partial(
        family.ref_hc_coefficients, config=config))(X, phi, alpha, bias)
    np.testing.assert_allclose(jnp.moveaxis(h_pre, 0, -1), r_pre, atol=1e-6)
    np.testing.assert_allclose(jnp.moveaxis(h_post, 0, -1), r_post, atol=1e-6)
    np.testing.assert_allclose(
        jnp.moveaxis(h_res, (0, 1), (-2, -1)), r_res, atol=1e-6)


def test_mixing_gradient_against_the_plain_form(config, mesh):
    cfg = family.build(config, mesh).cfg
    n, d = cfg.hc_mult, cfg.dim
    keys = jax.random.split(jax.random.key(7), 5)
    X = jax.random.normal(keys[0], (n, 2, 16, d))
    lp = {"hc_phi": jax.random.normal(keys[1], (n, d, cfg.hc_width)) * 0.05,
          "hc_alpha": jnp.asarray([0.5, 0.8, 1.1]),
          "hc_bias": jax.random.normal(keys[2], (cfg.hc_width,))}
    w = jax.random.normal(keys[3], (d, d)) * 0.1
    g = jax.random.normal(keys[4], (n, 2, 16, d))
    fn = lambda y: jnp.tanh(y @ w)

    def program(X, lp):
        return jnp.sum(xing4.hc_sublayer(cfg, lp, "hc", X, fn) * g)

    def plain(X, lp):
        return jnp.sum(family._ref_sublayer(X, lp, "hc", config, fn) * g)

    got = jax.jit(jax.grad(program, argnums=(0, 1)))(X, lp)
    want = jax.jit(jax.grad(plain, argnums=(0, 1)))(X, lp)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)


def test_yarn_frequencies_against_the_closed_form():
    got = np.asarray(yarn_frequencies(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    want = family.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(rope_frequencies(64, 10000.0))
    # low, high = floor, ceil of 64 ln(4096 / (beta 2 pi)) / (2 ln 1e4):
    # 10.47 -> 10 and 22.51 -> 23
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64.0, rtol=1e-6)
    assert np.all(np.diff(got / plain)[10:23] < 0)
    # the published temperature: 192^-0.5 (0.1 ln 64 + 1)^2
    assert xing4.Xing4Config().softmax_scale == pytest.approx(
        192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    assert xing4.Xing4Config().rope_magnitude == 1.0


def test_param_count_of_the_published_model_and_the_cut():
    # ISSUE 31's arithmetic: attention 28.41 M a block, 704.6 M of routed
    # experts a layer, 128.4 M a held expert layer
    full = xing4.param_count(xing4.Xing4Config())
    assert full == pytest.approx(30.28e9, rel=1e-3)
    cut = dict(vocab_size=16384, n_dense_layers=1, experts_held=8)
    n5 = xing4.param_count(xing4.Xing4Config(n_moe_layers=5, **cut))
    n6 = xing4.param_count(xing4.Xing4Config(n_moe_layers=6, **cut))
    assert n6 == pytest.approx(1.170e9, rel=1e-3)
    assert n6 - n5 == pytest.approx(128.4e6, rel=1e-3)


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["mla.qk_head_dim"] == 24 and g["mla.v_head_dim"] == 16
    assert g["mla.q_lora_rank"] == 24 and g["mla.kv_lora_rank"] == 16
    assert g["attn.scale"] == pytest.approx(fam.cfg.softmax_scale)
    assert g["hc.streams"] == 4 and g["hc.sinkhorn_iters"] == 20
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 4
    assert g["moe.rows_held"] == 2 * 48 * 2 * 4 / 8
    assert g["moe.tail_rows"] == 2 * 48 * 2 - g["moe.rows_held"]
    assert g["moe.shared_experts"] == 1
    assert g["mtp.depth"] == 1 and g["mtp.loss_weight"] == 0.3


def test_mesh_axes_it_cannot_run_are_refused():
    cfg = xing4.Xing4Config.tiny()
    mc = MeshConfig(dp=1, fsdp=1, ep=1, sp=2, tp=1).resolve(2)
    with pytest.raises(ValueError, match="latent attention"):
        xing4.validate_for_mesh(cfg, build_mesh(mc, jax.devices()[:2]), 2)


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
