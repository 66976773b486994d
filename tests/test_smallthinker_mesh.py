"""The smallthinker family's sizes, gauges, meshes and trainer (see
``test_smallthinker.py``): the published model's and the cut's parameter
counts; what the build's gauges say; the held experts over ep and fsdp on
CPU devices; what ``validate_for_mesh`` refuses; three steps through the
trainer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import smallthinker as family
from dlrover_tpu.models import moe, smallthinker
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.smallthinker_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _built, built, config, mesh)


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
    jax.jit(functools.partial(moe.moe_mlp, plain))(
        lp, jnp.zeros((1, 8, plain.dim)))
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
