"""The dots3 family over meshes (see ``test_dots3.py``): the axes it has
no form for are refused; ep and fsdp on CPU devices give one device's
loss and gradients; three steps through the trainer."""

import jax
import numpy as np
import pytest

from benchmarks.families import dots3 as family
from dlrover_tpu.models import dots3
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.dots3_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _built, config, mesh)


@pytest.mark.parametrize("axis,why", [
    ("tp", "no head-sharded form"), ("sp", "neither a selection nor"),
    ("pp", "blocks differ in shape")])
def test_an_axis_the_family_has_no_form_for_is_refused(axis, why):
    cfg = dots3.Dots3Config.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1, pp=1)
    sizes[axis] = 2
    mesh = build_mesh(MeshConfig(**sizes).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match=why):
        dots3.validate_for_mesh(cfg, mesh, batch=2)


def test_experts_held_must_divide_over_ep():
    cfg = dots3.Dots3Config.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        dots3.validate_for_mesh(cfg, build_mesh(mc, jax.devices()[:2]),
                                batch=2)


@pytest.mark.parametrize("held", [
    dict(heads_held=5), dict(heads_held=2, first_head=3),
    dict(swa_heads_held=0)])
def test_held_heads_lie_inside_the_layers_heads(held):
    with pytest.raises(ValueError, match="held of"):
        dots3.Dots3Config.tiny(**held)


def test_the_ep_and_fsdp_paths_on_cpu_devices(config):
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
    assert losses[2] < losses[0] - 0.05 and losses[1] <= losses[0], losses
    assert abs(losses[0] - fam.expected_first_loss) < 0.25
