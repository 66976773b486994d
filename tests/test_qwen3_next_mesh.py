"""The qwen3_next family's sizes, gauges, meshes and trainer (see
``test_qwen3_next.py``): the published model's and the cut's parameter
counts; the init the configuration states; what the build's gauges say;
what ``validate_for_mesh`` and the configuration refuse; three steps
through the trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import qwen3_next as family
from dlrover_tpu.models import qwen3_next
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.qwen3_next_family import (  # noqa: F401  (fixtures by import)
    built, config, mesh)


# ---------------------------------------------------------------------------
# Sizes, gauges, meshes, the trainer
# ---------------------------------------------------------------------------

def test_param_count_of_the_published_model_and_the_cut():
    # ISSUE 45's arithmetic: a Gated DeltaNet mixer 33.72 M, a gated
    # attention mixer 27.26 M, router + shared expert + gate 4.20 M, an
    # expert 3.146 M; the cut 1.1735 B, the whole model 79.67 B
    gdn = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    gattn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    rest = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048   # + the two norms
    assert gdn == 33_718_464 and gattn == 27_263_488
    expert = 3 * 2048 * 512
    whole = (36 * gdn + 12 * gattn + 48 * (rest + 512 * expert)
             + 2 * 151936 * 2048 + 2048)
    assert qwen3_next.param_count(qwen3_next.Qwen3NextConfig()) == whole
    assert whole == pytest.approx(79.67e9, rel=1e-3)
    cut = dict(vocab_size=18992, n_layers=8)
    n32 = qwen3_next.param_count(
        qwen3_next.Qwen3NextConfig(experts_held=32, **cut))
    n16 = qwen3_next.param_count(
        qwen3_next.Qwen3NextConfig(experts_held=16, **cut))
    assert n32 == 1_173_540_992
    assert n32 == (6 * gdn + 2 * gattn + 8 * (rest + 32 * expert)
                   + 2 * 18992 * 2048 + 2048)
    assert n32 - n16 == 8 * 16 * expert


def test_init_follows_the_configuration(config, mesh):
    fam = family.build(dict(config, assumed=dict(
        config["assumed"], out_proj_std=1e-4)), mesh)
    params = fam.init_params(jax.random.key(0))
    g, f = params["layers"]["pos0"], params["layers"]["pos3"]
    for slab in (g, f):
        for name in ("w_o", "w_down", "ws_down"):
            assert float(jnp.std(slab[name])) == pytest.approx(1e-4, rel=0.2)
        assert float(jnp.std(slab["router"])) == pytest.approx(0.02, rel=0.2)
        for name in ("attn_norm", "mlp_norm"):
            assert float(jnp.max(jnp.abs(slab[name]))) == 0.0
    assert float(jnp.max(jnp.abs(params["final_norm"]))) == 0.0
    assert float(jnp.min(g["dt_bias"])) == float(jnp.max(g["o_norm"])) == 1.0
    a = jnp.exp(g["a_log"])
    assert 0.0 < float(jnp.min(a)) and float(jnp.max(a)) <= 16.0
    assert float(jnp.max(jnp.abs(f["q_norm"]))) == 0.0


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["attn.gdn_layers"] == 6 and g["attn.full_layers"] == 2
    assert g["attn.gdn_key_heads"] == 2 and g["attn.gdn_value_heads"] == 4
    assert g["attn.gdn_chunk"] == 16 and g["attn.gdn_kernel"] == 0
    assert g["attn.rotary_dim"] == 8 and g["attn.group"] == 2
    assert g["layers.period"] == 4
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 2
    assert g["moe.rows_held"] == 2 * 48 * 2 * 2 / 8
    assert g["moe.shared_experts"] == 1 and g["moe.shared_gate"] == 1
    assert trace.text("layers.pattern") == "GGGFGGGF"


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_mesh_axes_it_cannot_run_are_refused(axis):
    cfg = qwen3_next.Qwen3NextConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1)
    sizes[axis] = 2
    mc = MeshConfig(**sizes).resolve(2)
    with pytest.raises(ValueError, match="recurrent state"):
        qwen3_next.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


def test_experts_held_must_divide_over_ep():
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        qwen3_next.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


def test_value_heads_must_group_over_key_heads():
    with pytest.raises(ValueError, match="do not group"):
        qwen3_next.Qwen3NextConfig.tiny(gdn_value_heads=3)


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
