"""The laguna family's sizes, gauges, meshes and trainer (see
``test_laguna.py``): the published model's and the cut's parameter
counts and FLOPs; what the build's gauges say; the held experts over ep
and fsdp on CPU devices; what ``validate_for_mesh`` refuses; three steps
through the trainer."""

import jax
import numpy as np
import pytest

from benchmarks.families import laguna as family
from benchmarks.harness import laguna_flops
from dlrover_tpu.models import laguna
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.laguna_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _built, built, config, load_config, mesh)

# ISSUE 60's arithmetic, at hidden 2048: attention with its gate at 64
# and at 48 heads, a router, an expert (and the shared one), the dense
# SwiGLU, two norms
SLIDING = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
FULL = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
ROUTER, EXPERT, DENSE = 2048 * 256, 3 * 2048 * 512, 3 * 2048 * 8192
NORMS = 2 * 2048


def _cut(depth: int) -> laguna.LagunaConfig:
    whole = laguna.LagunaConfig()
    return laguna.LagunaConfig(
        vocab_size=12544, layer_kinds=whole.layer_kinds[:depth],
        heads_per_layer=whole.heads_per_layer[:depth], experts_held=32)


def test_param_count_of_the_published_model_and_the_cuts():
    assert (SLIDING, FULL) == (37879808, 29458432)
    sparse = NORMS + ROUTER + EXPERT            # less attention and experts
    assert SLIDING + sparse + 32 * EXPERT == 142217216
    assert FULL + sparse + 32 * EXPERT == 133795840
    assert FULL + NORMS + DENSE == 79794176
    assert laguna.param_count(laguna.LagunaConfig()) == (
        FULL + NORMS + DENSE + 30 * SLIDING + 9 * FULL
        + 39 * (sparse + 256 * EXPERT) + 2 * 100352 * 2048 + 2048)
    # the 33.4B of described_as: what reads gating as a gate a head
    assert laguna.param_count(laguna.LagunaConfig()) == 33442596864
    head = 2 * 12544 * 2048 + 2048
    assert head == 51382272
    assert laguna.param_count(_cut(8)) == (
        79794176 + 6 * 142217216 + 133795840 + head) == 1118275584
    # the ladder's third rung: layers 0-4, f S S S F
    assert laguna.param_count(_cut(5)) == 691623936
    assert _cut(5).pattern_string == "fSSSF" and not _cut(5).tail_kinds


def test_the_benchmarks_configuration_is_the_catalog_rows_cut():
    config = load_config("laguna-xs.2-ep8-1chip.json")
    cfg = laguna.LagunaConfig.from_hf(
        config, n_experts=config["published_num_experts"],
        experts_held=config["num_experts"])
    whole = laguna.LagunaConfig()
    assert cfg == _cut(8)
    published = dict(config, **{
        key: config["published_" + key] for key in config["reduced"]})
    assert laguna.LagunaConfig.from_hf(published) == whole
    assert cfg.pattern_string == "fSSSFSSS"
    assert sorted(config["reduced"]) == sorted(
        key[len("published_"):] for key in config if key.startswith(
            "published_"))


def test_flops_per_token_counts_each_layer_at_its_own_heads_and_pairs():
    config = load_config("laguna-xs.2-ep8-1chip.json")
    sizes = laguna_flops.sizes_of(config)
    assert laguna_flops.band_pairs(16384, 512) == 8257792
    assert laguna_flops.band_pairs(16384) == 134225920
    assert laguna_flops.pairs_of(
        "sliding_attention", 256, 512) == 256 * 257 // 2
    matmul = (2 * FULL + 6 * SLIDING + DENSE + 7 * (
        ROUTER + EXPERT + 8 * 32 / 256 * EXPERT) + 12544 * 2048)
    assert laguna_flops.active_matmul_params(**sizes) == matmul
    attn = 12.0 * 128 * (2 * 48 * 134225920 + 6 * 64 * 8257792) / 16384
    assert laguna_flops.flops_per_token(seq=16384, **sizes) == pytest.approx(
        6.0 * matmul + attn, rel=1e-12)
    assert laguna_flops.expert_flops_per_row(2048, 512) == 2 * EXPERT
    # the issue's arithmetic: the six window layers' kernels must do 7.3
    # TFLOP a step, the two full ones 29.7
    call = laguna_flops.attention_flops_per_call
    swa = call(batch=1, n_heads=64, head_dim=128, pairs=8257792)
    full = call(batch=1, n_heads=48, head_dim=128, pairs=134225920)
    assert 6 * sum(swa.values()) == pytest.approx(7.3e12, rel=0.01)
    assert 2 * sum(full.values()) == pytest.approx(29.7e12, rel=0.01)


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["attn.heads_full"] == 4 and g["attn.heads_window"] == 6
    assert g["attn.group_full"] == 2 and g["attn.group_window"] == 3
    assert g["attn.window"] == 16 and g["attn.window_layers"] == 6
    assert g["attn.full_layers"] == 2 and g["attn.gate"] == 1
    assert g["attn.out_kept"] == 0  # the tiny build recomputes nothing
    assert g["rotary.yarn_factor"] == 4 and g["rotary.dims_full"] == 8
    assert g["rotary.dims_window"] == 16
    assert g["rotary.attention_factor"] == pytest.approx(1.13862943611)
    assert g["layers.period"] == 4 and g["layers.dense"] == 1
    assert g["moe.route_on"] == 0 and g["moe.act"] == 0
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 4
    assert g["moe.top_k"] == 2 and g["moe.shared_experts"] == 1
    assert g["moe.rows_held"] == 2 * 48 * 2 * 4 / 8
    assert trace.text("layers.pattern") == "fSSSFSSS"
    for scope in ("attn_proj", "attn_gate", "dense_mlp", "moe_shared",
                  "norm", "embed_lookup"):
        assert scope in trace.scopes(), scope


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


@pytest.mark.parametrize("axis,match", [
    ("sp", "ring and ulysses attention have no window"),
    ("tp", r"query heads differ \(\[4, 6\] on 2 key heads\)"),
    ("pp", "no form for a period whose blocks differ in shape"),
])
def test_an_axis_the_family_cannot_run_is_refused_by_name(axis, match):
    cfg = laguna.LagunaConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1, pp=1)
    sizes[axis] = 2
    mesh = build_mesh(MeshConfig(**sizes).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match=f"laguna: mesh {axis}=2: .*{match}"):
        laguna.validate_for_mesh(cfg, mesh, batch=2)


def test_the_batch_and_the_held_experts_must_divide_over_the_mesh():
    mesh = build_mesh(MeshConfig(dp=1, ep=2).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        laguna.validate_for_mesh(
            laguna.LagunaConfig.tiny(experts_held=3), mesh, batch=2)
    with pytest.raises(ValueError, match="does not divide over the mesh"):
        laguna.validate_for_mesh(laguna.LagunaConfig.tiny(), mesh, batch=3)
    laguna.validate_for_mesh(laguna.LagunaConfig.tiny(), mesh, batch=4)


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
