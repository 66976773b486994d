"""The phi4flash family's sizes, FLOPs, first loss, gauges, meshes and
trainer (see ``test_phi4flash.py``): the published model's and the cuts'
parameter counts; the closed form of the first loss against the float32
reference; what the build's gauges say; dp and fsdp on CPU devices; what
``validate_for_mesh`` refuses; three steps through the trainer."""

import jax
import numpy as np
import pytest

from benchmarks.families import phi4flash as family
from benchmarks.harness import phi4flash_flops
from dlrover_tpu.models import phi4flash
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig
from tests.phi4flash_family import (  # noqa: F401  (fixtures by import)
    built, config, load_config, mesh)
from tests.plain_forms import jitted_plain_loss
from tests.smallthinker_family import _assert_grads_agree

# ISSUE 63's arithmetic, at hidden 2560
MAMBA = 26214400 + 13107200 + 983040 + 824320 + 81920 + 25600 + 5120
ATTN, GMU, CROSS, SWIGLU, NORMS = (19660800, 26214400, 13107200, 78643200,
                                   10240)


def test_param_count_of_the_published_model_and_the_cut():
    assert MAMBA == 41241600
    layer = {"M": MAMBA, "S": ATTN, "F": ATTN, "G": GMU, "C": CROSS}
    whole = phi4flash.Phi4FlashConfig()
    assert whole.layer_kinds == "MS" * 8 + "MF" + "GC" * 7
    assert whole.kinds[16] == "M" and whole.kinds[17] == "F"
    assert (whole.channels, whole.dt_rank, whole.head_dim, whole.group) == (
        5120, 160, 64, 2)
    assert phi4flash.param_count(whole) == sum(
        layer[k] + SWIGLU + NORMS for k in whole.kinds
    ) + 5120 + 200064 * 2560 == 3852451840
    for kinds, vocab, count in (("MSMSMFGCGC", 50048, 1176012800),
                                ("MSMSMFGCGC", 25088, 1112115200),
                                ("MSMFGCGC", 50048, 957803520),
                                ("MSMFGC", 50048, 761175040)):
        cut = phi4flash.Phi4FlashConfig(layer_kinds=kinds, vocab_size=vocab)
        assert phi4flash.param_count(cut) == count, kinds


@pytest.mark.parametrize("kinds", ["MSMSGC", "MFGCM", "SMMFGC", "MSMFCG", ""])
def test_a_stack_that_is_no_hybrid_decoder_pair_is_refused(kinds):
    with pytest.raises(ValueError, match=r"\(M S\)\^a M F \(G C\)\^b"):
        phi4flash.Phi4FlashConfig.tiny(layer_kinds=kinds)


def test_the_benchmarks_configuration_is_the_catalog_rows_cut():
    config = load_config("phi-4-mini-flash-1chip.json")
    cfg = phi4flash.Phi4FlashConfig.from_hf(config)
    assert cfg == phi4flash.Phi4FlashConfig(
        layer_kinds="MSMFGCGC", vocab_size=50048)
    assert config["num_hidden_layers"] == len(config["layer_kinds"]) == 8
    published = dict(config, layer_kinds=config["published_layer_kinds"], **{
        key: config["published_" + key] for key in config["reduced"]})
    assert phi4flash.Phi4FlashConfig.from_hf(published) == (
        phi4flash.Phi4FlashConfig())
    assert config["published_layer_kinds"] == phi4flash.published_kinds(32)
    assert sorted(config["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert config["vocab_size"] % 128 == 0
    assert config["vocab_size"] / config["published_vocab_size"] >= 0.125
    with pytest.raises(ValueError, match="model_type"):
        phi4flash.Phi4FlashConfig.from_hf(dict(config, model_type="phi3"))


def test_flops_per_token_counts_each_kind_of_layer():
    config = load_config("phi-4-mini-flash-1chip.json")
    sizes = phi4flash_flops.sizes_of(config)
    matmul = (2 * (MAMBA - 81920 - 25600 - 5120 - 5120) + 2 * ATTN + 2 * GMU
              + 2 * CROSS + 8 * SWIGLU + 50048 * 2560)
    assert phi4flash_flops.active_matmul_params(**sizes) == matmul
    attn = 12.0 * 64 * 40 * (3 * 134225920 + 8257792) / 16384
    scan = 2 * 5120 * 16 * 19
    assert phi4flash_flops.flops_per_token(seq=16384, **sizes) == (
        pytest.approx(6.0 * matmul + attn + scan, rel=1e-12))
    # a step's attention as the model is credited (12 x 64 FLOPs a pair
    # and head: 12.6 T) and as the kernels must do it (the two backward
    # kernels each form the scores again: 9 products of 2 x 64 a pair,
    # 18.6 T causal and 0.38 T a window layer)
    assert attn * 16384 == pytest.approx(12.6e12, rel=0.01)
    call = phi4flash_flops.attention_flops_per_call
    full = call(batch=1, n_heads=40, head_dim=64, pairs=134225920)
    swa = call(batch=1, n_heads=40, head_dim=64, pairs=8257792)
    assert 3 * sum(full.values()) == pytest.approx(18.6e12, rel=0.01)
    assert sum(swa.values()) == pytest.approx(0.38e12, rel=0.01)
    per_call = phi4flash_flops.sscan_flops_bytes_per_call(
        tokens=16384, channels=5120, state=16, chunk=256)
    assert per_call["fwd"][0] == 5 * 16384 * 5120 * 16
    # the forward's HBM floor: 0.8 ms a call at 819 GB/s
    assert per_call["fwd"][1] / 819e9 == pytest.approx(0.85e-3, rel=0.02)
    assert per_call["bwd"][1] > per_call["fwd"][1]


def test_the_first_loss_is_the_tied_tables_own_row_not_ln_v(config):
    """The closed form at the published sizes, and held to what seeded
    weights give at a size where the branches still weigh a little: the
    float32 reference on 4 x 64 tokens at d 64 (the tolerance the job
    holds the chip's first loss to is 0.25)."""
    big = load_config("phi-4-mini-flash-1chip.json")
    loss = phi4flash_flops.expected_first_loss(big)
    assert 50.0 < loss < 50.6 and abs(loss - np.log(50048)) > 39
    assert loss == pytest.approx(50.38, abs=0.02)
    wide = dict(config, hidden_size=256, intermediate_size=512,
                vocab_size=512, num_attention_heads=4)
    wide["assumed"] = dict(config["assumed"], out_proj_std=2e-3, mamba=dict(
        config["assumed"]["mamba"], dt_rank=16))
    for c in (config, wide):
        mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
        fam = family.build(c, mesh)
        seen = []
        for seed in range(3):
            params = fam.init_params(jax.random.key(seed))
            tokens = jax.random.randint(
                jax.random.key(100 + seed), (4, 64), 0, fam.cfg.vocab_size)
            seen.append(float(jitted_plain_loss(family, c)(params, tokens)))
        assert abs(np.mean(seen) - fam.expected_first_loss) < 0.1, (
            seen, fam.expected_first_loss)
    # at the wide size the branches take a visible part of the own logit
    assert phi4flash_flops.residual_variance(wide) > 1.1 * 0.02 ** 2


# ---------------------------------------------------------------------------
# Gauges, meshes, the trainer
# ---------------------------------------------------------------------------

def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert (g["layers.tied_head"], g["layers.memory_readers"],
            g["layers.kv_readers"]) == (1, 2, 2)
    assert g["layers.memory_bytes"] == 2 * 64 * 128 * 4
    assert g["layers.kv_bytes"] == 2 * 2 * 64 * 2 * 16 * 4
    assert (g["mamba.kernel"], g["mamba.channels"], g["mamba.state"],
            g["mamba.dt_rank"], g["mamba.chunk"]) == (0, 128, 16, 4, 16)
    assert g["mamba.state_kept"] == 0  # the tiny build recomputes nothing
    assert (g["attn.heads"], g["attn.group"], g["attn.head_dim"],
            g["attn.window"], g["attn.out_kept"]) == (4, 2, 16, 16, 0)
    assert trace.text("layers.pattern") == "MSMSMFGCGC"
    for scope in ("mamba_proj", "mamba_conv", "mamba_xdt", "mamba_scan",
                  "mamba_gate", "gmu", "attn_proj", "cross_proj",
                  "dense_mlp", "norm", "embed_lookup"):
        assert scope in trace.scopes(), scope


def test_a_remat_build_keeps_the_kernels_residuals(config, mesh, built):
    _, params, tokens = built
    config = dict(config, assumed=dict(config["assumed"], remat="all"))
    fam = family.build(config, mesh)
    jax.eval_shape(jax.grad(fam.loss_fn), params, tokens)
    g = trace.gauges()
    assert g["mamba.state_kept"] == 1 and g["attn.out_kept"] == 1


def test_dp_and_fsdp_on_cpu_devices(config, built):
    """The loss and the gradients of one device over dp=2 and fsdp=2."""
    fam1, params, _ = built
    tokens = jax.random.randint(jax.random.key(4), (4, 32), 0, 256)
    want, want_grads = jax.jit(jax.value_and_grad(fam1.loss_fn))(
        params, tokens)
    for sizes in (dict(dp=2), dict(dp=1, fsdp=2), dict(dp=2, fsdp=2)):
        n = sizes.get("dp", 1) * sizes.get("fsdp", 1)
        mc = MeshConfig(**sizes).resolve(n)
        mesh = build_mesh(mc, devices=jax.devices()[:n])
        fam = family.build(config, mesh)
        placed = jax.device_put(
            params, named_shardings(mesh, fam.param_specs))
        loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(placed, tokens)
        assert abs(float(loss) - float(want)) < 2e-5, sizes
        _assert_grads_agree(grads, want_grads, tol=1e-3)


@pytest.mark.parametrize("axis,match", [
    ("sp", "a Mamba layer's state .* the shared memory and keys"),
    ("tp", "no head- or channel-sharded form of the five kinds"),
    ("pp", "must carry the memory m and the keys and values k, v beside "
           "the residual x"),
])
def test_an_axis_the_family_cannot_run_is_refused_by_name(axis, match):
    cfg = phi4flash.Phi4FlashConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1, pp=1)
    sizes[axis] = 2
    mesh = build_mesh(MeshConfig(**sizes).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError,
                       match=f"phi4flash: mesh {axis}=2: .*{match}"):
        phi4flash.validate_for_mesh(cfg, mesh, batch=2)


def test_the_batch_must_divide_over_the_mesh():
    mesh = build_mesh(MeshConfig(dp=2).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match="does not divide over the mesh"):
        phi4flash.validate_for_mesh(
            phi4flash.Phi4FlashConfig.tiny(), mesh, batch=3)
    phi4flash.validate_for_mesh(phi4flash.Phi4FlashConfig.tiny(), mesh,
                                batch=4)


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
