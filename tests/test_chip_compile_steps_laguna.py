"""``laguna-xs2-ep8-1chip-steady``'s whole step, compiled at real widths
for a described v5e (see ``test_chip_compile_steps.py``; a file of its
own so that no one file sets the pace of a ``--dist loadfile`` run)."""

import json
import os

import jax
import jax.numpy as jnp

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _kernel_calls, kernels_are_the_path, topo)

# The step built as benchmarks/jobs/finetune_loop.py builds it (the
# family, its TrainConfig, ElasticTrainer.lower_step) on one described
# chip: `step.hbm_planned_peak_bytes` here is the chip's `hbm_peak_gib`
# to the byte. Depth 8, 1 x 16384, every block keeping its flash output
# and lse (the ladder's first rung, ISSUE 60). Some slack may be added to
# it, no more.
LAGUNA_STEP_PLANNED_PEAK = 14450692096


def test_laguna_step_fits_the_chip_with_the_flash_kernels_at_both_shapes(
        topo, kernels_are_the_path):
    from benchmarks.families import laguna as family
    from dlrover_tpu.lint import memcheck
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "laguna-xs.2-ep8-1chip.json")) as f:
        config = json.load(f)
    mc = MeshConfig(dp=-1, **config.get("mesh", {})).resolve(1)
    mesh = build_mesh(mc, devices=topo.devices[:1])
    fam = family.build(config, mesh)
    tc = TrainConfig(global_batch_size=1, micro_batch_size=1,
                     **fam.train_config)
    trainer = ElasticTrainer(fam.loss_fn, fam.param_specs, mesh, mc, tc)
    params = jax.eval_shape(fam.init_params, jax.random.key(0))
    state = {"params": params,
             "opt": jax.eval_shape(trainer.optimizer.init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32),
             "lr_scale": jax.ShapeDtypeStruct((), jnp.float32)}
    accum, per = trainer.step_batch_shape
    trainer.record_avatars(
        state, jax.ShapeDtypeStruct((accum, per, 16384), jnp.int32))
    compiled, _ = trainer.lower_step(mesh, mc)

    hlo = compiled.as_text()
    assert fam.cfg.pattern_string == "fSSSFSSS"
    assert fam.param_count == 1118275584
    # the dense layer and the period's full layer in line, the period's
    # three window layers in line, the tail's three one scan: a kernel
    # of theirs is one call site of three trips. Every block keeps the
    # forward's output and lse, so a forward kernel runs once a layer.
    # (`_kernel_calls` counts names by their start: a plain kernel's
    # count holds the window kernel's, 2 + 4)
    for name, calls in (("attention_fwd", 6), ("attention_bwd_dq", 6),
                        ("attention_bwd_dkv", 6), ("attention_fwd_swa", 4),
                        ("attention_bwd_dq_swa", 4),
                        ("attention_bwd_dkv_swa", 4)):
        assert _kernel_calls(hlo, name) == calls, name
    # both shapes reach the kernels as the model states them: 48 heads
    # on 8 (group 6) and 64 on 8 (group 8), heads of 128
    assert "bf16[1,16384,48,128]" in hlo and "bf16[1,16384,64,128]" in hlo
    gauges = trace.gauges()
    assert gauges["attn.tile_fallback"] == 0
    assert (gauges["attn.heads_full"], gauges["attn.heads_window"]) == (48, 64)
    assert (gauges["attn.group_full"], gauges["attn.group_window"]) == (6, 8)
    assert (gauges["attn.window"], gauges["attn.gate"]) == (512, 1)
    assert gauges["attn.out_kept"] == 1
    # the window layers' tiles are the window's (PR 61), the full layers'
    # the causal call's
    assert (gauges["attn.window_block_q"], gauges["attn.window_block_k"],
            gauges["attn.window_dkv_block_q"],
            gauges["attn.window_dkv_block_k"]) == (256, 256, 512, 512)
    assert gauges["attn.window_band_pct"] == 52.9
    assert (gauges["attn.block_q"], gauges["attn.block_k"]) == (256, 512)
    assert (gauges["rotary.dims_full"], gauges["rotary.dims_window"]) == (
        64, 128)
    assert (gauges["moe.experts"], gauges["moe.experts_held"],
            gauges["moe.top_k"], gauges["moe.shared_experts"]) == (
                256, 32, 8, 1)
    assert gauges["moe.rows_held"] == 16384
    read = memcheck.read_memory_analysis(compiled)
    print(f"laguna step planned {read['planned_peak_bytes']} = "
          f"{read['planned_peak_bytes'] / 2**30:.4f} GiB, summed "
          f"{read['peak_bytes'] / 2**30:.4f}; window tiles "
          f"{gauges['attn.window_block_q']} x {gauges['attn.window_block_k']}"
          f", full {gauges['attn.block_q']} x {gauges['attn.block_k']}")
    assert read["planned_peak_bytes"] <= (
        LAGUNA_STEP_PLANNED_PEAK + 64 * 2**20) <= 15.75 * 2**30
