"""``phi4flash-1chip-steady``'s whole step, compiled at real widths for a
described v5e (see ``test_chip_compile_steps.py``; a file of its own so
that no one file sets the pace of a ``--dist loadfile`` run)."""

import json
import os

import jax
import jax.numpy as jnp

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import selective_scan
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _kernel_calls, kernels_are_the_path, topo)

# The step built as benchmarks/jobs/train_loop.py builds it (the family,
# TrainConfig's defaults, ElasticTrainer.lower_step) on one described
# chip: `step.hbm_planned_peak_bytes` here is the chip's `hbm_peak_gib`
# to the byte. Depth 8 (M S M F G C G C), 50048 ids, 1 x 16384, every
# attention block keeping its flash output and lse, every Mamba block its
# scan's output and states (the ladder's third rung, ISSUE 63: the first
# compiles too, at 13,796,312,576 B, and ran; PERF.md section 6 has why
# the third was taken). Some slack may be added to it, no more.
PHI4FLASH_STEP_PLANNED_PEAK = 11695506944


def test_phi4flash_step_fits_the_chip_with_the_scan_and_flash_kernels(
        topo, kernels_are_the_path, monkeypatch):
    from benchmarks.families import phi4flash as family
    from dlrover_tpu.lint import memcheck
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    monkeypatch.setattr(selective_scan, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "phi-4-mini-flash-1chip.json")) as f:
        config = json.load(f)
    mc = MeshConfig(dp=-1, **config.get("mesh", {})).resolve(1)
    mesh = build_mesh(mc, devices=topo.devices[:1])
    fam = family.build(config, mesh)
    tc = TrainConfig(global_batch_size=1, micro_batch_size=1)
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
    assert fam.cfg.pattern_string == "MSMFGCGC"
    assert fam.param_count == 957803520
    # the first decoder's M and S one scan (of one trip), the producers
    # in line, the second decoder's C one scan of two trips: a kernel of
    # a scan's is one call site. Every block keeps what its kernel's
    # backward reads, so a forward kernel runs once a layer: no second
    # call site for remat.
    # (`_kernel_calls` counts names by their start: a plain kernel's
    # count holds the window kernel's)
    for name, calls in (("sscan_fwd", 2), ("sscan_bwd", 2),
                        ("attention_fwd", 3), ("attention_bwd_dq", 3),
                        ("attention_bwd_dkv", 3), ("attention_fwd_swa", 1),
                        ("attention_bwd_dq_swa", 1),
                        ("attention_bwd_dkv_swa", 1)):
        assert _kernel_calls(hlo, name) == calls, name
    # heads of 64, group 2, reach the kernels as the model states them;
    # the scan's operands are (s, c) wide and its B and C (s, n, 128): no
    # (s, c, n) array exists
    assert "bf16[1,16384,40,64]" in hlo and "bf16[1,16384,20,64]" in hlo
    assert "bf16[1,16384,16,128]" in hlo
    assert "16384,5120,16]" not in hlo and "16384,16,5120]" not in hlo
    gauges = trace.gauges()
    assert gauges["attn.tile_fallback"] == 0
    assert (gauges["attn.heads"], gauges["attn.group"],
            gauges["attn.head_dim"], gauges["attn.window"]) == (40, 2, 64, 512)
    assert gauges["attn.out_kept"] == 1
    assert (gauges["mamba.kernel"], gauges["mamba.channels"],
            gauges["mamba.state"], gauges["mamba.dt_rank"],
            gauges["mamba.chunk"], gauges["mamba.state_kept"]) == (
                1, 5120, 16, 160, 256, 1)
    assert (gauges["layers.memory_readers"], gauges["layers.kv_readers"],
            gauges["layers.tied_head"]) == (2, 2, 1)
    assert gauges["layers.memory_bytes"] == 16384 * 5120 * 2
    assert gauges["layers.kv_bytes"] == 2 * 16384 * 20 * 64 * 2
    assert trace.text("layers.pattern") == "MSMFGCGC"
    read = memcheck.read_memory_analysis(compiled)
    print(f"phi4flash step planned {read['planned_peak_bytes']} = "
          f"{read['planned_peak_bytes'] / 2**30:.4f} GiB, summed "
          f"{read['peak_bytes'] / 2**30:.4f}; window tiles "
          f"{gauges['attn.window_block_q']} x {gauges['attn.window_block_k']}"
          f" (dk/dv {gauges['attn.window_dkv_block_q']} x "
          f"{gauges['attn.window_dkv_block_k']}), full "
          f"{gauges['attn.block_q']} x {gauges['attn.block_k']}")
    assert read["planned_peak_bytes"] <= (
        PHI4FLASH_STEP_PLANNED_PEAK + 64 * 2**20) <= 15.75 * 2**30
