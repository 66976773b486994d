"""A cell's whole step, compiled at real widths for a described v5e (see
``test_chip_compile.py``, which holds the kernels' own checks,
``test_chip_compile_blocks_*.py`` for the families' blocks, and
``tests/chip_compile.py`` for what the files share). These are the long
compiles, files of their own so that no one file sets the pace of a
``--dist loadfile`` run."""

import os
import re

import jax
import jax.numpy as jnp

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _in_scope, _kernel_calls, _wide_f32, kernels_are_the_path, topo)


# dots3-ep32-1chip-steady's whole step, built as
# benchmarks/jobs/finetune_loop.py builds it (the family, its
# TrainConfig, ElasticTrainer.lower_step) on one described chip:
# `step.hbm_peak_bytes` here is the chip's
# `d3_hbm_peak_gib` to the byte. Since PR 43 a full block keeps d L_I / d
# scores beside the selection's mask (256 MiB a layer, float32), and the
# recomputed forward runs neither the indexer's score kernel nor
# `dsa_probs`: one call a full layer a step where the parent made two.
# Since PR 46 every block also keeps its flash forward's output and lse
# (64 + 1 MiB a full layer, 32 + 0.5 a window layer), so a forward kernel
# runs once a layer a step where the parent ran it twice. Since PR 55 the
# selection's threshold is one kernel a full layer (`dsa_select`). That step
# peaks at 15,454,193,152 bytes (14.393 GiB; 15,256,935,424 before the
# five pairs were kept); some slack may be added to it, no more.
DOTS3_STEP_PEAK = 15454193152


def test_dots3_step_keeps_the_loss_gradient_in_the_memory_it_has(
        topo, kernels_are_the_path):
    import json

    from benchmarks.families import dots3 as family
    from dlrover_tpu.lint import memcheck
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "dots3-note-prev-ep32-1chip.json")) as f:
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
        state, jax.ShapeDtypeStruct((accum, per, 8192), jnp.int32))
    compiled, _ = trainer.lower_step(mesh, mc)

    hlo = compiled.as_text()
    assert (fam.cfg.layer_kinds.count("F"),
            fam.cfg.layer_kinds.count("S")) == (2, 3)
    for name, calls in (("dsa_index_fwd", 2), ("dsa_probs", 2),
                        ("dsa_select", 2),
                        ("dsa_index_bwd", 2),
                        ("attention_fwd_sel", 2), ("attention_fwd_swa", 3),
                        ("attention_bwd_dq_sel", 2),
                        ("attention_bwd_dq_swa", 3)):
        assert _kernel_calls(hlo, name) == calls, name
    # the threshold is that kernel (PR 55): no array of ordered bits
    assert "u32[1,8192,8192]" not in hlo
    assert trace.gauges()["dsa.loss_grad_kept"] == 1
    assert trace.gauges()["attn.out_kept"] == 1
    # the backward scales the kept array once a layer (`indexer_loss`'s
    # barrier), and the one score kernel reads that product as it lies:
    # no transposed copy of the float32 (s, s) cotangent (until PR 57
    # the key-side kernel read one, 256 MiB a layer)
    scaled = [line for line in _wide_f32(hlo, "fusion", 8192 * 8192)
              if "transpose(jvp" in line]
    assert len(scaled) == 2 and all(
        _in_scope(re.search(r'op_name="([^"]*)"', line).group(1), "dsa_loss")
        for line in scaled), scaled
    assert not re.search(
        r"= f32\[1,8192,8192\]\S* (copy|transpose)\(", hlo)
    peak = memcheck.read_memory_analysis(compiled)["peak_bytes"]
    print(f"dots3 step.hbm_peak_bytes {peak} = {peak / 2**30:.4f} GiB")
    assert peak <= DOTS3_STEP_PEAK + 64 * 2**20 <= 15.75 * 2**30


# minicpm-sala-d4-1chip-steady's whole step (PR 48), built the same way:
# `step.hbm_planned_peak_bytes` here is the chip's `sala_hbm_peak_gib` to
# the byte (15,213,162,496: 14.168 GiB of 15.75). The sparse block keeps
# its choice of blocks and the flash pair, the three lightning blocks the
# rule's output and states, so every forward kernel runs once a step.
SALA_STEP_PLANNED_PEAK = 15213162496


def test_minicpm_sala_step_fits_the_chip_with_every_forward_kernel_once(
        topo, kernels_are_the_path):
    import json

    from benchmarks.families import minicpm_sala as family
    from dlrover_tpu.lint import memcheck
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "minicpm-sala-9b-d4-1chip.json")) as f:
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
    assert fam.cfg.pattern_string == "SLLL"
    # the lightning layers are one scan: a kernel of theirs is one call
    # site of three trips
    for name, calls in (("blk_score", 1), ("attention_fwd_blk", 1),
                        ("attention_bwd_dq_blk", 1),
                        ("attention_bwd_dkv_blk", 1), ("lightning_fwd", 1),
                        ("lightning_bwd", 1), ("kda_out_fwd", 2),
                        ("kda_out_bwd", 1)):
        assert _kernel_calls(hlo, name) == calls, name
    gauges = trace.gauges()
    assert gauges["attn.out_kept"] == 1 and gauges["la.state_kept"] == 1
    assert gauges["attn.blk_sparse"] == 1 and gauges["la.kernel"] == 1
    assert (gauges["attn.block_q"], gauges["attn.block_k"]) == (128, 512)
    assert "s8[1,2,16384,256]" in hlo
    assert not re.search(r"\[1,(2|32),16384,16384\]", hlo)
    read = memcheck.read_memory_analysis(compiled)
    print(f"minicpm_sala step planned {read['planned_peak_bytes']} = "
          f"{read['planned_peak_bytes'] / 2**30:.4f} GiB, summed "
          f"{read['peak_bytes'] / 2**30:.4f}")
    assert read["planned_peak_bytes"] <= (
        SALA_STEP_PLANNED_PEAK + 64 * 2**20) <= 15.75 * 2**30
