"""The shardcheck contract model: one fixed tiny program per mesh.

SC001 diffs the step program's collective census against a checked-in
contract, which only means something if every generation of the
contract lowers the *same* program. This module pins that program: a
tiny llama (vocab 256, dim 64, 2 layers) with an explicitly small CE
chunk (64 < vocab — the default 2048 clips to the full tiny vocab,
which would make the chunked path materialize seq×vocab tensors and
trip its own SC003 gate), a fixed sequence length and global batch,
lowered through the exact ``ElasticTrainer`` machinery production uses
(``step_ir`` → ``lower_step`` avatars). Everything runs on CPU host
devices — contract generation and CI checking never touch a TPU.

Imports jax lazily: :mod:`dlrover_tpu.lint` must stay importable in
the dep-free graftlint environment, and the ``--hlo`` CLI needs to
force the CPU platform *before* jax initializes.
"""

from __future__ import annotations

import os
from typing import Dict, Tuple

from dlrover_tpu.lint import shardcheck

#: the pinned contract-program knobs — changing any of these re-keys
#: every contract (config_hash mismatch), which is exactly the signal
#: to regenerate with --fix-contracts
SEQ_LEN = 16
GLOBAL_BATCH = 8
MICRO_BATCH = 2
CE_CHUNK = 64
VOCAB = 256

#: global batch of the ``+overlap`` contract variants ONLY: the overlap
#: schedule pipelines the DCN leg across gradient-accumulation
#: microbatches, so its contract program must actually accumulate —
#: and the peeled scan must survive to the optimized HLO (the overlap
#: dimension reads loop structure). dp4 × micro 2 → accum 3 → a
#: trip-count-2 scan, which XLA keeps as a real while (a trip-count-1
#: loop is inlined away and the schedule evidence with it). Scoped to
#: overlap specs so every pre-existing contract keeps its config_hash.
OVERLAP_GLOBAL_BATCH = 24

#: pipeline-contract geometry (pp > 1 specs ONLY — non-pp contracts
#: keep the 2-layer config and their config_hash): 4 layers over
#: pp=2 x 2 virtual stages (one layer per chunk), 4 microbatches, so
#: the interleaved 1F1B model bubble is (p-1)/(m*v) = 1/8 — the
#: paper's (p-1)/(p*m) with v = p. The SC008 contract pins exactly
#: this geometry.
PP_LAYERS = 4
PP_MICROBATCHES = 4
PP_VIRTUAL_STAGES = 2
PP_SCHEDULE = "1f1b"


def ensure_cpu_devices(n: int) -> None:
    """Force the CPU platform with ≥ ``n`` virtual host devices. Must
    run before jax initializes its backend (mirrors tests/conftest.py,
    including the jax.config update, which wins over a JAX_PLATFORMS
    the environment already carries)."""
    # jax platform wiring, not DLROVER_TPU_* knobs: these two env vars
    # must be written before jax initializes, same as tests/conftest.py
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # graftlint: disable=JG003
    xla_flags = os.environ.get("XLA_FLAGS", "")  # graftlint: disable=JG003
    if "--xla_force_host_platform_device_count" not in xla_flags:
        # graftlint: disable=JG003
        os.environ["XLA_FLAGS"] = (
            xla_flags + f" --xla_force_host_platform_device_count={max(n, 8)}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"mesh needs {n} devices but jax initialized with {have} "
            "(jax imported before the device-count flag could be set? "
            "run the CLI in a fresh process)"
        )


def build_contract_trainer(
    axis_sizes: Dict[str, int], zero1: bool = False, n_slices: int = 1,
    overlap: bool = False,
):
    """(trainer, state, batch) for the pinned contract model on the
    mesh ``axis_sizes`` describes, placed on CPU host devices.
    ``zero1`` builds the weight-update-sharded variant of the step via
    the TrainConfig knob; ``n_slices > 1`` builds the mesh slice-major
    (virtual slices on CPU) and hands the trainer the slice count, so
    the hierarchical-collectives strategy and the per-link census see
    the multislice topology."""
    import jax
    import numpy as np

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import build_mesh, named_shardings
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    world = 1
    for s in axis_sizes.values():
        world *= s
    pp = axis_sizes.get("pp", 1)
    if pp > 1:
        # the pipeline variant of the pinned program: same tiny dims,
        # 4 layers so pp=2 x v=2 holds one layer per chunk, explicit
        # interleaved-1F1B schedule knobs — the SC008 geometry
        cfg = llama.LlamaConfig.tiny(
            vocab_size=VOCAB, ce_chunk_size=CE_CHUNK,
            n_layers=PP_LAYERS, pp_schedule=PP_SCHEDULE,
            pp_microbatches=PP_MICROBATCHES,
            pp_virtual_stages=PP_VIRTUAL_STAGES,
        )
    else:
        cfg = llama.LlamaConfig.tiny(
            vocab_size=VOCAB, ce_chunk_size=CE_CHUNK
        )
    mc = MeshConfig(
        dp=axis_sizes.get("dp", 1),
        pp=pp,
        fsdp=axis_sizes.get("fsdp", 1),
        ep=axis_sizes.get("ep", 1),
        sp=axis_sizes.get("sp", 1),
        tp=axis_sizes.get("tp", 1),
    ).resolve(world)
    mesh = build_mesh(
        mc, devices=jax.devices()[:world], n_slices=n_slices
    )
    specs = llama.param_specs(cfg, pp=mc.pp)
    # pp steps feed the schedule's own microbatching: one accum row
    # carrying the whole global batch (accum=1), so the loss call sees
    # GLOBAL_BATCH rows to split into PP_MICROBATCHES microbatches
    micro = (
        GLOBAL_BATCH // mc.data_parallel_size if pp > 1 else MICRO_BATCH
    )
    tc = TrainConfig(
        global_batch_size=(
            OVERLAP_GLOBAL_BATCH if overlap else GLOBAL_BATCH
        ),
        micro_batch_size=micro,
        warmup_steps=0,
        total_steps=100,
        zero1=zero1,
        overlap_collectives=overlap,
    )
    trainer = ElasticTrainer(
        None, specs, mesh, mc, tc,
        loss_factory=lambda m: (
            lambda p, t: llama.loss_fn(p, t, cfg, m)
        ),
        n_slices=n_slices,
    )
    trainer.shardcheck_hints = {
        "seq_len": SEQ_LEN, "vocab": cfg.vocab_size,
    }
    if pp > 1:
        # arms the SC008 pipeline-schedule contract dimension
        trainer.shardcheck_hints["pp_schedule"] = {
            "schedule": cfg.pp_schedule,
            "microbatches": cfg.pp_microbatches or mc.pp,
            "virtual_stages": cfg.pp_virtual_stages,
        }
    params = jax.device_put(
        llama.init_params(cfg, jax.random.key(0)),
        named_shardings(mesh, specs),
    )
    state = trainer.init_state(params)
    accum, per = trainer.step_batch_shape
    batch = np.zeros((accum, per, SEQ_LEN), np.int32)
    trainer.record_avatars(state, batch)
    return trainer, state, batch


def _pinned_flags():
    """The contract-program flag pin: the SPEC alone decides the
    reduction form (TrainConfig and the mesh), but the CE path choice
    is part of the contracted program too, so the one kernel-dispatch
    flag pins to its default (fused falls back to chunked off-TPU —
    the recorded program is the chunked-scan one)."""
    from dlrover_tpu.common import flags

    return flags.FUSED_CE.scoped(None)


def build_program(
    spec: str, pinned: bool = True
) -> Tuple["shardcheck.StepProgram", object]:
    """Lower the contract model for ``spec`` (e.g. ``"dp2xfsdp2"``,
    the zero-1 variant ``"dp4+zero1"``, or a multislice hierarchical
    variant like ``"dp4+2slice"``) and return
    ``(StepProgram, trainer)``."""
    from dlrover_tpu.common.world import WorldDescriptor

    wd = WorldDescriptor.parse(spec)
    axis_sizes = wd.axis_sizes()
    world = 1
    for s in axis_sizes.values():
        world *= s
    ensure_cpu_devices(world)
    with _pinned_flags():
        trainer, _, _ = build_contract_trainer(
            axis_sizes, zero1=wd.zero1, n_slices=wd.n_slices,
            overlap=wd.overlap,
        )
        program = trainer.step_ir(pinned=pinned)
    program.label = "hlo:" + wd.spec
    return program, trainer


def build_memcheck(spec: str) -> Dict:
    """Lower the contract model for ``spec`` under the same flag pins
    as :func:`build_program` and return the trainer's memcheck payload
    (lint/memcheck.py): the per-device component breakdown, analytic
    peak and guarded measured bytes of the pinned program — the MC001
    contract substrate."""
    from dlrover_tpu.common.world import WorldDescriptor

    wd = WorldDescriptor.parse(spec)
    axis_sizes = wd.axis_sizes()
    world = 1
    for s in axis_sizes.values():
        world *= s
    ensure_cpu_devices(world)
    with _pinned_flags():
        trainer, _, _ = build_contract_trainer(
            axis_sizes, zero1=wd.zero1, n_slices=wd.n_slices,
            overlap=wd.overlap,
        )
        return trainer.memcheck_payload()
