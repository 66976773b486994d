"""A cell's whole step and the families' blocks, compiled at real widths
for a described v5e (see ``test_chip_compile.py``, which holds the kernels'
own checks, and ``tests/chip_compile.py`` for what the files share). These
are the long compiles, a file of their own so that no one file sets the
pace of a ``--dist loadfile`` run."""

import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _in_scope, _kernel_calls, _op_names, _wide_f32, kernels_are_the_path,
    one_chip, topo)


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
                        ("dsa_index_bwd_dq", 2), ("dsa_index_bwd_dk", 2),
                        ("attention_fwd_sel", 2), ("attention_fwd_swa", 3),
                        ("attention_bwd_dq_sel", 2),
                        ("attention_bwd_dq_swa", 3)):
        assert _kernel_calls(hlo, name) == calls, name
    # the threshold is that kernel (PR 55): no array of ordered bits
    assert "u32[1,8192,8192]" not in hlo
    assert trace.gauges()["dsa.loss_grad_kept"] == 1
    assert trace.gauges()["attn.out_kept"] == 1
    # the backward scales the kept array once a layer: the transpose the
    # key-side score kernel reads is a copy of that product, not a second
    # product (`indexer_loss`'s barrier)
    scaled = [line for line in _wide_f32(hlo, "fusion", 8192 * 8192)
              if "transpose(jvp" in line]
    assert len(scaled) == 2 and all(
        _in_scope(re.search(r'op_name="([^"]*)"', line).group(1), "dsa_loss")
        for line in scaled), scaled
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


def _two_in_line(fn):
    """The gradient's function of two blocks in line, built as the family
    builds them: the first one's output is wanted, so its forward runs;
    the second's is not (the loss's value is not asked for), so its first
    forward runs only for what its checkpoint keeps."""
    return jax.grad(
        lambda lp, x: fn(lp, fn(lp, x)).astype(jnp.float32).sum(),
        argnums=(0, 1))


# xing4-ep8-1chip-steady (PR 31): two whole expert blocks of the step in
# line at the published widths (four streams of 2 x 4096 x 3584, ranks
# 768 / 512, 8 held experts of 64, the shared expert), forward and
# backward, recomputed as the family's own factory has it.
def test_xing4_expert_block_fwd_bwd_compiles(one_chip, kernels_are_the_path):
    from dlrover_tpu.models import xing4

    cfg = xing4.Xing4Config(
        vocab_size=16384, n_dense_layers=1, n_moe_layers=1, experts_held=8,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    layers = xing4.abstract_params(cfg)["layers"]
    lp = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype,
                                       sharding=one_chip), layers)
    X = jax.ShapeDtypeStruct((4, 2, 4096, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32)
    trace.gauge("attn.out_kept", 0)

    compiled = jax.jit(_two_in_line(
        xing4._block_fn(cfg, None, tokens))).lower(lp, X).compile()
    hlo = compiled.as_text()
    # since PR 46 a block keeps the flash forward's output and lse: one
    # forward call a block (the first block's own forward; the second's,
    # which runs for the kept pair alone) where `nothing_saveable` made
    # 2 + 1; a block's 2 of attention's backward, and forward, d-lhs and
    # d-rhs of each of the three grouped products (the first block's
    # forward products run twice); act(gate) x up and its backward as
    # passes
    assert _kernel_calls(hlo, "attention_fwd") == 2
    assert trace.gauges()["attn.out_kept"] == 1
    assert _kernel_calls(hlo, "attention_bwd") == 4
    assert _kernel_calls(hlo, "grouped_matmul_dlhs") == 6
    assert _kernel_calls(hlo, "grouped_matmul_drhs") == 6
    assert _kernel_calls(hlo, "grouped_matmul") == 21
    assert _kernel_calls(hlo, "moe_rows_gated") == 5
    assert trace.gauges()["moe.tail_skipped"] == 1
    assert "[8192,64,8" not in hlo  # no (tokens, experts, ...) dispatch tensor
    # since PR 49 the stream mixing is ops/hc_mix.py's four passes, two
    # sublayers a block. The pre-mix runs with the first block's forward
    # (2), with the second's as far as attention's kept pair needs it
    # (1) and in both recomputed forwards (4); the post + res-mix with
    # the first block's forward (2) and once a recomputed one (a block's
    # last X' is its result, which nothing reads again); each backward
    # once a sublayer
    assert trace.gauges()["layers.hc_fused"] == 1
    assert {name: _kernel_calls(hlo, name) for name in (
        "hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd")} == {
            "hc_pre_fwd": 7, "hc_post_fwd": 4, "hc_post_bwd": 4,
            "hc_pre_bwd": 4}
    # the streams are mixed in float32 inside the passes alone: no
    # float32 copy of a whole (2, 4096, 3584) slab in HBM
    assert not _wide_f32(hlo, "copy", at_least=2 * 4096 * 3584)
    # two blocks' own temporaries fit beside the cell's state and carries
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2**30
    assert trace.gauges()["moe.rows_held"] == 4096
    assert trace.gauges()["moe.tail_rows"] == 28672
    assert cfg.softmax_scale == pytest.approx(0.14468, rel=1e-4)


@pytest.mark.parametrize("attn", ["kda", "mla"])
def test_kimi_linear_expert_block_fwd_bwd_compiles(
        one_chip, kernels_are_the_path, attn):
    from dlrover_tpu.models import kimi_linear

    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=20480, n_layers=5, kda_layers=(1, 2, 3, 5),
        full_attn_layers=(4,), experts_held=32, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    lp = {
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        for name, (shape, _, _) in kimi_linear._block_shapes(
            cfg, attn, "moe").items()
    }
    x = jax.ShapeDtypeStruct((1, 8192, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    trace.gauge("attn.out_kept", 0)
    trace.gauge("kda.state_kept", 0)
    compiled = jax.jit(_two_in_line(
        kimi_linear._block_fn(cfg, None, attn, "moe"))).lower(lp, x).compile()
    hlo = compiled.as_text()
    # latent attention without rotary still runs the 192 / 128 kernels,
    # the forward once a block since PR 46 (the block keeps its output
    # and lse; `nothing_saveable` made 2 + 1 of it); a KDA block runs
    # none of them and, since PR 47, keeps the delta rule's output and
    # states (64 + 256 MiB): the rule's forward kernel runs once a block
    # where it made 2 + 1 (the second block's first forward now runs, up
    # to that kernel, for the kept pair alone: the input pass 2 + 2), the
    # output pass in the first block's forward and in both recomputed
    # ones, the backwards once a block, all under the scope the device
    # metrics select by
    flash = 1 if attn == "mla" else 0
    assert _kernel_calls(hlo, "attention_fwd") == 2 * flash
    assert trace.gauges()["attn.out_kept"] == flash
    assert trace.gauges()["kda.state_kept"] == 1 - flash
    assert _kernel_calls(hlo, "attention_bwd") == 4 * flash
    assert _kernel_calls(hlo, "grouped_matmul") == 21
    assert _kernel_calls(hlo, "moe_rows_gated") == 5
    delta = [n for n in _op_names(hlo) if "/kda_" in n]
    if flash:
        assert not delta
    else:
        assert collections.Counter((n.split("/")[-2], next(
            s for s in ("kda_conv", "kda_chunk", "kda_out")
            if _in_scope(n, s))) for n in delta) == {
            ("kda_bwd", "kda_chunk"): 2, ("kda_fwd", "kda_chunk"): 2,
            ("kda_in_bwd", "kda_conv"): 2, ("kda_in_fwd", "kda_conv"): 4,
            ("kda_out_bwd", "kda_out"): 2, ("kda_out_fwd", "kda_out"): 3}
        assert trace.gauges()["kda.io_fused"] == 1
        # the XLA form of the passes took float32 copies of every
        # activation into another layout and back: none is left
        assert not _wide_f32(hlo, "copy")
        # what the XLA form of the rule cost beside its loops: the solves
        # and the float32 moves of (8192, 4096) into chunk-major order
        assert "riangular" not in hlo
        assert not [line for line in _wide_f32(hlo, "transpose")
                    if _in_scope(line, "kda_chunk")]
    # two blocks' own temporaries fit beside the cell's 7.16 GiB of state
    # and 4.78 of float32 gradients (2.302 GiB the latent pair with its
    # kept 65 MiB; 2.960 the KDA pair with its kept 2 x 320 MiB, 2.976
    # when it kept nothing; one KDA block alone took 2.10, and 2.857
    # with the passes in XLA ops)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2.5 if flash else 3.0) * 2**30
    assert trace.gauges()["moe.rows_held"] == 8192
    assert trace.gauges()["moe.tail_rows"] == 57344


@pytest.mark.parametrize("kind", ["G", "F"])
def test_qwen3_next_block_fwd_bwd_compiles(
        one_chip, kernels_are_the_path, kind):
    """A block of the qwen3next cell at its shapes (16384 tokens, 32 of
    512 experts held): a Gated DeltaNet block runs the per-head rule's
    two kernels and the passes around them under the layer's scopes and
    no flash kernel; a gated attention block the flash kernels at 256 /
    256 and group 8 at the tiles the shapes choose."""
    from dlrover_tpu.models import qwen3_next

    cfg = qwen3_next.Qwen3NextConfig(
        vocab_size=18992, n_layers=8, experts_held=32, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    lp = {
        name: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        for name, (shape, _, _) in qwen3_next._block_shapes(cfg, kind).items()
    }
    x = jax.ShapeDtypeStruct((1, 16384, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    trace.gauge("attn.out_kept", 0)
    fn = qwen3_next._block_fn(cfg, None, kind)
    compiled = jax.jit(_two_in_line(
        lambda lp, x: fn(lp, x)[0])).lower(lp, x).compile()
    hlo = compiled.as_text()
    # since PR 46 a gated attention block keeps the flash forward's
    # output and lse: one forward call a block where `nothing_saveable`
    # made 2 + 1 for the pair
    flash = 1 if kind == "F" else 0
    assert _kernel_calls(hlo, "attention_fwd") == 2 * flash
    assert trace.gauges()["attn.out_kept"] == flash
    assert _kernel_calls(hlo, "attention_bwd") == 4 * flash
    assert _kernel_calls(hlo, "grouped_matmul") == 21
    delta = [n for n in _op_names(hlo) if "/gdn_" in n or "/kda_" in n]
    if flash:
        assert not delta
        assert (trace.gauges()["attn.block_q"],
                trace.gauges()["attn.block_k"]) == (256, 512)
    else:
        # the first block's forward, both recomputed forwards and both
        # backwards (the rule's state is not kept: 512 MiB a layer); the
        # input pass runs twice a direction: q and k over 16 heads, v
        # over 32
        assert collections.Counter((n.split("/")[-2], next(
            s for s in ("gdn_conv", "gdn_chunk", "gdn_out")
            if _in_scope(n, s))) for n in delta) == {
            ("gdn_bwd", "gdn_chunk"): 2, ("gdn_fwd", "gdn_chunk"): 3,
            ("kda_in_bwd", "gdn_conv"): 4, ("kda_in_fwd", "gdn_conv"): 6,
            ("kda_out_bwd", "gdn_out"): 2, ("kda_out_fwd", "gdn_out"): 3}
        assert trace.gauges()["attn.gdn_kernel"] == 1
        assert trace.gauges()["kda.io_fused"] == 1
        assert "riangular" not in hlo
    # two blocks' temporaries fit beside the cell's 6.56 GiB of state
    # (4.850 GiB the Gated DeltaNet pair, 4.371 the attention pair with
    # its kept 129 MiB)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4.6 if flash else 5.0) * 2**30
    assert trace.gauges()["moe.rows_held"] == 10240
    assert trace.gauges()["moe.shared_gate"] == 1
