"""A cell's whole step and the families' blocks, compiled at real widths
for a described v5e (see ``test_chip_compile.py``, which holds the kernels'
own checks, and ``tests/chip_compile.py`` for what the files share). These
are the long compiles, a file of their own so that no one file sets the
pace of a ``--dist loadfile`` run."""

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
# The parent's step peaks at 15,186,436,096 bytes (14.143 GiB); the two
# kept arrays and some slack may be added to it, no more.
DOTS3_PARENT_STEP_PEAK = 15186436096


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
    assert fam.cfg.layer_kinds.count("F") == 2
    for name, calls in (("dsa_index_fwd", 2), ("dsa_probs", 2),
                        ("dsa_index_bwd_dq", 2), ("dsa_index_bwd_dk", 2),
                        ("attention_fwd_sel", 4)):
        assert _kernel_calls(hlo, name) == calls, name
    assert trace.gauges()["dsa.loss_grad_kept"] == 1
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
    assert peak <= DOTS3_PARENT_STEP_PEAK + 560 * 2**20
    assert peak <= 15.75 * 2**30


# xing4-ep8-1chip-steady (PR 31): one whole expert block of the step at
# the published widths (four streams of 2 x 4096 x 3584, ranks 768 / 512,
# 8 held experts of 64, the shared expert), forward and backward, remat
# as the cell runs it.
def test_xing4_expert_block_fwd_bwd_compiles(one_chip, kernels_are_the_path):
    from dlrover_tpu.models import xing4
    from dlrover_tpu.ops import yarn_frequencies

    cfg = xing4.Xing4Config(
        vocab_size=16384, n_dense_layers=1, n_moe_layers=1, experts_held=8,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    layers = xing4.abstract_params(cfg)["layers"]
    lp = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype,
                                       sharding=one_chip), layers)
    X = jax.ShapeDtypeStruct((4, 2, 4096, cfg.dim), jnp.bfloat16,
                             sharding=one_chip)

    def loss(lp, X):
        positions = jnp.broadcast_to(jnp.arange(4096, dtype=jnp.int32),
                                     (2, 4096))
        inv_freq = yarn_frequencies(64, 10000.0, 64.0, 4096)
        fn = jax.checkpoint(
            lambda lp, X: xing4.block(cfg, None, positions, inv_freq, lp, X),
            policy=jax.checkpoint_policies.nothing_saveable)
        return fn(lp, X).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(lp, X).compile()
    hlo = compiled.as_text()
    # the remat forward is the only forward here (nothing else wants the
    # block's output): 1 + 2 of attention, and forward, d-lhs and d-rhs
    # of each of the three grouped products; act(gate) x up and its
    # backward as passes
    assert _kernel_calls(hlo, "attention_fwd") == 1
    assert _kernel_calls(hlo, "attention_bwd") == 2
    assert _kernel_calls(hlo, "grouped_matmul_dlhs") == 3
    assert _kernel_calls(hlo, "grouped_matmul_drhs") == 3
    assert _kernel_calls(hlo, "grouped_matmul") == 9
    assert _kernel_calls(hlo, "moe_rows_gated") == 2
    assert trace.gauges()["moe.tail_skipped"] == 1
    assert "[8192,64,8" not in hlo  # no (tokens, experts, ...) dispatch tensor
    # a block's own temporaries fit beside the cell's state and carries
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

    def loss(lp, x):
        fn = jax.checkpoint(
            lambda lp, x: kimi_linear.block(cfg, None, attn, "moe", lp, x),
            policy=jax.checkpoint_policies.nothing_saveable)
        return fn(lp, x).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(lp, x).compile()
    hlo = compiled.as_text()
    # latent attention without rotary still runs the 192 / 128 kernels;
    # a KDA block runs none of them and its own two instead: the remat
    # forward and the backward (the loss's value is not asked for, so
    # the first forward is gone), both under the scope the device
    # metrics select by
    flash = 1 if attn == "mla" else 0
    assert _kernel_calls(hlo, "attention_fwd") == flash
    assert _kernel_calls(hlo, "attention_bwd") == 2 * flash
    assert _kernel_calls(hlo, "grouped_matmul") == 9
    assert _kernel_calls(hlo, "moe_rows_gated") == 2
    delta = [n for n in _op_names(hlo) if "/kda_" in n]
    if flash:
        assert not delta
    else:
        # each kernel once, under its layer's scope, the backward's too
        # (the first forward is gone, so a forward kernel runs once)
        assert sorted((n.split("/")[-2], next(
            s for s in ("kda_conv", "kda_chunk", "kda_out")
            if _in_scope(n, s))) for n in delta) == [
            ("kda_bwd", "kda_chunk"), ("kda_fwd", "kda_chunk"),
            ("kda_in_bwd", "kda_conv"), ("kda_in_fwd", "kda_conv"),
            ("kda_out_bwd", "kda_out"), ("kda_out_fwd", "kda_out")]
        assert trace.gauges()["kda.io_fused"] == 1
        # the XLA form of the passes took float32 copies of every
        # activation into another layout and back: none is left
        assert not _wide_f32(hlo, "copy")
        # what the XLA form of the rule cost beside its loops: the solves
        # and the float32 moves of (8192, 4096) into chunk-major order
        assert "riangular" not in hlo
        assert not [line for line in _wide_f32(hlo, "transpose")
                    if _in_scope(line, "kda_chunk")]
    # a block's own temporaries fit beside the cell's 7.16 GiB of state
    # and 4.78 of float32 gradients; a KDA block's are under what they
    # were with the passes in XLA ops (2.857 GiB; 2.10 now)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        3 if flash else 2.3) * 2**30
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

    def loss(lp, x):
        fn = jax.checkpoint(
            lambda lp, x: qwen3_next.block(cfg, None, kind, lp, x)[0],
            policy=jax.checkpoint_policies.nothing_saveable)
        return fn(lp, x).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(lp, x).compile()
    hlo = compiled.as_text()
    flash = 1 if kind == "F" else 0
    assert _kernel_calls(hlo, "attention_fwd") == flash
    assert _kernel_calls(hlo, "attention_bwd") == 2 * flash
    assert _kernel_calls(hlo, "grouped_matmul") == 9
    delta = [n for n in _op_names(hlo) if "/gdn_" in n or "/kda_" in n]
    if flash:
        assert not delta
        assert (trace.gauges()["attn.block_q"],
                trace.gauges()["attn.block_k"]) == (256, 512)
    else:
        # the remat forward and the backward of each (the first forward
        # is gone with the loss's value); the input pass runs twice a
        # direction: q and k over 16 heads, v over 32
        assert sorted((n.split("/")[-2], next(
            s for s in ("gdn_conv", "gdn_chunk", "gdn_out")
            if _in_scope(n, s))) for n in delta) == [
            ("gdn_bwd", "gdn_chunk"), ("gdn_fwd", "gdn_chunk"),
            ("kda_in_bwd", "gdn_conv"), ("kda_in_bwd", "gdn_conv"),
            ("kda_in_fwd", "gdn_conv"), ("kda_in_fwd", "gdn_conv"),
            ("kda_out_bwd", "gdn_out"), ("kda_out_fwd", "gdn_out")]
        assert trace.gauges()["attn.gdn_kernel"] == 1
        assert trace.gauges()["kda.io_fused"] == 1
        assert "riangular" not in hlo
    # a block's temporaries fit beside the cell's 6.56 GiB of state
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
    assert trace.gauges()["moe.rows_held"] == 10240
    assert trace.gauges()["moe.shared_gate"] == 1
