"""The kimi_linear family's blocks, two in line, forward and backward,
compiled at real widths for a described v5e (the other families':
``test_chip_compile_blocks_*.py``; see ``test_chip_compile.py``, which
holds the kernels' own checks, ``test_chip_compile_steps.py`` for a cell's
whole step, and ``tests/chip_compile.py`` for what the files share)."""

import collections

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.observability import trace
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _in_scope, _kernel_calls, _op_names, _two_in_line, _wide_f32,
    kernels_are_the_path, one_chip, topo)


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
