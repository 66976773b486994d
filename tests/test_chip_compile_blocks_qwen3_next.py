"""The qwen3_next family's blocks, two in line, forward and backward, compiled
at real widths for a described v5e (the other families':
``test_chip_compile_blocks_*.py``; see ``test_chip_compile.py``, which
holds the kernels' own checks, ``test_chip_compile_steps.py`` for a cell's
whole step, and ``tests/chip_compile.py`` for what the files share)."""

import collections

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.observability import trace
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _in_scope, _kernel_calls, _op_names, _two_in_line, kernels_are_the_path,
    one_chip, topo)


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
