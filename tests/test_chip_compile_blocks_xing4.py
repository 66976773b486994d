"""The xing4 family's blocks, two in line, forward and backward, compiled at
real widths for a described v5e (the other families':
``test_chip_compile_blocks_*.py``; see ``test_chip_compile.py``, which
holds the kernels' own checks, ``test_chip_compile_steps.py`` for a cell's
whole step, and ``tests/chip_compile.py`` for what the files share)."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.observability import trace
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _kernel_calls, _two_in_line, _wide_f32, kernels_are_the_path, one_chip,
    topo)


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
