"""The main path's kernels that are not attention's, compiled at real widths
for a described v5e: the delta rules and the passes around them, the
stream mixing, the grouped product, fused cross-entropy and the
state-space scan, on one chip and over four (see ``test_chip_compile.py``,
which holds attention's and the selection's and says what a compile for a
described chip shows; ``tests/chip_compile.py`` for what the files
share)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import fused_ce, kda
from dlrover_tpu.parallel.mesh import BATCH_AXES
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _compile, _in_scope, _kernel_calls, _op_names, kernels_are_the_path, mesh4,
    one_chip, topo)


def _kda_args(sharding, batch=1):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    wide = (batch, 8192, 32, 128)
    return [arg(wide, jnp.bfloat16)] * 3 + [
        arg(wide, jnp.float32), arg(wide[:3], jnp.float32)]


def _kda_loss(mesh=None):
    def loss(*a):
        with jax.named_scope("kda_chunk"):      # as kda_attention calls it
            o = kda.chunk_kda(*a, chunk=64, mesh=mesh)
        return o.astype(jnp.float32).sum()
    return loss




def test_chunked_delta_rule_fwd_bwd_compiles_in_its_memory(one_chip):
    """``ops/kda.py``'s XLA form at the kimi-linear cell's shapes: what
    its backward keeps is one 16-chunk segment's intermediates, not the
    sequence's (3.39 GiB before the segments, which the step could not
    hold)."""
    compiled = jax.jit(jax.grad(_kda_loss(), argnums=range(5))).lower(
        *_kda_args(one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75 * 2**30
    assert not _op_names(compiled.as_text())
    assert trace.gauges()["kda.kernel"] == 0


def test_chunked_delta_rule_kernels_compile_in_the_same_memory(
        one_chip, kernels_are_the_path):
    """The Pallas kernels there: one call forward; under differentiation
    the forward again with a state a chunk (256 MiB, all the backward
    keeps beside the inputs) and the hand-written backward. Nothing
    passes between kernels but that, so no segments."""
    args = _kda_args(one_chip)
    names = _op_names(_compile(_kda_loss(), *args))
    assert len(names) == 1 and _in_scope(names[0], "kda_chunk")
    compiled = jax.jit(jax.grad(_kda_loss(), argnums=range(5))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75 * 2**30
    hlo = compiled.as_text()
    names = _op_names(hlo)
    assert len(names) == 2 and all(_in_scope(n, "kda_chunk") for n in names)
    assert sum("kda_fwd" in n for n in names) == 1
    assert sum("kda_bwd" in n for n in names) == 1
    assert "riangular" not in hlo         # no triangular_solve is left
    assert trace.gauges()["kda.kernel"] == 1
    assert trace.gauges()["kda.heads_per_step"] == 4
    assert trace.gauges()["kda.chunks_per_step"] == 2


def _gdn_args(sharding, seq=16384):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return [arg((1, seq, 16, 128), jnp.bfloat16)] * 2 + [
        arg((1, seq, 32, 128), jnp.bfloat16),
        arg((1, seq, 32), jnp.float32), arg((1, seq, 32), jnp.float32)]


def _gdn_loss(*a):
    with jax.named_scope("gdn_chunk"):          # as gdn_attention calls it
        o = kda.chunk_gdn(*a, chunk=64)
    return o.astype(jnp.float32).sum()


def test_per_head_delta_rule_kernels_compile_at_the_cells_shapes(
        one_chip, kernels_are_the_path):
    """The per-head form (one decay a head, 32 value heads over 16 key
    heads, 16384 tokens: the qwen3next cell's layer): one call forward,
    under differentiation the forward with a state a chunk (512 MiB) and
    the hand-written backward; no triangular solve and no scan is left."""
    args = _gdn_args(one_chip)
    names = _op_names(_compile(_gdn_loss, *args))
    assert len(names) == 1 and _in_scope(names[0], "gdn_chunk")
    compiled = jax.jit(jax.grad(_gdn_loss, argnums=range(5))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30
    hlo = compiled.as_text()
    names = _op_names(hlo)
    assert len(names) == 2 and all(_in_scope(n, "gdn_chunk") for n in names)
    assert sum("gdn_fwd" in n for n in names) == 1
    assert sum("gdn_bwd" in n for n in names) == 1
    assert "riangular" not in hlo
    assert trace.gauges()["attn.gdn_kernel"] == 1


def _kda_io_losses(mesh=None):
    """The KDA layer's two elementwise passes at the kimi-linear cell's
    shapes, under the scopes ``kimi_linear.kda_attention`` opens."""
    def inputs(xs, taps):
        with jax.named_scope("kda_conv"):
            out = kda.conv_silu_norm(xs, taps, heads=32,
                                     scales=(128 ** -0.5, 1.0, None), mesh=mesh)
        return sum(o.astype(jnp.float32).sum() for o in out)

    def output(o, gate, weight):
        with jax.named_scope("kda_out"):
            out = kda.norm_gate(o, gate, weight, 1e-5, mesh=mesh)
        return out.astype(jnp.float32).sum()

    return {"kda_conv": (inputs, "kda_in"), "kda_out": (output, "kda_out")}


def _kda_io_args(scope, sharding, replicated, batch=1):
    def arg(shape, at=sharding):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=at)

    if scope == "kda_conv":
        return ((arg((batch, 8192, 32 * 128)),) * 3,
                (arg((32 * 128, 4), replicated),) * 3)
    wide = arg((batch, 8192, 32, 128))
    return wide, wide, arg((128,), replicated)


def _assert_one_pass_each_way(scope, hlo_fwd, hlo_grad, kernel):
    """One call forward; under differentiation (no value asked for) the
    backward's alone, which opens the scope itself. Nothing else of the
    pass is a kernel, and every call lies in the pass's scope."""
    names = _op_names(hlo_fwd)
    assert len(names) == 1 and f"{kernel}_fwd" in names[0]
    assert _in_scope(names[0], scope)
    names = _op_names(hlo_grad)
    assert len(names) == 1 and f"{kernel}_bwd" in names[0]
    assert _in_scope(names[0], scope)


@pytest.mark.parametrize("scope", ["kda_conv", "kda_out"])
def test_kda_elementwise_passes_compile(one_chip, kernels_are_the_path, scope):
    loss, kernel = _kda_io_losses()[scope]
    args = _kda_io_args(scope, one_chip, one_chip)
    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    _assert_one_pass_each_way(
        scope, _compile(loss, *args), _compile(grad, *args), kernel)
    assert trace.gauges()["kda.io_fused"] == 1




def _hc_sublayer(mesh, streams, replicated, batch=2):
    """One sublayer's stream mixing at the xing4 cell's widths, ``fn``
    the identity: ``(loss, its arguments)``."""
    from dlrover_tpu.models import xing4

    cfg = xing4.Xing4Config()

    def loss(X, phi, alpha, bias):
        lp = {"hc_phi": phi, "hc_alpha": alpha, "hc_bias": bias}
        out = xing4.hc_sublayer(cfg, lp, "hc", X, lambda y: y, mesh=mesh)
        return out.astype(jnp.float32).sum()

    def arg(shape, at=replicated):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=at)

    return loss, (arg((4, batch, 4096, cfg.dim), streams),
                  arg((4, cfg.dim, cfg.hc_width)), arg((3,)),
                  arg((cfg.hc_width,)))


def _assert_the_hc_passes(hlo_fwd, hlo_grad):
    """Two passes forward; under differentiation (no value asked for)
    the pre-mix's forward and both backwards, which open the scope
    themselves. Every call lies in ``hc_mix``."""
    for hlo, kernels in ((hlo_fwd, ["hc_pre_fwd", "hc_post_fwd"]),
                         (hlo_grad, ["hc_pre_fwd", "hc_post_bwd",
                                     "hc_pre_bwd"])):
        names = _op_names(hlo)
        assert sorted(part for name in names for part in name.split("/")
                      if part.startswith("hc_p")) == sorted(kernels)
        assert all(_in_scope(name, "hc_mix") for name in names)
    assert trace.gauges()["layers.hc_fused"] == 1


def test_hc_mix_passes_compile(one_chip, kernels_are_the_path):
    loss, args = _hc_sublayer(None, one_chip, one_chip)
    _assert_the_hc_passes(
        _compile(loss, *args),
        _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), *args))


def test_grouped_matmul_compiles_at_xing4_shape(
        one_chip, kernels_are_the_path):
    # one grouped product of that block alone, forward and backward:
    # 32768 rows of which the 8 held experts own what the router sends
    # (the rest is the tail the kernels only zero), 3584 -> 1024, bf16
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    x = jax.ShapeDtypeStruct((32768, 3584), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 3584, 1024), jnp.bfloat16,
                             sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def loss(x, w, sizes):
        return grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1)), x, w, sizes)
    assert _kernel_calls(hlo, "grouped_matmul_dlhs") == 1
    assert _kernel_calls(hlo, "grouped_matmul_drhs") == 1
    assert _kernel_calls(hlo, "grouped_matmul") == 3
    assert "ragged-dot" not in hlo


def test_grouped_matmul_falls_back_where_shapes_do_not_tile(
        one_chip, kernels_are_the_path):
    # an expert width that is no multiple of 128: the compiler's own
    # grouped kernel takes it (lax.ragged_dot), not a masked dense dot
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 2048, 1000), jnp.bfloat16,
                             sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    hlo = _compile(grouped_matmul, x, w, sizes)
    assert "ragged-dot" in hlo and _kernel_calls(hlo, "grouped_matmul") == 0


# (tokens, d, vocab): Llama-3-8B's head at seq 2048, the widths the
# backward was refused at under the default 16 MiB of scoped VMEM
# ("Scoped allocation with size 22.52M and limit 16.00M" in the dx
# kernel at d=4096, 18.00M in the dw kernel at d=2048 with 8192 tokens),
# and Llama-3-70B's d=8192, where the tiles have to shrink as well
CE_SHAPES = [(2048, 4096, 128256), (8192, 2048, 32768),
             (2048, 8192, 128256),
             (8192, 2048, 50304),   # OLMoE's head: vocabulary tile 384
             (8192, 4096, 32768)]   # mistral7b-d5-steady's


def _ce_args(n, d, v, sharding):
    return (
        jax.ShapeDtypeStruct((1, n, d), jnp.bfloat16, sharding=sharding),
        jax.ShapeDtypeStruct((d, v), jnp.bfloat16, sharding=sharding),
        jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=sharding),
    )


def _fused_nll(x, w, t):
    return fused_ce._fused_ce(
        fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, False, x, w, t
    )[0]


@pytest.mark.parametrize("n,d,v", CE_SHAPES)
def test_fused_ce_fwd_compiles(one_chip, n, d, v):
    hlo = _compile(_fused_nll, *_ce_args(n, d, v, one_chip))
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("n,d,v", CE_SHAPES)
def test_fused_ce_fwd_bwd_compiles(one_chip, n, d, v):
    hlo = _compile(
        jax.grad(_fused_nll, argnums=(0, 1)), *_ce_args(n, d, v, one_chip)
    )
    # the forward sweep that also carries dX's softmax term, and dw
    assert hlo.count("tpu_custom_call") == 2
    # a vocabulary some multiple of 128 up to the tile divides is not
    # padded to the tile into a copy of the head (50304 -> 50688 at 512)
    assert f",{-(-v // 512) * 512}]" not in hlo or v % 512 == 0


# dots3-ep32-1chip-steady's head (PR 40): the first cell past d = 4096.
# At 5120 the three kernels keep the default tiles (256 tokens x 512
# columns: dw's blocks are 36.7 MiB of the 48 MiB budget; they halve from
# d = 8192); 19008 columns are no multiple of 128 and pad to 19456.
def test_fused_ce_compiles_at_dots3_width(one_chip):
    n, d, v = 8192, 5120, 19008
    for kernel in (fused_ce.LOSS, fused_ce.LOSS_DX, fused_ce.DW):
        assert fused_ce._tile_geometry(
            n, v, d, jnp.bfloat16, jnp.bfloat16, fused_ce.DEFAULT_BLOCK_T,
            fused_ce.DEFAULT_BLOCK_V, kernel) == (256, 512, 8192, 19456)
    args = _ce_args(n, d, v, one_chip)
    assert _compile(_fused_nll, *args).count("tpu_custom_call") == 1
    hlo = _compile(jax.grad(_fused_nll, argnums=(0, 1)), *args)
    assert hlo.count("tpu_custom_call") == 2
    for name in ("fused_ce_fwd", "fused_ce_bwd_dw"):
        assert _kernel_calls(hlo, name) == 1, name


def test_chunked_delta_rule_compiles_over_four_chips(
        mesh4, kernels_are_the_path):
    # a sequence a device: the same kernels on each device's batch row
    args = _kda_args(NamedSharding(mesh4, P(BATCH_AXES)), batch=4)
    hlo = _compile(jax.grad(_kda_loss(mesh4), argnums=range(5)), *args)
    names = _op_names(hlo)
    assert len(names) == 2 and all(_in_scope(n, "kda_chunk") for n in names)


def test_fused_ce_compiles_over_four_chips(mesh4, kernels_are_the_path):
    n, d, v = 2048, 4096, 128256
    x = jax.ShapeDtypeStruct(
        (4, n, d), jnp.bfloat16,
        sharding=NamedSharding(mesh4, P(BATCH_AXES, None, None)))
    w = jax.ShapeDtypeStruct(
        (d, v), jnp.bfloat16, sharding=NamedSharding(mesh4, P("fsdp", None)))
    t = jax.ShapeDtypeStruct(
        (4, n), jnp.int32, sharding=NamedSharding(mesh4, P(BATCH_AXES, None)))

    def nll(x, w, t):
        return fused_ce.cross_entropy_sums(x, w, t, mesh=mesh4)[0]

    fused_ce.reset_sweep_report()
    hlo = _compile(jax.grad(nll, argnums=(0, 1)), x, w, t)
    assert hlo.count("tpu_custom_call") == 2
    assert "all-gather" in hlo  # the fsdp-sharded head, gathered whole
    # the gauge says the same of each shard's loss
    assert trace.gauges()["fused_ce.logit_sweeps"] == 2


@pytest.mark.parametrize("scope", ["kda_conv", "kda_out"])
def test_kda_elementwise_passes_compile_over_four_chips(
        mesh4, kernels_are_the_path, scope):
    """Under ``shard_map`` on each chip's batch rows, the taps and the
    norm's weight replicated (their gradients summed over the chips)."""
    loss, kernel = _kda_io_losses(mesh4)[scope]
    args = _kda_io_args(scope, NamedSharding(mesh4, P(BATCH_AXES)),
                        NamedSharding(mesh4, P()), batch=4)
    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    _assert_one_pass_each_way(
        scope, _compile(loss, *args), _compile(grad, *args), kernel)


def test_hc_mix_passes_compile_over_four_chips(mesh4, kernels_are_the_path):
    """Under ``shard_map`` on each chip's batch rows, ``phi``, ``alpha``
    and the bias replicated (their gradients summed over the chips)."""
    loss, args = _hc_sublayer(
        mesh4, NamedSharding(mesh4, P(None, BATCH_AXES)),
        NamedSharding(mesh4, P()), batch=4)
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)
    _assert_the_hc_passes(_compile(loss, *args), hlo)
    assert "all-reduce" in hlo


# granite4h-ep8-1chip-steady (PR 52): the state-space scan at the cell's
# shape, 32 heads of 64 (a pair a lane tile), one group of state 128, 64
# chunks of 256. One call forward; under differentiation the forward with
# a float32 state a chunk (64 MiB) and the hand-written backward, whose
# sums of the per-head cotangents XLA closes under the same scope.
def test_ssd_kernels_compile_at_the_cells_shape(one_chip,
                                                kernels_are_the_path):
    from dlrover_tpu.ops import ssd

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((1, 16384, 32, 64)), sds((1, 16384, 32), jnp.float32),
            sds((32,), jnp.float32), sds((1, 16384, 128)),
            sds((1, 16384, 128)), sds((32,), jnp.float32))

    def loss(*operands):
        with jax.named_scope("ssm_chunk"):
            return ssd.ssd(*operands, chunk=256).astype(jnp.float32).sum()

    names = _op_names(_compile(loss, *args))
    assert len(names) == 1 and _in_scope(names[0], "ssm_chunk")
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
    hlo = compiled.as_text()
    names = _op_names(hlo)
    assert len(names) == 2 and all(_in_scope(n, "ssm_chunk") for n in names)
    assert _kernel_calls(hlo, "ssd_bwd") == 1
    assert "f32[1,64,2048,128]" in hlo          # a state a chunk
    assert trace.gauges()["ssm.kernel"] == 1
    assert ssd._heads_a_step(32, 64) == 32      # C B^T once a chunk
