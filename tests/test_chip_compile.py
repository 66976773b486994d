"""The main path's kernels, compiled at real widths for a described v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): what it
refuses here it refuses on the chip — a block that overflows scoped
VMEM, a misaligned slice, a Mosaic kernel left to the automatic
partitioner — and interpret mode shows none of that. Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture, never at import
(``tests/chip_compile.py``, with the readers of a compiled program's text
that this file shares with ``test_chip_compile_steps.py``, a cell's whole
step and the families' blocks, and ``test_chip_compile_experts.py``, the
expert layer).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    attention, blocksel, dsa, fused_ce, kda, lightning)
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel.mesh import BATCH_AXES
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _compile, _in_scope, _kernel_calls, _op_names, kernels_are_the_path,
    one_chip, topo)


# Llama-3-8B attention: b1, s2048, 32 q / 8 kv heads, head_dim 128
def _qkv(sharding):
    q = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


FLASH_TILES = [(128, 128), (512, 1024)]


@pytest.mark.parametrize("bq,bk", FLASH_TILES)
def test_flash_fwd_compiles(one_chip, bq, bk):
    hlo = _compile(
        lambda q, k, v: attention._flash_fwd_pallas(q, k, v, True, bq, bk),
        *_qkv(one_chip),
    )
    assert hlo.count("tpu_custom_call") == 1


@pytest.mark.parametrize("bq,bk", FLASH_TILES)
def test_flash_fwd_bwd_compiles(one_chip, kernels_are_the_path, bq, bk):
    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, True, bq, bk)
        return out.astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dk/dv


# The listed cells' own attention shapes, bf16, causal, 32 q / 8 kv
# heads of 128, at the tiles the kernels choose for themselves: a tile
# the v5e's compiler refuses fails here and not in the chip run.
CELL_SEQ, CELL_HEADS, CELL_KV_HEADS, CELL_HEAD_DIM = 4096, 32, 8, 128


def _cell_qkv(batch, sharding):
    q = jax.ShapeDtypeStruct(
        (batch, CELL_SEQ, CELL_HEADS, CELL_HEAD_DIM), jnp.bfloat16,
        sharding=sharding)
    kv = jax.ShapeDtypeStruct(
        (batch, CELL_SEQ, CELL_KV_HEADS, CELL_HEAD_DIM), jnp.bfloat16,
        sharding=sharding)
    return q, kv, kv


def _chosen_loss(mesh=None):
    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, mesh=mesh)  # tiles: chosen
        return out.astype(jnp.float32).sum()
    return loss


def test_cell_tiles_are_larger_than_128():
    tiles = attention.flash_tiles(
        CELL_SEQ, CELL_SEQ, CELL_HEAD_DIM, CELL_HEADS // CELL_KV_HEADS,
        jnp.bfloat16)
    assert all(min(t) > 128 for t in tiles.values()), tiles


def test_flash_one_chip_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path):
    # mistral7b-d5-steady: 2 sequences a step on one chip
    args = _cell_qkv(2, one_chip)
    assert _compile(_chosen_loss(), *args).count("tpu_custom_call") == 1
    hlo = _compile(jax.grad(_chosen_loss(), argnums=(0, 1, 2)), *args)
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dk/dv


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "b,s,h,hkv,d",
    [(1, 1024, 32, 8, 128),   # a ring chunk: diagonal and off it
     (8, 196, 12, 12, 64),    # ViT-B/16's patches: one whole block
     (8, 197, 12, 12, 64),    # with the class token: a prime
     (1, 8192, 64, 8, 128)],  # group 8 (Llama-3-70B heads)
)
def test_flash_other_callers_compile_at_chosen_tiles(
        one_chip, kernels_are_the_path, b, s, h, hkv, d, causal):
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out, lse = attention.flash_attention_with_lse(q, k, v, causal)
        return out.astype(jnp.float32).sum() + lse.sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert hlo.count("tpu_custom_call") == 3


# olmoe-1chip-steady: 16 query = 16 kv heads of 128 (group 1) at the
# model's whole context, a shape no Mistral cell has
def test_flash_olmoe_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path):
    tiles = attention.flash_tiles(4096, 4096, 128, 1, jnp.bfloat16)
    assert all(min(t) > 128 for t in tiles.values()), tiles
    q = jax.ShapeDtypeStruct((2, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert _compile(_chosen_loss(), q, q, q).count("tpu_custom_call") == 1
    hlo = _compile(jax.grad(_chosen_loss(), argnums=(0, 1, 2)), q, q, q)
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dk/dv


# smallthinker-ep4-1chip-steady (PR 37): 28 query heads on 4 kv heads of
# 128 (group 7) at 16384 positions, the full layers' causal kernels and
# the window layers' (window 4096: the band's walk, kernels named _swa),
# at the tiles the kernels choose
@pytest.mark.parametrize("window", [None, 4096])
def test_flash_smallthinker_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path, window):
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, window=window)
        return out.astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert hlo.count("tpu_custom_call") == 3
    suffix = "_swa" if window else ""
    for name in ("attention_fwd", "attention_bwd_dq", "attention_bwd_dkv"):
        assert re.search(rf"%{name}{suffix}(\.\d+)? = ", hlo), name
    assert ("_swa" in hlo) == bool(window)


# dots3-ep32-1chip-steady (PR 40): b1, s8192. A full layer's 32 held
# heads of 192 / 128 read the selection as an int8 operand, tile by tile,
# in all three kernels; a window layer's 16 held heads of 256 / 128 walk
# a band of 513, which no tile divides.
@pytest.mark.parametrize("kind", ["select", "window"])
def test_flash_dots3_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path, kind):
    heads, d = (32, 192) if kind == "select" else (16, 256)
    q = jax.ShapeDtypeStruct((1, 8192, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8,
                                sharding=one_chip)

    def loss(q, k, v, mask):
        kw = dict(select=mask) if kind == "select" else dict(window=513)
        return attention.flash_attention(q, k, v, **kw).astype(
            jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, v, mask)
    assert hlo.count("tpu_custom_call") == 3
    suffix = "_sel" if kind == "select" else "_swa"
    for name in ("attention_fwd", "attention_bwd_dq", "attention_bwd_dkv"):
        assert re.search(rf"%{name}{suffix}(\.\d+)? = ", hlo), name
    # the selection is one byte a pair: never widened to a tensor a head
    assert "s8[1,8192,8192]" in hlo or kind == "window"
    assert not re.search(r"\[1,32,8192,8192\]|\[1,8192,8192,32\]", hlo)


# The indexer at the same cell: 64 index heads of 128 against one index
# key a position; forward and the backward L_I needs; and the kernel
# that sums the main attention's probabilities over the 32 held heads.
# No (8192, 8192, 64) array is in either program.
def test_dsa_index_kernels_compile_at_the_cell_shape(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 8192, 128), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1, 8192, 64), jnp.float32, sharding=one_chip)

    def loss(q, k, w):
        return jnp.sum(dsa.index_scores(q, k, w) ** 2)

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, w)
    # (alone under grad XLA names the forward's call after its jvp scope)
    for name in ("dsa_index_fwd", "dsa_index_bwd_dq", "dsa_index_bwd_dk"):
        assert sum("custom-call(" in line and name in line.split(" = ")[0]
                   for line in hlo.splitlines()) == 1, name
    assert not re.search(r"8192,8192,64\]|8192,64,8192\]|64,8192,8192\]", hlo)


def test_dsa_probs_kernel_compiles_at_the_cell_shape(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8,
                                sharding=one_chip)
    hlo = _compile(
        lambda q, k, lse, mask: dsa.head_summed_probs(
            q, k, lse, mask, 192 ** -0.5), q, q, lse, mask)
    assert hlo.count("tpu_custom_call") == 1 and "dsa_probs" in hlo
    assert not re.search(r"32,8192,8192\]|8192,8192,32\]", hlo)


# The threshold of both cells that select (PR 55): one kernel whose grid
# step holds 256 whole rows at 8192 positions and 128 at 16384 (8 MiB of
# scores a block, twice for the pipeline, and the int32 keys), the mask
# its one result; no array of ordered bits and no XLA pass over (s, s).
@pytest.mark.parametrize("s,rows", [(8192, 256), (16384, 128)])
def test_dsa_select_kernel_compiles_at_the_cells_shapes(
        one_chip, kernels_are_the_path, s, rows):
    scores = jax.ShapeDtypeStruct((1, s, s), jnp.float32, sharding=one_chip)
    assert dsa._select_rows(s) == rows
    hlo = _compile(lambda x: dsa.selection_mask(x, 2048), scores)
    assert hlo.count("tpu_custom_call") == 1
    assert re.search(r"%dsa_select(\.\d+)? = ", hlo)
    assert f"u32[1,{s},{s}]" not in hlo and "while(" not in hlo


# keye-vl-ep8-1chip-steady (PR 54): b1, s16384. 32 query heads on 4 key
# heads of 128 (group 8) read the selection tile by tile in all three
# `_sel` kernels; the indexer's 16 heads of 64 (half a lane tile) against
# one key of 64; `dsa_probs` reads the key head where it lies. No array
# a head over (16384, 16384) and no key repeated eight times is in a
# program.
def test_flash_keye_vl_cell_compiles_over_the_selection_at_group_8(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8,
                                sharding=one_chip)

    def loss(q, k, v, mask):
        return attention.flash_attention(q, k, v, select=mask).astype(
            jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k, mask)
    assert hlo.count("tpu_custom_call") == 3
    for name in ("attention_fwd", "attention_bwd_dq", "attention_bwd_dkv"):
        assert re.search(rf"%{name}_sel(\.\d+)? = ", hlo), name
    assert "s8[1,16384,16384]" in hlo
    assert not re.search(r"\[1,(4|32),16384,16384\]", hlo)


def test_dsa_index_kernels_compile_at_sixteen_heads_of_64(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 16384, 16, 64), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 64), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1, 16384, 16), jnp.float32, sharding=one_chip)

    def loss(q, k, w):
        return jnp.sum(dsa.index_scores(q, k, w) ** 2)

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, w)
    for name in ("dsa_index_fwd", "dsa_index_bwd_dq", "dsa_index_bwd_dk"):
        assert sum("custom-call(" in line and name in line.split(" = ")[0]
                   for line in hlo.splitlines()) == 1, name
    assert not re.search(
        r"16384,16384,16\]|16384,16,16384\]|16,16384,16384\]", hlo)


def test_dsa_probs_kernel_reads_grouped_keys_where_they_lie(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, 16384), jnp.float32,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8,
                                sharding=one_chip)
    hlo = _compile(
        lambda q, k, lse, mask: dsa.head_summed_probs(
            q, k, lse, mask, 128 ** -0.5), q, k, lse, mask)
    assert hlo.count("tpu_custom_call") == 1 and "dsa_probs" in hlo
    # the key goes in at its 4 heads: nothing of it at 32
    assert not re.search(r"bf16\[1,(32,16384|16384,32),128\][^\n]*broadcast",
                         hlo)
    assert not re.search(r"32,16384,16384\]|16384,16384,32\]", hlo)


# minicpm-sala-d4-1chip-steady (PR 48): b1, s16384. The minicpm4 layer's
# 32 query heads on 2 key heads of 128 (group 16) read a choice of blocks a
# key-value head, (1, 2, 16384, 256) int8, in all three kernels: forward
# and dq a q block's whole strip, spread over a k block's lanes by a
# product with a 0 / 1 matrix; dk/dv the transposed choice's 16 rows of
# its k block, each repeated over 64 sublanes. No key-level mask exists.
def test_flash_minicpm_sala_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16,
                             sharding=one_chip)
    chosen = jax.ShapeDtypeStruct((1, 2, 16384, 256), jnp.int8,
                                  sharding=one_chip)

    def loss(q, k, v, chosen):
        return attention.flash_attention(
            q, k, v, select=chosen, select_block=64).astype(jnp.float32).sum()

    assert attention.flash_tiles(16384, 16384, 128, 16, jnp.bfloat16) == {
        "fwd": (128, 512), "dq": (128, 512), "dkv": (1024, 1024)}
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k, chosen)
    assert hlo.count("tpu_custom_call") == 3
    for name in ("attention_fwd", "attention_bwd_dq", "attention_bwd_dkv"):
        assert re.search(rf"%{name}_blk(\.\d+)? = ", hlo), name
    assert "s8[1,2,16384,256]" in hlo
    assert not re.search(r"\[1,(2|32),16384,16384\]", hlo)


# The choice at the same cell: one call of the scoring kernel holds a
# group's 1023 pooled keys whole and stores (2, 16384, 256) float32; the
# threshold is XLA's passes over that. No (32, 16384, 1023) array.
def test_block_score_kernel_compiles_at_the_cell_shape(
        one_chip, kernels_are_the_path):
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16,
                             sharding=one_chip)

    def choose(q, k):
        scores = blocksel.block_scores(
            q, blocksel.pooled_keys(k, 32, 16), block=64, kernel=32,
            stride=16, scale=128 ** -0.5)
        return blocksel.pick_blocks(scores, block=64, topk=64,
                                    init_blocks=1, window=2048)

    compiled = jax.jit(choose).lower(q, k).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert re.search(r"%blk_score(\.\d+)? = ", hlo)
    assert "s8[1,2,16384,256]" in hlo
    assert not re.search(r"16384,10(23|24)\]|10(23|24),16384\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20
    assert trace.gauges()["attn.blk_score_kernel"] == 1


# The lightning rule at the same cell: 32 heads of 128, 64 chunks of 256.
# One call forward; under differentiation the forward with a float32
# state a chunk (128 MiB) and the hand-written backward.
def test_lightning_kernels_compile_at_the_cells_shape(
        one_chip, kernels_are_the_path):
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    slopes = jax.ShapeDtypeStruct((32,), jnp.float32, sharding=one_chip)

    def loss(q, k, v, slopes):
        with jax.named_scope("la_chunk"):
            return lightning.lightning_attention(
                q, k, v, slopes, chunk=256).astype(jnp.float32).sum()

    names = _op_names(_compile(loss, x, x, x, slopes))
    assert len(names) == 1 and _in_scope(names[0], "la_chunk")
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, slopes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30
    hlo = compiled.as_text()
    names = _op_names(hlo)
    assert len(names) == 2 and all(_in_scope(n, "la_chunk") for n in names)
    assert _kernel_calls(hlo, "lightning_fwd") == 1
    assert _kernel_calls(hlo, "lightning_bwd") == 1
    assert "f32[1,32,64,128,128]" in hlo        # a state a chunk
    assert trace.gauges()["la.kernel"] == 1


# xing4-ep8-1chip-steady (PR 31): latent attention's kernels take q/k
# heads of 192 against v heads of 128 and the scale yarn states (the
# cell's whole expert block: test_chip_compile_steps.py).
def test_flash_two_widths_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path):
    tiles = attention.flash_tiles(4096, 4096, 192, 1, jnp.bfloat16, 128)
    assert all(min(t) > 128 for t in tiles.values()), tiles
    qk = jax.ShapeDtypeStruct((2, 4096, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, scale=0.1447)
        assert out.shape == (2, 4096, 32, 128)
        return out.astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dk/dv


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


# Over more than one device the kernels run per shard under shard_map:
# left to the partitioner they are refused ("Mosaic kernels cannot be
# automatically partitioned").


@pytest.fixture(scope="module")
def mesh4(topo):
    return build_mesh(MeshConfig(dp=-1, fsdp=4), devices=list(topo.devices))


def test_flash_compiles_over_four_chips(mesh4, kernels_are_the_path):
    sh = NamedSharding(mesh4, P(BATCH_AXES, None, None, None))
    q = jax.ShapeDtypeStruct((4, 2048, 32, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 2048, 8, 128), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, mesh=mesh4)
        return out.astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert hlo.count("tpu_custom_call") == 3


def test_flash_four_chip_cell_compiles_at_chosen_tiles(
        mesh4, kernels_are_the_path):
    # mistral7b-d20-fsdp4-steady: 4 sequences a step, one a device
    sh = NamedSharding(mesh4, P(BATCH_AXES, None, None, None))
    args = _cell_qkv(4, sh)
    loss = _chosen_loss(mesh4)
    assert _compile(loss, *args).count("tpu_custom_call") == 1
    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert hlo.count("tpu_custom_call") == 3


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
