"""Attention's kernels and the selection's, compiled at real widths for a
described v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): what it
refuses here it refuses on the chip — a block that overflows scoped
VMEM, a misaligned slice, a Mosaic kernel left to the automatic
partitioner — and interpret mode shows none of that. Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture, never at import
(``tests/chip_compile.py``, with the readers of a compiled program's text
that this file shares with ``test_chip_compile_passes.py``, the main
path's other kernels, ``test_chip_compile_steps.py`` and
``test_chip_compile_blocks*.py``, a cell's whole step and the families'
blocks, and ``test_chip_compile_experts.py`` and
``test_chip_compile_expert_rows*.py``, the expert layer).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention, blocksel, dsa, lightning
from dlrover_tpu.parallel.mesh import BATCH_AXES
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _compile, _in_scope, _kernel_calls, _op_names, kernels_are_the_path, mesh4,
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


def _grouped_call_compiles(one_chip, heads, kv_heads, window):
    """A call of 128-wide heads at 16384 positions under `jax.grad`, at
    the tiles the kernels choose: three kernels, named by the window."""
    q = jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, kv_heads, 128), jnp.bfloat16,
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


def _window_tiles():
    g = trace.gauges()
    return tuple((g[f"attn.window_{kernel}block_q"],
                  g[f"attn.window_{kernel}block_k"])
                 for kernel in ("", "dkv_"))


# smallthinker-ep4-1chip-steady (PR 37): 28 query heads on 4 kv heads of
# 128 (group 7) at 16384 positions, the full layers' causal kernels and
# the window layers' (window 4096: the band's walk, kernels named _swa),
# at the tiles the kernels choose: a band of eight tiles moves none
@pytest.mark.parametrize("window", [None, 4096])
def test_flash_smallthinker_cell_compiles_at_chosen_tiles(
        one_chip, kernels_are_the_path, window):
    _grouped_call_compiles(one_chip, 28, 4, window)
    if window:
        assert _window_tiles() == ((256, 512), (1024, 1024))


# laguna-xs2-ep8-1chip-steady's window layers (PR 60): 64 query heads on
# 8 (group 8), a window of 512, whose tiles the window narrows (PR 61)
def test_flash_laguna_window_layers_compile_at_chosen_tiles(
        one_chip, kernels_are_the_path):
    _grouped_call_compiles(one_chip, 64, 8, 512)
    assert _window_tiles() == ((256, 256), (512, 512))
    assert trace.gauges()["attn.window_band_pct"] == 52.9


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
    if kind == "window":  # a band of 513 in tiles of 512 (PR 61)
        assert _window_tiles() == ((512, 512), (512, 512))
        assert trace.gauges()["attn.window_band_pct"] == 50.1
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
    for name in ("dsa_index_fwd", "dsa_index_bwd"):
        assert sum("custom-call(" in line and name in line.split(" = ")[0]
                   for line in hlo.splitlines()) == 1, name
    assert not re.search(r"8192,8192,64\]|8192,64,8192\]|64,8192,8192\]", hlo)


def _least_vmem_limit(monkeypatch, fn, *shapes):
    """``(the compiled text, the scoped-VMEM limit in bytes it first
    compiled under)``: the limit follows the compiler's refusals up
    from 8 MiB."""
    limit = 8 * 2**20
    for _ in range(6):
        monkeypatch.setattr(dsa, "_VMEM_LIMIT", limit)
        try:
            # a function of its own a limit: a jit's trace is cached by
            # its function, and the limit is read while it is traced
            return _compile(lambda *a: fn(*a), *shapes), limit
        except Exception as refused:
            reached = re.search(
                r"Scoped allocation with size ([\d.]+)M", str(refused))
            assert reached, refused
            limit = math.ceil(float(reached.group(1))) * 2**20
    pytest.fail("the compiler refused six limits in a row")


# The fused backward (PR 57) holds the key's whole gradient, (s, d)
# float32, in VMEM beside its blocks and the accumulators a head. What it
# takes is read from the compiler: under a limit too small the refusal
# names the size it had reached ("Scoped allocation with size 38.50M"),
# so the limit follows the refusals up until the compile passes: 43 MiB
# at dots3's shape, 18 at keye-vl's, of `_VMEM_LIMIT`'s 64.
@pytest.mark.parametrize("s,h,d", [(8192, 64, 128), (16384, 16, 64)])
def test_dsa_index_bwd_stands_under_the_vmem_limit_at_the_cells_shapes(
        one_chip, monkeypatch, s, h, d):
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1, s, h), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, s, s), jnp.float32, sharding=one_chip)
    hlo, limit = _least_vmem_limit(
        monkeypatch, lambda *a: dsa._index_bwd_pallas(*a, False), q, k, w, g)
    assert hlo.count("tpu_custom_call") == 1
    print(f"dsa_index_bwd at {(s, h, d)} compiles in {limit >> 20} MiB")
    assert 8 * 2**20 < limit < attention._VMEM_LIMIT


def test_dsa_probs_kernel_compiles_at_the_cell_shape(
        one_chip, kernels_are_the_path, monkeypatch):
    q = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8,
                                sharding=one_chip)
    hlo, limit = _least_vmem_limit(
        monkeypatch, lambda q, k, lse, mask: dsa.head_summed_probs(
            q, k, lse, mask, 192 ** -0.5), q, q, lse, mask)
    assert hlo.count("tpu_custom_call") == 1 and "dsa_probs" in hlo
    assert not re.search(r"32,8192,8192\]|8192,8192,32\]", hlo)
    # every head's q block and every key head's k block are resident
    # (PR 62): what that takes, read as `dsa_index_bwd`'s is
    print(f"dsa_probs at {q.shape} on {q.shape} compiles in "
          f"{limit >> 20} MiB")
    assert 8 * 2**20 < limit < attention._VMEM_LIMIT


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
    for name in ("dsa_index_fwd", "dsa_index_bwd"):
        assert sum("custom-call(" in line and name in line.split(" = ")[0]
                   for line in hlo.splitlines()) == 1, name
    assert not re.search(
        r"16384,16384,16\]|16384,16,16384\]|16,16384,16384\]", hlo)


def test_dsa_probs_kernel_reads_grouped_keys_where_they_lie(
        one_chip, kernels_are_the_path, monkeypatch):
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 32, 16384), jnp.float32,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8,
                                sharding=one_chip)
    hlo, limit = _least_vmem_limit(
        monkeypatch, lambda q, k, lse, mask: dsa.head_summed_probs(
            q, k, lse, mask, 128 ** -0.5), q, k, lse, mask)
    assert hlo.count("tpu_custom_call") == 1 and "dsa_probs" in hlo
    # the key goes in at its 4 heads: nothing of it at 32
    assert not re.search(r"bf16\[1,(32,16384|16384,32),128\][^\n]*broadcast",
                         hlo)
    assert not re.search(r"32,16384,16384\]|16384,16384,32\]", hlo)
    print(f"dsa_probs at {q.shape} on {k.shape} compiles in "
          f"{limit >> 20} MiB")
    assert 8 * 2**20 < limit < attention._VMEM_LIMIT


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
