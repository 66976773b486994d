"""The main path's kernels, compiled at real widths for a described v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2): what it
refuses here it refuses on the chip — a block that overflows scoped
VMEM, a misaligned slice, a Mosaic kernel left to the automatic
partitioner — and interpret mode shows none of that. Nothing runs, so
these say nothing about results or times.

The topology is described inside a fixture, never at import, and the
compiles run in the test's own process: only one process may load the
TPU's library, and under xdist only the worker given this file does.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    attention, dsa, fused_ce, grouped_matmul, kda, moe_rows)
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel.mesh import BATCH_AXES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernels_are_the_path(monkeypatch):
    """The public wrappers ask ``jax.default_backend()``, which is the
    CPU here, and would take their reference branch."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_ce, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe_rows, "_on_tpu", lambda: True)
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


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


# That cell's whole step, built as benchmarks/jobs/finetune_loop.py
# builds it (the family, its TrainConfig, ElasticTrainer.lower_step) on
# one described chip: `step.hbm_peak_bytes` here is the chip's
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


# The expert layer of that cell: 8192 tokens x 8 choices = 65536 rows
# through 64 experts of 2048 x 1024, bf16. What the test holds is that
# the v5e's compiler takes the grouped-matmul kernels at the tiles they
# choose (ops/grouped_matmul.py), forward, d-lhs and d-rhs, and that no
# tensor of (tokens, experts, capacity) is in the program.
def _olmoe_expert_layer(sharding):
    import dataclasses

    from dlrover_tpu.models import moe

    cfg = dataclasses.replace(
        moe.MoeConfig.olmoe_1b_7b(), n_layers=1, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    layers = moe.abstract_params(cfg)["layers"]
    lp = {
        k: jax.ShapeDtypeStruct(layers[k].shape[1:], layers[k].dtype,
                                sharding=sharding)
        for k in ("router", "w_gate", "w_up", "w_down")
    }
    y = jax.ShapeDtypeStruct((2, 4096, cfg.dim), jnp.bfloat16,
                             sharding=sharding)

    def loss(lp, y):
        out, aux = moe.moe_mlp(cfg, lp, y)
        return out.astype(jnp.float32).sum() + aux

    return loss, lp, y


def _kernel_calls(hlo, name):
    return sum("custom-call(" in line
               and line.split(" = ")[0].strip().lstrip("%").startswith(name)
               for line in hlo.splitlines())


def test_olmoe_expert_layer_compiles(one_chip, kernels_are_the_path):
    loss, lp, y = _olmoe_expert_layer(one_chip)
    hlo = _compile(loss, lp, y)
    assert _kernel_calls(hlo, "grouped_matmul") == 3  # gate, up, down
    assert "ragged-dot" not in hlo
    assert "[8192,64," not in hlo  # no (tokens, experts, ...) dispatch tensor


def test_olmoe_expert_layer_fwd_bwd_compiles(one_chip, kernels_are_the_path):
    loss, lp, y = _olmoe_expert_layer(one_chip)
    hlo = _compile(jax.grad(loss, argnums=(0, 1)), lp, y)
    # forward, d-lhs and d-rhs of each of the three products (up's
    # d-lhs adds onto gate's in place: no add of the two outside)
    assert _kernel_calls(hlo, "grouped_matmul_dlhs") == 3
    assert _kernel_calls(hlo, "grouped_matmul_drhs") == 3
    assert _kernel_calls(hlo, "grouped_matmul") == 9
    assert "[8192,64," not in hlo


# xing4-ep8-1chip-steady (PR 31): latent attention's kernels take q/k
# heads of 192 against v heads of 128 and the scale yarn states; and one
# whole expert block of the step at the published widths (four streams
# of 2 x 4096 x 3584, ranks 768 / 512, 8 held experts of 64, the shared
# expert), forward and backward, remat as the cell runs it.
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


def _op_names(hlo, target="tpu_custom_call"):
    """The ``op_name`` of every custom call to ``target``."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in hlo.splitlines()
            if f'custom_call_target="{target}"' in line]


def _wide_f32(hlo, op, at_least=8192 * 4096):
    """The lines of ``hlo`` where ``op`` makes a float32 array of
    ``at_least`` elements."""
    found = []
    for line in hlo.splitlines():
        shape = re.search(r"= f32\[([0-9,]+)\]\S* " + op + r"\(", line)
        if shape and np.prod(
                [int(d) for d in shape.group(1).split(",")]) >= at_least:
            found.append(line)
    return found


def _in_scope(op_name, scope):
    # as benchmarks/harness/hlo_scopes.py reads it: a whole component,
    # bare or wrapped by a transform
    return scope in re.split(r"[/()]", op_name)


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


# The expert layer of the four expert cells, forward and backward under
# remat as the cells run it: (tokens, choices, experts, held, width,
# expert width, activation), and the temporaries the parent's program
# needed for the same block (XLA's gathers over all t x k rows). Where
# pairs can sort into a tail the row movements run ops/moe_rows.py's
# kernels, bound by the live count: combine's forward and dispatch's
# backward (`moe_rows_summed`) and combine's backward
# (`moe_rows_cotangents`), and since PR 42 `act(gate) x up` and its
# backward (`moe_rows_gated`, `moe_rows_gated_bwd`) while the grouped
# products walk no tile of the tail; OLMoE, which holds every expert,
# keeps XLA's gathers and fusion and the walk it had.
EXPERT_CELLS = {
    "smallthinker": ((16384, 6, 64, 16, 2560, 768, "relu"), 1971133440),
    "xing4": ((8192, 4, 64, 8, 3584, 1024, "silu"), 910812160),
    "kimi": ((8192, 8, 256, 32, 2304, 1024, "silu"), 1054416896),
    "dots3": ((8192, 8, 256, 8, 5120, 1536, "silu"), 2630225408),
    "olmoe": ((8192, 8, 64, None, 2048, 1024, "silu"), 675513856),
}


def _expert_layer(cell, sharding, mesh=None, batch=1):
    from dlrover_tpu.models import moe

    (t, k, e, held, d, f, act), _ = EXPERT_CELLS[cell]
    cfg = moe.MoeConfig(
        dim=d, ffn_dim=f, n_experts=e, experts_per_token=k,
        experts_held=held, expert_act=act, n_layers=1, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    layers = moe.abstract_params(cfg)["layers"]
    specs = moe.param_specs(cfg)["layers"]
    lp = {
        name: jax.ShapeDtypeStruct(
            layers[name].shape[1:], layers[name].dtype,
            sharding=sharding if mesh is None else NamedSharding(
                mesh, P(*specs[name][1:])))
        for name in ("router", "w_gate", "w_up", "w_down")
    }
    y = jax.ShapeDtypeStruct(
        (batch, t // batch, d), jnp.bfloat16,
        sharding=sharding if mesh is None else NamedSharding(
            mesh, P(BATCH_AXES, None, None)))

    def loss(lp, y):
        fn = jax.checkpoint(
            lambda lp, y: moe.moe_mlp(cfg, lp, y, mesh)[0],
            policy=jax.checkpoint_policies.nothing_saveable)
        return fn(lp, y).astype(jnp.float32).sum()

    return jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1))).lower(lp, y).compile()


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_expert_rows_fwd_bwd_compile_in_the_parents_memory(
        one_chip, kernels_are_the_path, cell):
    compiled = _expert_layer(cell, one_chip)
    hlo = compiled.as_text()
    tail = EXPERT_CELLS[cell][0][3] is not None
    # combine's forward (the backward reads no output of it, so the
    # remat forward has none) and dispatch's backward, and combine's
    # backward; the three products forward, again under remat, d-lhs
    # and d-rhs; the pass between the products forward, again under
    # remat, and backward
    assert _kernel_calls(hlo, "moe_rows_summed") == (2 if tail else 0)
    assert _kernel_calls(hlo, "moe_rows_cotangents") == (1 if tail else 0)
    assert _kernel_calls(hlo, "moe_rows_gated_bwd") == (1 if tail else 0)
    assert _kernel_calls(hlo, "moe_rows_gated") == (3 if tail else 0)
    assert _kernel_calls(hlo, "grouped_matmul_dlhs") == 3
    assert _kernel_calls(hlo, "grouped_matmul") == 12
    assert trace.gauges()["moe.rows_kernel"] == int(tail)
    assert trace.gauges()["moe.tail_skipped"] == int(tail)
    assert trace.gauges()["moe.row_block"] == (256 if tail else 0)
    # every kernel under the scope the device metrics select by
    for name in _op_names(hlo):
        if "moe_rows_gated" in name or "grouped_matmul" in name:
            assert _in_scope(name, "moe_experts"), name
        elif "moe_rows_" in name:
            assert _in_scope(name, "moe_combine") or _in_scope(
                name, "moe_dispatch"), name
    # no (t x k, d) array beside the parent's: the kernels' lists of
    # int32 and float32 scalars (the live pairs, the sorted weights and
    # their cotangent) are 0.4 MB each at 98304 pairs. The pass's
    # backward writes over two of its operands, as XLA's fusion did, and
    # up's d-lhs over gate's
    parent = EXPERT_CELLS[cell][1]
    assert compiled.memory_analysis().temp_size_in_bytes < parent + 2 * 2**20


def test_expert_layer_over_four_chips_keeps_xlas_gathers(topo, monkeypatch):
    """One program across the 2 x 2 mesh, ep 2: inside ``moe_mlp``'s
    ``shard_map`` each rank holds half of the held experts and the other
    half's pairs are its tail. That ``shard_map`` checks how values vary
    over the mesh (tp's psum hangs on it), and the check writes a
    ``pvary`` into a kernel's body, which Mosaic does not lower: no
    Pallas kernel compiles inside it, the grouped products' neither. So
    under a mesh the rows move by XLA's gathers and the products by
    ``lax.ragged_dot``, as on the CPU meshes, and the program compiles."""
    monkeypatch.setattr(moe_rows, "_on_tpu", lambda: True)
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    mesh = build_mesh(MeshConfig(dp=-1, ep=2), devices=list(topo.devices))
    hlo = _expert_layer("xing4", None, mesh, batch=4).as_text()
    assert "moe_rows_" not in hlo
    assert trace.gauges()["moe.rows_kernel"] == 0


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
