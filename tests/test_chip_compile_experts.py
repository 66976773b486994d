"""The expert layer of every expert cell, compiled at real widths for a
described v5e (see ``test_chip_compile.py``, which holds the kernels' own
checks, and ``tests/chip_compile.py`` for what the files share)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import dsa, moe_rows
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel.mesh import BATCH_AXES
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _compile, _in_scope, _kernel_calls, _op_names, kernels_are_the_path,
    one_chip, topo)


# The expert layer of olmoe-1chip-steady: 8192 tokens x 8 choices =
# 65536 rows through 64 experts of 2048 x 1024, bf16. What the test
# holds is that the v5e's compiler takes the grouped-matmul kernels at
# the tiles they choose (ops/grouped_matmul.py), forward, d-lhs and
# d-rhs, and that no tensor of (tokens, experts, capacity) is in the
# program.
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
# Since PR 53, where the dead rows pay for it (`moe_rows.gather_pays`:
# the three cells of `BOUNDED`), dispatch's forward is a kernel too
# (`moe_rows_gathered`) and the gathered rows are no residual of gate's
# and up's products, whose backward gathers the live rows again: such a
# layer holds one `(t k, d)` array less than its parent's (PR 52's bytes
# were 2630160896, 2558014464 and, for granite's layer, 4786955776).
EXPERT_CELLS = {
    "smallthinker": ((16384, 6, 64, 16, 2560, 768, "relu"), 1971133440),
    "xing4": ((8192, 4, 64, 8, 3584, 1024, "silu"), 910812160),
    "kimi": ((8192, 8, 256, 32, 2304, 1024, "silu"), 1054416896),
    "dots3": ((8192, 8, 256, 8, 5120, 1536, "silu"), 1948318208),
    "olmoe": ((8192, 8, 64, None, 2048, 1024, "silu"), 675513856),
    # PR 45, many small experts: 320 rows an expert of width 512, a
    # router 512 wide, 163840 pairs through the sort
    "qwen3next": ((16384, 10, 512, 32, 2048, 512, "silu"), 1850138624),
    # PR 52's cell, listed by PR 53: 163840 pairs of width 4096, an
    # eighth of them live, tokens XLA's gather cannot stage (128 MiB)
    "granite": ((16384, 10, 72, 9, 4096, 768, "silu"), 3441587200),
}
BOUNDED = {"dots3", "qwen3next", "granite"}


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
    # dispatch's forward: the kernel in the forward, under remat and once
    # more for gate's and up's d-rhs, and no gather of every row; or
    # XLA's whole gather, forward and under remat
    (t, k, _, _, d, _, _), _ = EXPERT_CELLS[cell]
    whole = sum(f"bf16[{t * k},{d}]" in line.split(" gather(")[0]
                for line in hlo.splitlines() if " gather(" in line)
    bounded = cell in BOUNDED
    assert trace.gauges()["moe.dispatch_bounded"] == int(bounded)
    assert _kernel_calls(hlo, "moe_rows_gathered") == (3 if bounded else 0)
    if tail:
        assert whole == (0 if bounded else 2)
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
