"""The expert layer compiled at real widths for a described v5e: OLMoE's,
which holds every expert, at the tiles the kernels choose and in its
parent's memory, and one program across four chips (the cells that hold
a share of their experts: ``test_chip_compile_expert_rows.py`` and
``test_chip_compile_expert_rows_bounded.py``; see ``test_chip_compile.py``,
which holds the kernels' own checks, and ``tests/chip_compile.py`` for
what the files share, the cells' table among it)."""

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import dsa, moe_rows
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    _compile, _expert_layer, _kernel_calls,
    expert_rows_compile_in_the_parents_memory, kernels_are_the_path, one_chip,
    topo)


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


@pytest.mark.parametrize("cell", ["olmoe"])
def test_expert_rows_fwd_bwd_compile_in_the_parents_memory(
        one_chip, kernels_are_the_path, cell):
    expert_rows_compile_in_the_parents_memory(one_chip, cell)


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
