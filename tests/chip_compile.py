"""What the files of compile checks for a described v5e share
(``test_chip_compile.py`` and ``test_chip_compile_passes.py``: the kernels;
``test_chip_compile_steps.py``: a cell's whole step;
``test_chip_compile_blocks*.py``: the families' blocks;
``test_chip_compile_experts.py`` and ``test_chip_compile_expert_rows*.py``:
the expert layer): the fixtures that describe the chip, the readers of a
compiled program's text, and the expert cells' layer with what it is held
to.

The topology is described inside a fixture, never at import, and the
compiles run in the test's own process: only one process may load the
TPU's library unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1 command
sets it), and under xdist only a worker given one of these files does.
A file takes the fixtures by importing them.
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
    attention, blocksel, dsa, fused_ce, grouped_matmul, hc_mix, kda,
    lightning, moe_rows, ssd)
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


# Over more than one device the kernels run per shard under shard_map:
# left to the partitioner they are refused ("Mosaic kernels cannot be
# automatically partitioned").
@pytest.fixture(scope="module")
def mesh4(topo):
    return build_mesh(MeshConfig(dp=-1, fsdp=4), devices=list(topo.devices))


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
    monkeypatch.setattr(lightning, "_on_tpu", lambda: True)
    monkeypatch.setattr(blocksel, "_on_tpu", lambda: True)
    monkeypatch.setattr(hc_mix, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")


def _two_in_line(fn):
    """The gradient's function of two blocks in line, built as the family
    builds them: the first one's output is wanted, so its forward runs;
    the second's is not (the loss's value is not asked for), so its first
    forward runs only for what its checkpoint keeps."""
    return jax.grad(
        lambda lp, x: fn(lp, fn(lp, x)).astype(jnp.float32).sum(),
        argnums=(0, 1))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _kernel_calls(hlo, name):
    return sum("custom-call(" in line
               and line.split(" = ")[0].strip().lstrip("%").startswith(name)
               for line in hlo.splitlines())

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


def expert_rows_compile_in_the_parents_memory(one_chip, cell):
    """Under ``kernels_are_the_path``: the kernels a cell's expert layer
    calls, the scopes they sit under and the temporaries it needs."""
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
