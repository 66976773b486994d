"""What the three files of compile checks for a described v5e share
(``test_chip_compile.py``: the kernels; ``test_chip_compile_steps.py``: a
cell's whole step and the families' blocks; ``test_chip_compile_experts.py``:
the expert layer): the fixtures that describe the chip, and the readers of
a compiled program's text.

The topology is described inside a fixture, never at import, and the
compiles run in the test's own process: only one process may load the
TPU's library unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1 command
sets it), and under xdist only a worker given one of the three files does.
A file takes the fixtures by importing them.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.ops import (
    attention, blocksel, dsa, fused_ce, grouped_matmul, hc_mix, kda,
    lightning, moe_rows, ssd)


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
    monkeypatch.setattr(lightning, "_on_tpu", lambda: True)
    monkeypatch.setattr(blocksel, "_on_tpu", lambda: True)
    monkeypatch.setattr(hc_mix, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")


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
