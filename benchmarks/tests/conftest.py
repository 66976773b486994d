"""Helpers of the benchmark's own tests. Run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repository's tier-1 tests (``tests/``).
"""

import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# as run.py does: a fixed compile cache, so that CheckpointEngine does not
# put one under a per-test checkpoint directory
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def cell_metrics(cell: str):
    """The per-layer metrics ``BENCHMARK.json`` gives ``cell``, in its
    order: those without a ``workloads`` list and those that list it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    return [m["name"] for m in per_layer
            if cell in m.get("workloads", (cell,))]


def made_up_v5e_ctx(cell_name: str, calls):
    """What a reader gets after a traced run of ``cell_name`` on one v5e
    whose device ran ``calls`` (``[(instruction name, seconds)]``) back
    to back in one step; the reader's log lines collect in ``.logged``."""
    cell = load_json("workloads", cell_name + ".json")
    ops, t = [], 0.0
    for name, seconds in calls:
        ops.append((t, t + seconds * 1e9, name, ""))
        t += seconds * 1e9
    logged = []
    return types.SimpleNamespace(
        cell=cell, config=load_json("configs", cell["config"] + ".json"),
        log=logged.append, logged=logged, step_op_names={}, counters={},
        devices=[types.SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite")],
        trace=types.SimpleNamespace(devices={"d0": ops},
                                    spans=[(0, t, "step")],
                                    window_ns=(0.0, t)))


def one_device_mesh():
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    import jax

    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def make_ctx(cell_name: str, seconds: float = 1.0, seed: int = 7,
             trace_dir: str = ""):
    """What ``run.py`` hands a job, for a cell on the CPU."""
    import jax

    from benchmarks.families import llama as family

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir=trace_dir, log=lambda msg: None, family=family,
    )
