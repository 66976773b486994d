"""``trace.scope`` and the reading of a compiled step by phase and scope.

The benchmark's readers (``benchmarks/harness/step_phases.py``) tell the
forward, the recomputed forward, the backward and the update apart by
what JAX writes into every instruction's ``op_name``
(``rematted_computation``, ``transpose(jvp(``) and find a scope as a
whole component of that path. The builds below go through
``ElasticTrainer`` on the CPU, once with and once without remat for each
of the five families' tiny configurations: they are what fails when a
JAX upgrade renames one of those, or a refactor drops a scope.
"""

import functools
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import hlo_scopes, step_phases
from dlrover_tpu.observability import trace
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = ("tiny-cpu", "tiny-cpu-olmoe", "tiny-cpu-xing4",
           "tiny-cpu-kimi-linear", "tiny-cpu-smallthinker")
# the scope that holds a family's dense feed-forward and the one that
# holds its attention's projections (None: the family has none)
DENSE = {"tiny-cpu": "dense_mlp", "tiny-cpu-olmoe": None,
         "tiny-cpu-xing4": "dense_mlp", "tiny-cpu-kimi-linear": "dense_mlp",
         "tiny-cpu-smallthinker": None}
PROJ = {"tiny-cpu": "attn_proj", "tiny-cpu-olmoe": "attn_proj",
        "tiny-cpu-xing4": "mla_proj", "tiny-cpu-kimi-linear": "kda_proj",
        "tiny-cpu-smallthinker": "attn_proj"}


@functools.lru_cache(maxsize=None)
def step_table(name: str, remat: bool):
    """``{instruction: op_name}`` of the step ``ElasticTrainer`` builds
    for the tiny configuration ``name``, read from ``step.hlo``."""
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        config = json.load(f)
    config["assumed"]["remat"] = "all" if remat else "off"
    mc = MeshConfig().resolve(1)
    mesh = build_mesh(mc, devices=jax.devices()[:1])
    family = importlib.import_module("benchmarks.families." + config["family"])
    fam = family.build(config, mesh)
    trainer = ElasticTrainer(
        fam.loss_fn, fam.param_specs, mesh, mc,
        TrainConfig(global_batch_size=2, micro_batch_size=2))
    state = trainer.init_state(fam.init_params(jax.random.key(0)))
    accum, per = trainer.step_batch_shape
    tokens = jax.device_put(
        jnp.zeros((accum, per, 64), jnp.int32), trainer.batch_sharding)
    trainer.step(state, tokens)
    return hlo_scopes.op_names(trace.text("step.hlo"))


def _parts(op_name):
    return set(re.split(r"[/()]", op_name))


# -- (a) trace.scope ----------------------------------------------------------

def test_scope_registers_its_name_and_the_op_name_carries_it():
    ring = trace.TraceRing()

    def f(x):
        with ring.scope("zeta"):
            x = jnp.sin(x) * 2.0
        with ring.scope("alpha"):
            return jnp.cos(x)

    text = jax.jit(f).lower(jnp.ones((8,))).compile().as_text()
    assert ring.scopes() == ["alpha", "zeta"]
    named = [n for n in hlo_scopes.op_names(text).values() if n]
    assert any("zeta" in _parts(n) and n.endswith("sin") for n in named)
    assert any("alpha" in _parts(n) and n.endswith("cos") for n in named)
    ring.clear()        # JAX keeps what it traced: so do the names
    assert ring.scopes() == ["alpha", "zeta"]
    assert "zeta" not in trace.scopes()     # the process's ring is another


def test_scope_without_jax_is_a_null_context():
    code = (
        "import sys\n"
        "from dlrover_tpu.observability import trace\n"
        "assert 'jax' not in sys.modules\n"
        "with trace.scope('no_jax') as got:\n"
        "    assert got is None\n"
        "assert trace.scopes() == ['no_jax']\n"
        "assert 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=ROOT)


# -- (b) the phases and the scopes of a built step ----------------------------

@pytest.mark.parametrize("remat", (True, False), ids=("remat", "no-remat"))
@pytest.mark.parametrize("name", CONFIGS)
def test_phases_and_scopes_of_the_built_step(name, remat):
    # (a reducer's own instructions carry a path without the step's
    # prefix; they are no operation of a device trace)
    named = [n for n in step_table(name, remat).values()
             if n.startswith("jit(")]
    by_phase = {p: [] for p in step_phases.PHASES}
    for op_name in named:
        by_phase[step_phases.phase_of(op_name)].append(op_name)

    # the update: its scopes' instructions, and nothing else
    update = [n for n in named
              if _parts(n) & set(step_phases.OPTIMIZER_SCOPES)]
    assert sorted(update) == sorted(by_phase["optimizer"])
    assert any("optimizer_update" in _parts(n) for n in update)
    # (at one microbatch a step the scale is 1 and XLA drops
    # grad_finish's only product: the scope is opened, and empty)
    assert "grad_finish" in trace.scopes()
    assert not any("transpose(" in n or "rematted_computation" in n
                   for n in update)

    # forward and backward always, the recomputed forward under remat
    # (off the TPU ops/kda.py's chunked form recomputes its own
    # segments, whatever the model's remat says: kda_chunk's)
    assert by_phase["fwd"] and by_phase["bwd"]
    assert any("kda_chunk" not in _parts(n)
               for n in by_phase["remat_fwd"]) == remat
    assert all("transpose(jvp(" in n for n in by_phase["bwd"])
    assert all("transpose(jvp(" in n
               and "checkpoint/rematted_computation" in n
               for n in by_phase["remat_fwd"])
    assert not any("transpose(" in n for n in by_phase["fwd"])
    # lax.transpose ends a forward path: not the backward's mark
    assert step_phases.phase_of(
        "jit(step)/jvp()/while/body/closed_call/attn_proj/transpose") == "fwd"

    # the dots where they belong, in every phase the step has
    phases = ("fwd", "bwd") + (("remat_fwd",) if remat else ())
    for scope in filter(None, (DENSE[name], PROJ[name])):
        for phase in phases:
            assert any(
                scope in _parts(n) and n.endswith("dot_general")
                for n in by_phase[phase]), (scope, phase)
    for phase in phases:
        assert any("norm" in _parts(n) for n in by_phase[phase]), phase
    # and no product of a layer under no scope at all
    scopes = set(trace.scopes())
    bare = [n for n in named if n.endswith("dot_general")
            and "while" in _parts(n) and not _parts(n) & scopes]
    assert not bare, bare

    # the scans' own: a layer's parameters sliced out, results stacked
    own = {n.rsplit("/", 1)[1] for n in named
           if step_phases.scan_own(n, frozenset(scopes))}
    assert {"dynamic_slice", "dynamic_update_slice"} <= own


# -- (c), (d) the scopes' one door and their table ----------------------------

def test_no_named_scope_left_beside_trace_scope():
    left = []
    for sub in ("models", "ops", "train"):
        folder = os.path.join(ROOT, "dlrover_tpu", sub)
        for fname in sorted(os.listdir(folder)):
            if fname.endswith(".py"):
                with open(os.path.join(folder, fname)) as f:
                    left += [f"{sub}/{fname}:{i}" for i, line in
                             enumerate(f, 1) if "named_scope(" in line]
    assert not left, left


def test_every_scope_has_its_row_in_the_design_note():
    for name in CONFIGS:
        step_table(name, True)
    rows = set()    # the names in the first cell of a table's row
    with open(os.path.join(ROOT, "docs", "design", "observability.md")) as f:
        for line in f:
            if line.startswith("| `"):
                rows.update(re.findall(r"`(\w+)`", line.split("|")[1]))
    scopes = trace.scopes()
    assert {"dense_mlp", "attn_proj", "norm", "grad_finish",
            "optimizer_update", "kda_chunk", "mla_proj"} <= set(scopes)
    assert not [s for s in scopes if s not in rows]
