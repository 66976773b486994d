"""``BENCHMARK.json``'s lists against the files under ``benchmarks/``:
the per-layer list keeps its room (at most 128 entries, no reader listed
twice under two names), every name points at a file and every file is
listed or stated as left out. Pure JSON reads: no JAX, under a second.

ISSUE 58 asked for this file under ``tests/`` (tier-1); a ``benchmark``
PR adds no file outside the benchmark's own directories, so it stands
here until a later PR may move it (``PERF.md`` section 7).
"""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT, load_json

LIMIT = 128
#: metric files that no entry lists, and why
UNLISTED = {
    # the saving cell's eleven: mistral7b-d5-save waits for a rate and a
    # bound of its own (PERF.md 7 A)
    "save_stall_s", "ckpt_drain_s", "saving_step_ms", "saving_device_idle",
    "save_join_s", "save_snapshot_s", "d2h_issue_s", "d2h_wait_s",
    "stage_background_s", "stage_shm_write_s", "save_d2h_idle_s",
    # the chip's sandboxed kernel has no schedstat (PERF.md section 3)
    "step_runq_ms", "late_runq_ms",
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
PER_LAYER = BENCHMARK["per_layer"]
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
FILES = sorted(f[:-len(".json")]
               for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".json"))


def _what_it_is(name: str):
    """A metric without its words: its file's keys less ``what``, and its
    reader's code less the docstring. Two entries that agree in this are
    one reader listed twice."""
    spec = load_json("layer_metrics", name + ".json")
    spec.pop("what", None)
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    code = ""
    if os.path.exists(path):
        with open(path) as f:
            code = re.sub(r'^""".*?"""\n', "", f.read(), flags=re.S)
    return json.dumps(spec, sort_keys=True), code


def test_the_per_layer_list_keeps_its_room():
    assert len(PER_LAYER) <= LIMIT
    names = [m["name"] for m in PER_LAYER]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("metric", PER_LAYER, ids=lambda m: m["name"])
def test_a_listed_metric_has_its_file_and_its_cells(metric):
    spec = load_json("layer_metrics", metric["name"] + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    cells = metric.get("workloads")
    if cells is not None:
        assert cells and len(set(cells)) == len(cells)
        assert set(cells) <= set(CELLS)
        # in the cells' own order, so that two lists compare by eye
        assert cells == [c for c in CELLS if c in cells]
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


def test_no_reader_is_listed_twice():
    """A new cell never lists an existing reader again under a name of
    its own: it adds itself to the reader's ``workloads`` (a ``benchmark``
    PR), or the reader carries no list and the cell has it already."""
    seen = {}
    for m in PER_LAYER:
        seen.setdefault(_what_it_is(m["name"]), []).append(m["name"])
    assert [names for names in seen.values() if len(names) > 1] == []


@pytest.mark.parametrize("name", FILES)
def test_a_metric_file_is_listed_or_stated(name):
    listed = name in {m["name"] for m in PER_LAYER}
    assert listed != (name in UNLISTED), name


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_a_cell_has_its_file_and_its_configuration(cell):
    spec = load_json("workloads", cell["name"] + ".json")
    assert (spec["config"], spec["traffic"]) == (
        cell["config"], cell["traffic"])
    config, = [c for c in BENCHMARK["configs"] if c["name"] == cell["config"]]
    assert config["file"] == f"benchmarks/configs/{cell['config']}.json"
    held = load_json("configs", cell["config"] + ".json")
    assert held["chips"] == cell["chips"]
    assert os.path.exists(os.path.join(BENCH, "jobs", spec["job"] + ".py"))
    assert os.path.exists(
        os.path.join(BENCH, "families", held["family"] + ".py"))
    # every cell reports at least one per-layer metric beside the shared
    # ones that carry no list
    assert any(cell["name"] in m.get("workloads", ()) for m in PER_LAYER)


def test_every_configuration_keeps_a_cell():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) <= max(
        1, len(CELLS) // 4)
