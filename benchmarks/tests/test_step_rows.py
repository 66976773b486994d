"""The readers of the program's step rows (``harness/step_rows.py``) on
a table of rows written by hand, and the new metrics' files."""

import os
import types

import pytest

from benchmarks.tests.conftest import BENCH, load_json

from benchmarks.harness import step_rows  # noqa: E402

METRICS = (
    "step_interval_ms", "step_interval_mean_ms", "step_drift_pct",
    "late_steps_pct", "late_ms_per_step", "late_named_ms", "late_gc_ms",
    "late_runq_ms", "late_cpu_ms", "late_blocked_ms", "step_host_cpu_ms",
    "step_runq_ms", "gc_pause_ms",
)


def _row(interval, named=0.0, gc=0.0, runq=0.001, cpu=0.002, traced=0,
         edge=0):
    return {"step": 0, "t": 0.0, "interval_s": interval, "dispatch_s": 6e-4,
            "named_s": named, "gc_n": (0, 0, int(gc > 0)),
            "gc_s": (0.0, 0.0, gc), "cpu_s": cpu, "proc_cpu_s": cpu,
            "runq_s": runq, "nivcsw": 0, "majflt": 0, "traced": traced,
            "edge": edge, "late_s": 0.0}


def recorded_rows():
    """The warm-up's row (2 s of the job's own preparations), sixteen
    rows of a window whose step drifts from 300 to 303 ms with three
    late rows of different causes among them, the row that holds
    ``start_trace`` and four traced rows."""
    window = [_row(0.300)] * 4 + [_row(0.301)] * 4 + [_row(0.302)] * 4 + [
        _row(0.303)] * 4
    window[5] = _row(0.352, gc=0.040)                  # 40 gc, 10 blocked
    window[9] = _row(0.322, runq=0.011, cpu=0.012)     # 10 runq, 10 cpu
    window[10] = _row(0.402, named=0.100, cpu=0.050)   # 100 named
    return ([_row(2.0, cpu=1.5)] + window + [_row(5.0, edge=1)]
            + [_row(0.310, traced=1)] * 4)


def _numbers(rows):
    from dlrover_tpu.observability import trace

    return step_rows.numbers(rows, trace.baseline, trace.late_account)


def test_the_thirteen_numbers_by_hand():
    got = _numbers(recorded_rows())
    assert set(got) == set(METRICS)
    # the run's median row: 302 ms (the 8th and the 9th of 16)
    assert got["step_interval_ms"] == pytest.approx(302.0)
    assert got["step_interval_mean_ms"] == pytest.approx(
        (4 * 300 + 3 * 301 + 2 * 302 + 4 * 303 + 352 + 322 + 402) / 16)
    assert got["step_drift_pct"] == pytest.approx(100 * (303 / 300 - 1))
    assert got["late_steps_pct"] == pytest.approx(100 * 3 / 16)
    # excess over 302: 50, 20 and 100
    assert got["late_ms_per_step"] == pytest.approx(170 / 16)
    assert got["late_named_ms"] == pytest.approx(100 / 16)
    assert got["late_gc_ms"] == pytest.approx(40 / 16)
    assert got["late_runq_ms"] == pytest.approx(10 / 16)
    assert got["late_cpu_ms"] == pytest.approx(10 / 16)
    assert got["late_blocked_ms"] == pytest.approx(10 / 16)
    from dlrover_tpu.observability.trace import LATE_CAUSES

    assert sum(got[f"late_{c}_ms"] for c in LATE_CAUSES) == (
        pytest.approx(got["late_ms_per_step"]))
    assert got["step_host_cpu_ms"] == pytest.approx(
        (14 * 2 + 12 + 50) / 16)
    assert got["step_runq_ms"] == pytest.approx(1.0)
    assert got["gc_pause_ms"] == pytest.approx(40 / 16)


def test_neither_the_first_row_nor_the_profilers_are_read():
    rows = recorded_rows()
    kept = step_rows.window_rows(rows)
    assert len(kept) == 16
    assert all(r["interval_s"] < 0.5 and not r["traced"] for r in kept)
    # the traced rows alone: nothing to read
    assert _numbers(rows[:1] + rows[-5:]) is None


def test_numbers_that_nothing_measured_are_left_out():
    blind = [dict(r, runq_s=None) for r in recorded_rows()]
    got = _numbers(blind)
    assert "step_runq_ms" not in got and "late_runq_ms" not in got
    # what the run queue would have taken is blocked's now
    assert got["late_cpu_ms"] == pytest.approx(10 / 16)
    assert got["late_blocked_ms"] == pytest.approx(20 / 16)
    assert got["late_ms_per_step"] == pytest.approx(170 / 16)
    few = _numbers(recorded_rows()[:6])
    assert "step_drift_pct" not in few and few["late_steps_pct"] == 0.0


@pytest.mark.parametrize("metric", METRICS)
def test_a_process_without_rows_reads_none(metric, monkeypatch):
    from dlrover_tpu.observability import trace

    spec = load_json("layer_metrics", metric + ".json")
    # a program older than the rows has no such function
    monkeypatch.delattr(trace, "step_rows")
    assert step_rows.read(spec, types.SimpleNamespace()) is None
    # one that has kept none yet returns an empty table
    monkeypatch.setattr(trace, "step_rows", list, raising=False)
    assert step_rows.read(spec, types.SimpleNamespace()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_reader_hands_out_its_files_number(metric, monkeypatch):
    import importlib.util

    from dlrover_tpu.observability import trace

    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(metric, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    assert module.read is step_rows.read
    spec = load_json("layer_metrics", metric + ".json")
    monkeypatch.setattr(trace, "step_rows", recorded_rows)
    ctx = types.SimpleNamespace()
    assert module.read(spec, ctx) == _numbers(recorded_rows())[metric]
    assert ctx.step_row_numbers[metric] == module.read(spec, ctx)


@pytest.mark.parametrize("metric", METRICS)
def test_every_new_metric_file_says_what_it_reads(metric):
    spec = load_json("layer_metrics", metric + ".json")
    assert {"layer", "unit", "better", "source", "moves", "what",
            "number"} == set(spec)
    assert spec["number"] == metric
    assert (spec["layer"], spec["source"]) == ("trainer", "program_counter")
    assert spec["unit"] == ("%" if metric.endswith("_pct") else "ms")
    listed = [m for m in load_json("..", "BENCHMARK.json")["per_layer"]
              if m["name"] == metric]
    if metric in ("step_runq_ms", "late_runq_ms"):
        # the machine with the chip has no schedstat: listed, their
        # absence would refuse every traced run
        assert listed == []
        return
    assert listed == [{"name": metric, "unit": spec["unit"],
                       "better": spec["better"], "source": spec["source"],
                       "layer": spec["layer"], "moves": spec["moves"]}]
