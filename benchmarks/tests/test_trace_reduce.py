"""The reduction from trace to numbers, on a trace recorded on the chip:
five steps of ``mistral7b-d5-steady`` on one v5e (PR 24's first chip
run, ``--trace 1 --seed 3``). The same file gives the same numbers every
time; busy and window seconds are the ones that run printed."""

import gzip
import json
import os

import pytest

from conftest import HERE

from benchmarks.harness import readers, trace_reduce

RECORDED = os.path.join(HERE, "data", "mistral7b-d5-steady-5steps.xplane.pb.gz")
SPANS = ("batch", "step", "save")


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def trace(xplane):
    return trace_reduce.load(xplane, SPANS)


def test_what_the_trace_holds(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace_reduce.count_spans(trace, "step") == 5
    assert trace_reduce.count_spans(trace, "batch") == 5
    names = {op[2] for op in trace.devices["/device:TPU:0"]}
    assert {"attention_fwd.16", "attention_bwd.20",
            "jvp_fused_ce_fwd_.1"} <= names


def test_busy_and_idle_share(trace):
    busy_s, window_s = trace_reduce.busy_and_window_s(trace)
    assert busy_s == pytest.approx(5.152017171, abs=1e-9)
    assert window_s == pytest.approx(5.164752028, abs=1e-9)
    assert readers.idle_share({}, type("C", (), {"trace": trace})) == \
        pytest.approx(0.24657247687710493, abs=1e-9)


@pytest.mark.parametrize("metric,ms", [
    ("flash_attn_ms", 482.7480668), ("fused_ce_ms", 58.3538272),
])
def test_kernel_sums_per_step(trace, metric, ms):
    with open(os.path.join(HERE, "..", "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    ctx = type("C", (), {"trace": trace})
    assert readers.trace_ms_per_step(spec, ctx) == pytest.approx(ms, abs=1e-6)


def test_nothing_to_read_is_none(trace):
    assert trace_reduce.matching_s(trace, ["^all-gather"]) is None


def test_same_numbers_every_time(xplane):
    first = trace_reduce.load(xplane, SPANS)
    second = trace_reduce.load(xplane, SPANS)
    for fn in (trace_reduce.busy_and_window_s, trace_reduce.top_ops,
               trace_reduce.idle_gaps):
        assert fn(first) == fn(second)


def test_breakdown_rows(trace):
    ops = trace_reduce.top_ops(trace)
    assert len(ops) == 10 and ops[0][0] == "while.8"
    assert all(len(name) < 80 for name, _ in ops)
    gaps = dict(trace_reduce.idle_gaps(trace))
    assert set(gaps) <= {"batch", "step", "save", "unannotated"}
    busy_s, window_s = trace_reduce.busy_and_window_s(trace)
    assert sum(gaps.values()) == pytest.approx(window_s - busy_s, abs=1e-6)


def test_union_does_not_count_nested_time_twice():
    ops = [(0.0, 10.0, "while.1", ""), (2.0, 4.0, "fusion.1", ""),
           (12.0, 13.0, "fusion.2", "")]
    trace = trace_reduce.Trace({"d": ops}, [(0.0, 20.0, "step")])
    assert trace_reduce.busy_and_window_s(trace) == (11.0 / 1e9, 20.0 / 1e9)
    assert trace_reduce.idle_gaps(trace) == [["step", 9.0 / 1e9]]
