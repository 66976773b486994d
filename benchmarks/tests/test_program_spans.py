"""The readers of the program's own spans, on a trace recorded on the
chip after the program had them: five steps of ``mistral7b-d5-steady``
on one v5e (PR 25, ``--trace 1 --seed 0 --keep-trace``). The older
recording of the same cell, from before the spans, stands for a program
without them."""

import gzip
import os
import types

import pytest

from conftest import HERE, load_json

from benchmarks.harness import program_spans, trace_reduce

RECORDED = os.path.join(
    HERE, "data", "mistral7b-d5-steady-5steps-spans.xplane.pb.gz")
BEFORE_THE_SPANS = os.path.join(
    HERE, "data", "mistral7b-d5-steady-5steps.xplane.pb.gz")
LOOP_SPANS = ("batch", "step", "save")
METRICS_DIR = os.path.join(HERE, "..", "layer_metrics")


def _ctx(tmp_path_factory, recorded):
    """What ``run.py`` hands a reader after a traced run: the directory
    the profiler wrote to, and the reduced trace."""
    trace_dir = tmp_path_factory.mktemp("trace")
    run_dir = trace_dir / "plugins" / "profile" / "recorded"
    run_dir.mkdir(parents=True)
    path = run_dir / "recorded.xplane.pb"
    with gzip.open(recorded) as f:
        path.write_bytes(f.read())
    return types.SimpleNamespace(
        trace_dir=str(trace_dir),
        trace=trace_reduce.load(str(path), LOOP_SPANS),
    )


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return _ctx(tmp_path_factory, RECORDED)


@pytest.fixture(scope="module")
def ctx_before(tmp_path_factory):
    return _ctx(tmp_path_factory, BEFORE_THE_SPANS)


def _spec(metric):
    return load_json("layer_metrics", metric + ".json")


def test_five_train_step_spans_with_their_identity(ctx):
    spans = [s for s in program_spans.spans_of(ctx) if s.name == "train_step"]
    assert len(spans) == 5 == trace_reduce.count_spans(ctx.trace, "step")
    assert len({s.stats["id"] for s in spans}) == 5
    steps = [s.stats["step"] for s in spans]
    assert steps == list(range(steps[0], steps[0] + 5))
    assert all(s.stats["kind"] == "step" and s.stats["parent"] == 0
               and s.stats["host_step"] == s.stats["step"] for s in spans)
    assert len({s.line for s in spans}) == 1
    # each dispatch lies inside the loop's own `step` span of that step
    loop = [sp for sp in ctx.trace.spans if sp[2] == "step"]
    for mine, (lo, hi, _) in zip(spans, loop):
        assert lo <= mine.start_ns and mine.end_ns <= hi
    # read once, kept on ctx
    assert program_spans.spans_of(ctx) is program_spans.spans_of(ctx)


def test_step_dispatch_is_the_median_train_step(ctx):
    durs = sorted(s.end_ns - s.start_ns for s in program_spans.spans_of(ctx)
                  if s.name == "train_step")
    got = program_spans.span_ms_median(_spec("step_dispatch_ms"), ctx)
    assert got == pytest.approx(durs[2] / 1e6)
    assert 0.1 < got < 5.0


@pytest.mark.parametrize("metric", ["d2h_wait_s", "save_d2h_idle_s"])
def test_a_cell_that_never_saves_has_no_d2h_wait(ctx, metric):
    spec = _spec(metric)
    assert not [s for s in program_spans.spans_of(ctx)
                if s.name == spec["span"]]
    assert program_spans.span_ms_median(spec, ctx) is None
    assert program_spans.idle_in_span(spec, ctx) is None


@pytest.mark.parametrize("metric", ["step_dispatch_ms", "trainer_idle_ms"])
def test_a_program_without_spans_reports_nothing(ctx_before, metric):
    assert program_spans.spans_of(ctx_before) == []
    read = getattr(program_spans, {
        "step_dispatch_ms": "span_ms_median",
        "trainer_idle_ms": "idle_in_span"}[metric])
    assert read(_spec(metric), ctx_before) is None


def test_idle_in_span_is_part_of_the_loops_idle_gaps(ctx):
    gaps = dict(trace_reduce.idle_gaps(ctx.trace))
    per_step_ms = program_spans.idle_in_span(_spec("trainer_idle_ms"), ctx)
    # the dispatch lies inside the loop's `step` span, so its idle time
    # is part of that row, and of the total. (On this recording it is
    # 0.0: the device's plane lies one to two milliseconds early against
    # the host's, so the 2.1 ms a step is idle fall before the dispatch.
    # PERF.md, PR 25.)
    assert 0.0 <= per_step_ms * 5 / 1e3 <= gaps["step"]
    assert per_step_ms * 5 / 1e3 <= sum(gaps.values())
    # the loop's own spans as intervals give the loop's own row
    steps = [(a, b) for a, b, name in ctx.trace.spans if name == "step"]
    assert program_spans.idle_ns_inside(ctx.trace, steps) / 1e9 == \
        pytest.approx(gaps["step"], abs=1e-9)
    # the whole window as one interval is all of the idle time
    lo, hi = ctx.trace.window_ns
    assert program_spans.idle_ns_inside(ctx.trace, [(lo, hi)]) / 1e9 == \
        pytest.approx(sum(gaps.values()), abs=1e-9)
    # per occurrence and per step agree where each step has one span
    spec = dict(_spec("trainer_idle_ms"), per="occurrence")
    assert program_spans.idle_in_span(spec, ctx) == pytest.approx(per_step_ms)


def test_counters_and_gauges_come_from_the_programs_process():
    from dlrover_tpu.observability import trace

    trace.trace_ring.clear()
    try:
        assert program_spans.counter_seconds_mean(
            _spec("build_lower_s"), None) is None
        assert program_spans.gauge(_spec("hbm_peak_gib"), None) is None
        for _ in range(2):
            with trace.span("compile", "build.lower"):
                pass
        # hbm_peak_gib reads the compiler's plan since PR 58, not the sum
        trace.gauge("step.hbm_peak_bytes", 4 * 2 ** 30)
        trace.gauge("step.hbm_planned_peak_bytes", 3 * 2 ** 30)
        count, seconds = trace.counters()["build.lower"]
        assert program_spans.counter_seconds_mean(
            _spec("build_lower_s"), None) == pytest.approx(seconds / count)
        assert program_spans.gauge(_spec("hbm_peak_gib"), None) == 3.0
    finally:
        trace.trace_ring.clear()


def _takes_a_span_reader(fname: str) -> bool:
    """A metric's ``.py`` that takes its ``read`` from ``program_spans``
    (the others read scopes, kernels or step rows and have their own
    tests)."""
    with open(os.path.join(METRICS_DIR, fname)) as f:
        return "benchmarks.harness.program_spans import" in f.read()


@pytest.mark.parametrize("metric", sorted(
    f[:-len(".py")] for f in os.listdir(METRICS_DIR)
    if f.endswith(".py") and _takes_a_span_reader(f)))
def test_every_metric_file_names_a_reader_and_what_it_reads(metric):
    import importlib.util

    spec = _spec(metric)
    path = os.path.join(METRICS_DIR, metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(metric, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    assert module.read in (
        program_spans.counter_seconds_mean, program_spans.gauge,
        program_spans.span_ms_median, program_spans.idle_in_span)
    assert ("gauge" in spec) != ("span" in spec)
    if module.read is program_spans.idle_in_span:
        assert spec["unit"] in program_spans.UNIT_PER_SECOND
        assert spec["per"] in ("step", "occurrence")
        assert spec["source"] == "device_trace"
    else:
        assert spec["source"] == "program_counter"
