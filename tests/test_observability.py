"""The goodput observatory (docs/design/observability.md): trace spine,
per-rank step-time digests, straggler detection, lost-time attribution
and the job-timeline merge CLI."""

import json
import os
import time

import pytest

from dlrover_tpu.common import flags


@pytest.fixture
def no_gc_hook():
    """The collector's hook off for the test: a collection may begin
    anywhere, and a test that counts events or counters exactly cannot
    have its span among them. An earlier test's trainer installed it."""
    import gc

    from dlrover_tpu.observability.trace import trace_ring

    was_on = trace_ring.on_gc in gc.callbacks
    if was_on:
        gc.callbacks.remove(trace_ring.on_gc)
    yield
    if was_on and trace_ring.on_gc not in gc.callbacks:
        gc.callbacks.append(trace_ring.on_gc)


@pytest.fixture
def traced(monkeypatch, no_gc_hook):
    """Spine on, recording into a clean ring."""
    from dlrover_tpu.observability.trace import trace_ring

    monkeypatch.setenv("DLROVER_TPU_TRACE", "1")
    trace_ring.clear()
    yield trace_ring
    trace_ring.clear()


# ---------------------------------------------------------------------------
# trace spine
# ---------------------------------------------------------------------------


def test_trace_ring_off_by_default(monkeypatch):
    from dlrover_tpu.observability.trace import TraceRing

    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    r = TraceRing()
    r.record("step", "train_step", time.monotonic(), 0.01)
    with r.span("compile") as sp:
        pass
    assert r.events() == []
    # per-kind seconds are kept with the ring off, as the counters are
    assert r.kind_seconds() == {"compile": sp.dur}


def test_trace_ring_records_spans_and_kind_totals(traced):
    m0 = time.monotonic()
    traced.record("step", "train_step", m0, 0.25, host_step=7)
    traced.record("ckpt_restore", "restore", m0 + 0.3, 0.5, tier="disk")
    with traced.span("compile", "lower_step.w4", world=4):
        pass
    evs = traced.events()
    assert [e["kind"] for e in evs] == ["step", "ckpt_restore", "compile"]
    assert evs[0]["attrs"]["host_step"] == 7
    assert evs[1]["attrs"]["tier"] == "disk"
    ks = traced.kind_seconds()
    assert ks["step"] == pytest.approx(0.25)
    assert ks["ckpt_restore"] == pytest.approx(0.5)


def test_trace_ring_bounded_but_totals_survive(traced, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_TRACE_RING_CAP", "20")
    m0 = time.monotonic()
    for i in range(100):
        traced.record("step", f"s{i}", m0 + i, 0.01)
    assert len(traced.events()) <= 21
    # per-kind seconds keep counting through overflow
    assert traced.kind_seconds()["step"] == pytest.approx(1.0)


# -- one span() call, three sinks ----------------------------------------


@pytest.fixture
def untraced(monkeypatch, no_gc_hook):
    """Spine off (the default), clean counters."""
    from dlrover_tpu.observability.trace import trace_ring

    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    trace_ring.clear()
    yield trace_ring
    trace_ring.clear()


@pytest.mark.parametrize("times", [1, 3])
def test_counters_count_with_the_ring_off(untraced, times):
    from dlrover_tpu.observability import trace

    for _ in range(times):
        with trace.span("ckpt_save", "d2h.wait") as sp:
            time.sleep(0.002)
        assert sp.dur >= 0.002
    count, seconds = trace.counters()["d2h.wait"]
    assert count == times and seconds >= 0.002 * times
    assert untraced.events() == []
    assert untraced.kind_seconds() == {"ckpt_save": seconds}
    # what counters() hands out is a copy
    trace.counters().clear()
    assert "d2h.wait" in trace.counters()


def test_gauges_hold_the_last_value_with_the_ring_off(untraced):
    from dlrover_tpu.observability import trace

    trace.gauge("step.hbm_peak_bytes", 10)
    trace.gauge("step.hbm_peak_bytes", 12)
    assert trace.gauges() == {"step.hbm_peak_bytes": 12.0}
    assert untraced.events() == []
    untraced.clear()
    assert trace.gauges() == {} and trace.counters() == {}


def test_record_reaches_the_ring_only(traced):
    from dlrover_tpu.observability import trace

    trace.record("compile", "resize.first_step_compile",
                 time.monotonic() - 1.0, 1.0, tid="resize")
    assert [e["name"] for e in traced.events()] == [
        "resize.first_step_compile"]
    assert trace.counters() == {}


def test_span_identity_parent_and_inherited_step(traced):
    from dlrover_tpu.observability import trace

    with trace.span("ckpt_save", "save.blocking", step=9, tier="shm") as a:
        with trace.span("ckpt_save", "d2h.issue") as b:
            b.set(shards=4)
        with trace.span("ckpt_save", "d2h.wait", step=10) as c:
            pass
    with trace.span("step", "train_step") as d:
        pass
    assert len({a.id, b.id, c.id, d.id}) == 4
    assert (a.parent, b.parent, c.parent, d.parent) == (
        None, a.id, a.id, None)
    assert (a.step, b.step, c.step, d.step) == (9, 9, 10, None)
    by_name = {e["name"]: e["attrs"] for e in traced.events()}
    assert by_name["d2h.issue"] == {
        "shards": 4, "id": b.id, "parent": a.id, "step": 9}
    assert by_name["save.blocking"]["tier"] == "shm"
    assert "parent" not in by_name["train_step"]


def test_span_cause_crosses_threads(traced):
    """A new thread starts with no enclosing span: the work it is
    handed names its cause, and states its step, explicitly."""
    import threading

    from dlrover_tpu.observability import trace

    seen = {}

    def stage(cause, step):
        with trace.span("ckpt_save", "stage.background", cause=cause,
                        step=step) as bg:
            with trace.span("ckpt_save", "stage.shm_write") as child:
                pass
        seen.update(bg=bg, child=child)

    with trace.span("ckpt_save", "save.blocking", step=5) as pause:
        t = threading.Thread(target=stage, args=(pause.id, 5))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert seen["bg"].parent == pause.id and seen["bg"].step == 5
    assert seen["child"].parent == seen["bg"].id and seen["child"].step == 5
    tids = {e["name"]: e["tid"] for e in traced.events()}
    assert tids["stage.background"] != tids["save.blocking"]


def test_span_inside_its_own_kind_adds_nothing_to_the_kind(traced):
    from dlrover_tpu.observability import trace

    with trace.span("ckpt_save", "save.blocking") as outer:
        with trace.span("ckpt_save", "d2h.wait") as inner:
            time.sleep(0.002)
        with trace.span("host", "callback") as other:
            pass
    ks = traced.kind_seconds()
    assert ks["ckpt_save"] == pytest.approx(outer.dur)
    assert ks["host"] == pytest.approx(other.dur)
    assert inner.dur > 0 and len(traced.events()) == 3


def test_span_closes_and_unwinds_on_an_exception(untraced):
    from dlrover_tpu.observability import trace

    with pytest.raises(ValueError):
        with trace.span("ckpt_save", "save.blocking"):
            raise ValueError("boom")
    assert trace.counters()["save.blocking"][0] == 1
    with trace.span("step", "train_step") as after:
        pass
    assert after.parent is None


@pytest.mark.parametrize("spine", ["0", "1"])
def test_span_lands_in_the_profilers_host_plane(monkeypatch, tmp_path,
                                                spine, no_gc_hook):
    """Under a profiler session a span is a host event named
    ``dlrover/<name>`` with its identity and attributes as stats, on the
    profiler's clock; whether the ring is on makes no difference."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from dlrover_tpu.observability import trace

    monkeypatch.setenv("DLROVER_TPU_TRACE", spine)
    trace.trace_ring.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("ckpt_save", "save.blocking", step=7,
                        tier="shm") as outer:
            with trace.span("ckpt_save", "d2h.wait") as inner:
                inner.set(bytes=4096)
    finally:
        jax.profiler.stop_trace()
        trace.trace_ring.clear()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dlrover/"):
                    found[e.name] = (e, dict(e.stats))
    assert set(found) == {"dlrover/save.blocking", "dlrover/d2h.wait"}
    ev_out, out = found["dlrover/save.blocking"]
    ev_in, inn = found["dlrover/d2h.wait"]
    assert out == {"kind": "ckpt_save", "id": outer.id, "parent": 0,
                   "step": 7, "tier": "shm"}
    assert inn == {"kind": "ckpt_save", "id": inner.id,
                   "parent": outer.id, "step": 7, "bytes": 4096}
    assert ev_out.start_ns <= ev_in.start_ns
    assert (ev_in.start_ns + ev_in.duration_ns
            <= ev_out.start_ns + ev_out.duration_ns)


def test_trace_module_imports_without_jax():
    """Master and agent import the spine; it must never pull JAX in."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from dlrover_tpu.observability import trace\n"
        "with trace.span('step', 'train_step', step=1):\n"
        "    pass\n"
        "trace.gauge('g', 1)\n"
        "assert trace.counters()['train_step'][0] == 1\n"
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))]\n"
        "assert not bad, bad\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=root)


def test_chrome_export_epoch_clock_and_dump(traced, tmp_path):
    m0 = time.monotonic()
    wall_now_us = time.time() * 1e6
    traced.record("step", "train_step", m0, 0.1)
    ev = traced.chrome_events(pid=5)[0]
    assert ev["ph"] == "X" and ev["pid"] == 5
    assert ev["dur"] == 100000
    # epoch-us clock: the span maps to ~now
    assert abs(ev["ts"] - wall_now_us) < 60e6
    path = traced.dump(
        str(tmp_path / "t.json"), role="worker", node_id=3, process_id=1
    )
    doc = json.load(open(path))
    meta = doc["dlrover"]
    assert meta["role"] == "worker"
    assert meta["clock"] == "epoch_us"
    assert meta["node_id"] == 3
    assert len(doc["traceEvents"]) == 1


@pytest.fixture
def gc_hook(no_gc_hook):
    """The collector's hook on for this test alone."""
    import gc

    from dlrover_tpu.observability import trace

    assert trace.install_gc_hook() is True
    assert trace.install_gc_hook() is False      # idempotent
    yield
    gc.callbacks.remove(trace.trace_ring.on_gc)


def test_gc_and_user_spans_reach_the_spine(traced, gc_hook):
    """GC + user spans adopt the spine's span classification: gc ->
    gc_pause (named by generation), dataloader -> input_wait, other
    cats -> host; all three through the one ring."""
    import gc

    from dlrover_tpu.observability import trace
    from dlrover_tpu.profiler.py_tracing import py_tracer

    with py_tracer.span("dataloader.next", cat="dataloader"):
        pass
    with py_tracer.span("preprocess", cat="user"):
        pass
    gc.collect()
    by_name = {e["name"]: e for e in traced.events()}
    assert by_name["dataloader.next"]["kind"] == "input_wait"
    assert by_name["preprocess"]["kind"] == "host"
    assert by_name["gc.gen2"]["kind"] == "gc_pause"
    assert by_name["gc.gen2"]["dur"] > 0
    assert "collected" in by_name["gc.gen2"]["attrs"]
    counters = trace.counters()
    assert counters["dataloader.next"][0] == 1
    assert counters["gc.gen2"][0] >= 1


def test_gc_pause_reaches_the_counters_with_every_flag_unset(
        untraced, gc_hook):
    """The flags that used to turn host tracing on are gone: a
    collection is counted always, and reaches the ring only behind
    ``DLROVER_TPU_TRACE``."""
    import gc

    from dlrover_tpu.observability import trace

    assert not hasattr(flags, "PY_TRACING")
    assert not hasattr(flags, "PY_TRACING_CAP")
    gc.collect()
    gc.collect(0)
    counters = trace.counters()
    assert counters["gc.gen2"][0] == 1 and counters["gc.gen0"][0] >= 1
    assert counters["gc.gen2"][1] > 0
    assert untraced.kind_seconds()["gc_pause"] == pytest.approx(
        sum(s for name, (_, s) in counters.items()
            if name.startswith("gc.gen")))
    assert untraced.events() == []
    untraced.clear()
    assert "gc.gen2" not in trace.counters()


def test_attribution_from_kind_seconds():
    from dlrover_tpu.observability.trace import (
        attribution_from_kind_seconds,
    )

    out = attribution_from_kind_seconds(
        {"step": 6.0, "compile": 2.0, "ckpt_save": 0.5,
         "ckpt_restore": 0.5, "input_wait": 1.0},
        wall_s=20.0,
    )
    cats = out["categories"]
    assert cats["productive"] == 6.0
    assert cats["compile"] == 2.0
    assert cats["checkpoint"] == 1.0
    assert cats["input_stall"] == 1.0
    assert cats["unattributed"] == 10.0
    assert sum(cats.values()) == pytest.approx(out["wall_s"])
    # overflowing measurements scale down instead of summing past wall
    over = attribution_from_kind_seconds({"step": 30.0}, wall_s=10.0)
    assert sum(over["categories"].values()) == pytest.approx(10.0)


def test_spine_prometheus_lines(traced):
    from dlrover_tpu.observability import digest as digest_mod
    from dlrover_tpu.observability.trace import prometheus_lines

    traced.record("step", "train_step", time.monotonic(), 0.2)
    digest_mod.set_last_window(
        {"count": 8, "mean_s": 0.2, "p50_s": 0.19, "p95_s": 0.3,
         "max_s": 0.31}
    )
    text = "\n".join(prometheus_lines())
    assert 'dlrover_tpu_trace_seconds_total{kind="step"}' in text
    assert 'dlrover_tpu_step_time_seconds{stat="p95"} 0.3' in text
    assert "dlrover_tpu_step_window_steps 8" in text


def test_spine_prometheus_lines_carry_counters_and_gauges(untraced):
    """With the ring off the worker's /metrics still has what the spans
    counted and the gauges: the operator's reader of both."""
    from dlrover_tpu.observability import trace

    for _ in range(2):
        with trace.span("ckpt_save", "d2h.wait"):
            pass
    trace.gauge("ckpt.staged_bytes", 8154000000)
    text = "\n".join(trace.prometheus_lines())
    assert 'dlrover_tpu_span_count_total{name="d2h.wait"} 2' in text
    assert 'dlrover_tpu_span_seconds_total{name="d2h.wait"} 0.0' in text
    assert ('dlrover_tpu_trace_gauge{name="ckpt.staged_bytes"} 8.154e+09'
            in text)
    # ... and the seconds a kind, which no longer wait for the ring
    assert 'dlrover_tpu_trace_seconds_total{kind="ckpt_save"}' in text


# ---------------------------------------------------------------------------
# step-time digests
# ---------------------------------------------------------------------------


def test_step_digest_window_fold_and_drain():
    from dlrover_tpu.observability.digest import StepTimeDigest

    d = StepTimeDigest()
    assert d.snapshot_and_reset() is None
    for v in [0.1] * 18 + [0.5, 0.9]:
        d.add(v)
    w = d.snapshot_and_reset()
    assert w["count"] == 20
    assert w["p50_s"] == pytest.approx(0.1)
    assert w["p95_s"] == pytest.approx(0.5)
    assert w["max_s"] == pytest.approx(0.9)
    assert w["mean_s"] == pytest.approx((18 * 0.1 + 0.5 + 0.9) / 20)
    # the drain reset the window
    assert d.snapshot_and_reset() is None


def test_step_digest_bounded_samples_full_count():
    from dlrover_tpu.observability.digest import StepTimeDigest

    d = StepTimeDigest(max_samples=10)
    for _ in range(100):
        d.add(0.1)
    w = d.snapshot_and_reset()
    assert w["count"] == 100  # mean/count fold every sample
    assert w["p50_s"] == pytest.approx(0.1)


def test_worker_context_report_drains_digest(monkeypatch):
    """The throttled step report drains one digest window, attaches the
    spine's input-wait delta, and publishes the window for /metrics."""
    from dlrover_tpu.observability import digest as digest_mod
    from dlrover_tpu.observability.digest import StepTimeDigest
    from dlrover_tpu.observability.trace import trace_ring
    from dlrover_tpu.train.bootstrap import WorkerContext, WorkerEnv

    monkeypatch.setenv("DLROVER_TPU_TRACE", "1")
    trace_ring.clear()

    sent = []

    class Client:
        def report_global_step(self, step, digest=None):
            sent.append((step, digest))

    ctx = WorkerContext(WorkerEnv(), Client())
    d = StepTimeDigest()
    for _ in range(4):
        d.add(0.05)
    trace_ring.record("input_wait", "dataloader.next", time.monotonic(),
                      0.7)
    ctx.report_step(3, force=True, digest=d)
    step, payload = sent[-1]
    assert step == 3
    assert payload["count"] == 4
    assert payload["input_wait_s"] == pytest.approx(0.7)
    assert digest_mod.last_window()["count"] == 4
    # second report: window drained, nothing new -> no digest attached
    ctx.report_step(4, force=True, digest=d)
    assert sent[-1][1] is None
    # input-wait is a DELTA: nothing new accrued
    d.add(0.05)
    ctx.report_step(5, force=True, digest=d)
    assert sent[-1][1]["input_wait_s"] == 0.0
    trace_ring.clear()


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_straggler_detector_flags_delayed_rank_in_simulated_fleet():
    from dlrover_tpu.master.monitor.straggler import StragglerDetector

    det = StragglerDetector(ratio=1.5, windows=3)
    flagged = []
    for window in range(4):
        for nid in range(8):
            p50 = 0.35 if nid == 5 else 0.1 + 0.001 * nid
            rec = det.observe(nid, p50, count=30)
            if rec is not None:
                flagged.append((window, rec))
    assert det.stragglers() == [5]
    # flagged exactly once, on the K-th consecutive window
    assert len(flagged) == 1
    window, rec = flagged[0]
    assert window == 2 and rec.node_id == 5
    assert rec.windows == 3
    assert rec.p50_s == pytest.approx(0.35)
    # lost time: the fleet waits (p50 - median) per step of each slow
    # window — all 4 windows were slow
    assert det.lost_seconds() == pytest.approx(
        4 * 30 * (0.35 - det._median([0.1 + 0.001 * n for n in range(8)
                                      if n != 5] + [0.35])), rel=0.01,
    )


def test_straggler_detector_quiet_on_uniform_fleet():
    from dlrover_tpu.master.monitor.straggler import StragglerDetector

    det = StragglerDetector(ratio=1.5, windows=2)
    for _ in range(10):
        for nid in range(6):
            # uniform fleet with realistic jitter
            assert det.observe(nid, 0.1 + 0.005 * (nid % 3), count=30) is None
    assert det.stragglers() == []
    assert det.lost_seconds() == 0.0


def test_straggler_recovers_and_consecutive_requirement():
    from dlrover_tpu.master.monitor.straggler import StragglerDetector

    det = StragglerDetector(ratio=1.5, windows=3)
    # alternating slow/fast windows never flag (consecutive required)
    for window in range(8):
        p50_slow = 0.4 if window % 2 == 0 else 0.1
        det.observe(0, 0.1)
        assert det.observe(1, p50_slow, count=10) is None
    assert det.stragglers() == []
    # flag, then recover
    for _ in range(3):
        det.observe(0, 0.1)
        det.observe(1, 0.4, count=10)
    assert det.stragglers() == [1]
    det.observe(1, 0.1)
    assert det.stragglers() == []


def test_digest_report_reaches_monitor_and_diagnosis_via_servicer():
    """GlobalStepReport.digest -> SpeedMonitor (straggler + attribution
    ledgers) and a newly flagged rank -> the diagnosis pipeline; the
    StragglersRequest RPC unions the runtime stragglers in."""
    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.common.serde import deserialize, serialize
    from dlrover_tpu.diagnosis.data import DiagnosisDataType
    from dlrover_tpu.master.diagnosis.manager import DiagnosisManager
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
    from dlrover_tpu.master.servicer import MasterServicer

    sm = SpeedMonitor()
    sm.straggler_detector.windows = 2
    diag = DiagnosisManager(speed_monitor=sm)
    servicer = MasterServicer(speed_monitor=sm, diagnosis_manager=diag)
    # backdate training start: the attribution clamps lost seconds into
    # the elapsed wall, and a milliseconds-old job would scale the
    # injected categories toward zero
    sm.collect_global_step(1, time.time() - 300.0)
    step = 1
    for _ in range(3):
        for nid in range(3):
            step += 1
            slow = nid == 2
            report = msg.GlobalStepReport(
                node_id=nid, step=step, timestamp=time.time(),
                digest={"count": 10, "mean_s": 0.3 if slow else 0.1,
                        "p50_s": 0.3 if slow else 0.1,
                        "p95_s": 0.31, "max_s": 0.4},
            )
            # the real wire path serializes; digest dict must survive
            resp = servicer.report(deserialize(serialize(report)))
            assert resp.success
    assert sm.stragglers() == [2]
    # the flagged rank produced a diagnosis observation
    recs = diag.data_manager.get_data(DiagnosisDataType.STRAGGLER)
    assert len(recs) == 1
    assert recs[0].node_id == 2
    assert recs[0].p50_s == pytest.approx(0.3)
    # the stragglers RPC unions netcheck + runtime stragglers
    resp = servicer.get(msg.StragglersRequest())
    assert resp.nodes == [2]
    # checkpoint blocking report feeds the attribution ledger
    servicer.report(msg.CheckpointStepReport(node_id=0, step=step,
                                             blocking_s=1.25))
    assert sm.attribution()["categories"]["checkpoint"] == pytest.approx(
        1.25
    )


def test_departed_rank_leaves_straggler_fleet():
    """Elastic shrink: a removed worker's p50 must stop skewing the
    fleet median and a flagged-but-gone rank must leave the straggler
    list (a replacement node reusing the id starts clean)."""
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor()
    sm.straggler_detector.windows = 2
    for _ in range(2):
        for nid in range(3):
            slow = nid == 2
            sm.collect_step_digest(nid, {
                "count": 5, "mean_s": 0.3 if slow else 0.1,
                "p50_s": 0.3 if slow else 0.1, "p95_s": 0.31,
                "max_s": 0.4,
            })
    assert sm.stragglers() == [2]
    sm.remove_running_worker("worker", 2)
    assert sm.stragglers() == []
    det = sm.straggler_detector
    st = det.export_state()
    assert "2" not in st["latest_p50"] and "2" not in st["strikes"]
    # a replacement reusing the id starts with zero strikes
    assert det.observe(2, 0.1, count=5) is None
    assert sm.stragglers() == []


def test_failed_step_report_retries_digest_window(monkeypatch):
    """A report that fails mid-master-relaunch must not erase its
    window from the attribution: the drained digest merges into the
    next successful report."""
    from dlrover_tpu.observability.digest import StepTimeDigest
    from dlrover_tpu.train.bootstrap import WorkerContext, WorkerEnv

    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    sent = []

    class FlakyClient:
        fail = True

        def report_global_step(self, step, digest=None):
            if self.fail:
                raise OSError("master relaunching")
            sent.append((step, digest))

    client = FlakyClient()
    ctx = WorkerContext(WorkerEnv(), client)
    d = StepTimeDigest()
    for _ in range(4):
        d.add(0.1)
    ctx.report_step(10, force=True, digest=d)  # fails, window stashed
    assert sent == []
    client.fail = False
    for _ in range(6):
        d.add(0.2)
    ctx.report_step(20, force=True, digest=d)
    step, payload = sent[-1]
    assert step == 20
    # both windows folded: 4x0.1 + 6x0.2
    assert payload["count"] == 10
    assert payload["mean_s"] == pytest.approx((4 * 0.1 + 6 * 0.2) / 10)
    assert payload["max_s"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# trainer integration: step + compile spans, digest fold
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def three_trainer_steps():
    """Three steps of a tiny trainer with the ring on: (ring events,
    counters, gauges, the digest's window, kind seconds, step rows)."""
    import jax

    from dlrover_tpu.models import llama
    from dlrover_tpu.observability import trace
    from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    mp = pytest.MonkeyPatch()
    mp.setenv("DLROVER_TPU_TRACE", "1")
    trace.trace_ring.clear()
    try:
        cfg = llama.LlamaConfig.tiny()
        mc = MeshConfig(dp=1, fsdp=1, sp=1, tp=1).resolve(1)
        mesh = build_mesh(mc, devices=jax.devices()[:1])
        specs = llama.param_specs(cfg)
        params = jax.device_put(
            llama.init_params(cfg, jax.random.key(0)),
            named_shardings(mesh, specs),
        )
        tc = TrainConfig(global_batch_size=2, micro_batch_size=2,
                         warmup_steps=0, total_steps=10)
        trainer = ElasticTrainer(
            lambda p, t: llama.loss_fn(p, t, cfg, None), specs, mesh, mc, tc
        )
        state = trainer.init_state(params)
        batch = jax.random.randint(
            jax.random.key(1), (1, 2, 16), 0, cfg.vocab_size
        )
        for _ in range(3):
            state, loss = trainer.step(state, batch)
        jax.block_until_ready(loss)
        yield (trace.trace_ring.events(), trace.counters(), trace.gauges(),
               trainer.step_digest.snapshot_and_reset(),
               trace.trace_ring.kind_seconds(), trace.step_rows())
    finally:
        trace.trace_ring.clear()
        mp.undo()


def test_trainer_emits_step_compile_spans_and_digest(three_trainer_steps):
    events, counters, _, window, kind_seconds, rows = three_trainer_steps
    kinds = [e["kind"] for e in events]
    # warm-compile default on: the AOT build recorded compile spans
    assert "compile" in kinds
    # steps after the first (build) call recorded step spans
    steps = [e["attrs"] for e in events if e["name"] == "train_step"]
    assert [(a["step"], a["host_step"]) for a in steps] == [(2, 2), (3, 3)]
    # the build call opens no interval: the one row runs from the
    # second call's dispatch to the third's, and it is a ring event too
    (row,) = rows
    assert (row["step"], row["edge"], row["traced"]) == (3, 0, 0)
    (row_event,) = [e for e in events if e["name"] == "step_row"]
    assert row_event["kind"] == "step"
    assert row_event["dur"] == pytest.approx(row["interval_s"])
    assert row_event["attrs"]["step"] == 3
    # the digest folded the interval, dispatch to dispatch (it rounds
    # to microseconds), not the dispatch inside it
    assert window is not None and window["count"] == 1
    assert window["mean_s"] == pytest.approx(row["interval_s"], abs=2e-6)
    assert row["dispatch_s"] < row["interval_s"]
    # ... which is step 3's, up to where the account closed inside it
    assert 0 < row["dispatch_s"] <= [
        e for e in events if e["name"] == "train_step"][1]["dur"]
    # step 3's span carries the account of the interval it closed
    assert "prev_interval_ms" not in steps[0]
    assert steps[1]["prev_interval_ms"] == pytest.approx(
        row["interval_s"] * 1e3)
    assert {"prev_cpu_ms", "prev_named_ms"} <= set(steps[1])
    # the build's children decompose it: the kind counts the build once
    (build,) = [e for e in events if e["name"] == "build"]
    assert kind_seconds["compile"] == pytest.approx(build["dur"])


@pytest.mark.parametrize("name,parent", [
    ("first_step", None),
    ("build", "first_step"),
    ("build.avatars", "build"),
    ("build.lower", "build"),
    ("build.compile", "build"),
    ("build.checks", "build"),
    ("build.speculate", "build"),
])
def test_trainer_first_step_spans(three_trainer_steps, name, parent):
    events, counters, _, _, _, _ = three_trainer_steps
    by_name = {e["name"]: e for e in events}
    attrs = by_name[name]["attrs"]
    assert counters[name][0] == 1
    assert attrs["step"] == 1
    if parent is None:
        assert "parent" not in attrs
        # the first call stays out of train_step
        assert counters["train_step"][0] == 2
    else:
        assert attrs["parent"] == by_name[parent]["attrs"]["id"]
        assert by_name[name]["dur"] <= by_name[parent]["dur"]
    if name == "build":
        assert (attrs["cache"], attrs["world"]) == ("miss", 1)
        lowered = by_name["build.lower"]["dur"]
        compiled = by_name["build.compile"]["dur"]
        assert lowered + compiled <= by_name["build"]["dur"]


@pytest.mark.parametrize("gauge", [
    "step.hbm_peak_bytes", "step.hbm_temp_bytes", "step.hbm_argument_bytes",
])
def test_trainer_sets_hbm_gauges_at_build(three_trainer_steps, gauge):
    _, _, gauges, _, _, _ = three_trainer_steps
    assert gauges[gauge] > 0
    assert gauges["step.hbm_peak_bytes"] >= gauges[gauge]


# ---------------------------------------------------------------------------
# the step's account: one row an interval between two entries to step()
# ---------------------------------------------------------------------------


class _Stepper:
    """A tiny built trainer whose every step fetches its loss, as the
    benchmark's jobs and any loop that logs its loss do."""

    def __init__(self):
        import jax

        from dlrover_tpu.models import llama
        from dlrover_tpu.parallel import (
            MeshConfig, build_mesh, named_shardings,
        )
        from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

        cfg = llama.LlamaConfig.tiny()
        mc = MeshConfig(dp=1, fsdp=1, sp=1, tp=1).resolve(1)
        mesh = build_mesh(mc, devices=jax.devices()[:1])
        specs = llama.param_specs(cfg)
        params = jax.device_put(
            llama.init_params(cfg, jax.random.key(0)),
            named_shardings(mesh, specs),
        )
        tc = TrainConfig(global_batch_size=2, micro_batch_size=2,
                         warmup_steps=0, total_steps=100000)
        self.trainer = ElasticTrainer(
            lambda p, t: llama.loss_fn(p, t, cfg, None), specs, mesh, mc, tc
        )
        self.state = self.trainer.init_state(params)
        self.batch = jax.random.randint(
            jax.random.key(1), (1, 2, 16), 0, cfg.vocab_size
        )
        self.step()     # the build

    def step(self, times: int = 1):
        for _ in range(times):
            self.state, loss = self.trainer.step(self.state, self.batch)
            float(loss)

    def settle(self, rows: int = 12):
        """A clean spine, an open interval and ``rows`` ordinary rows:
        enough for the running median to judge the next."""
        from dlrover_tpu.observability import trace

        self.trainer._account.reset()
        trace.trace_ring.clear()
        self.trainer.step_digest.snapshot_and_reset()
        self.step(rows + 1)


@pytest.fixture(scope="module")
def stepper_built():
    return _Stepper()


@pytest.fixture
def stepper(stepper_built, monkeypatch):
    from dlrover_tpu.observability import trace

    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    stepper_built.settle()
    yield stepper_built
    trace.trace_ring.clear()


def _late(trace):
    """The ``late.<cause>`` counters: ({cause: seconds}, late rows)."""
    rows = {name[len("late."):]: v for name, v in trace.counters().items()
            if name.startswith("late.")}
    counts = {n for n, _ in rows.values()}
    assert len(counts) <= 1     # every cause counts every late row
    return ({cause: rows.get(cause, (0, 0.0))[1]
             for cause in trace.LATE_CAUSES},
            counts.pop() if counts else 0)


def _next_row(stepper):
    """One more step: (the row its entry closed, what that row alone
    added to the ``late.<cause>`` counters, the late rows it added)."""
    from dlrover_tpu.observability import trace

    before, n_before = _late(trace)
    stepper.step()
    after, n_after = _late(trace)
    return (trace.step_rows()[-1],
            {cause: after[cause] - before[cause] for cause in after},
            n_after - n_before)


ROW_FIELDS = {
    "step", "t", "interval_s", "dispatch_s", "named_s", "gc_n", "gc_s",
    "cpu_s", "proc_cpu_s", "runq_s", "nivcsw", "majflt", "traced", "edge",
    "late_s",
}


def test_step_row_a_step_with_every_field(stepper):
    from dlrover_tpu.observability import trace

    rows = trace.step_rows()
    assert len(rows) == 12      # 13 calls: the first opens, closes none
    first = stepper.trainer._host_step - 11
    assert [r["step"] for r in rows] == list(range(first, first + 12))
    for r in rows:
        assert set(r) == ROW_FIELDS
        assert 0 < r["dispatch_s"] < r["interval_s"]
        assert 0 < r["cpu_s"] <= r["proc_cpu_s"] + 1e-3
        assert len(r["gc_n"]) == len(r["gc_s"]) == 3
        assert (r["traced"], r["edge"]) == (0, 0)
        assert r["runq_s"] is None or r["runq_s"] >= 0
    # with every flag unset nothing reached the ring
    assert trace.trace_ring.events() == []
    # what step_rows() hands out is a copy
    trace.step_rows().clear()
    rows[0]["step"] = -1
    assert trace.step_rows()[0]["step"] == first


def test_step_row_intervals_tile_the_wall(stepper):
    """Dispatch to dispatch: the rows' intervals sum to the wall between
    the first dispatch and the last, whatever the loop did in between."""
    from dlrover_tpu.observability import trace

    before = len(trace.step_rows())
    t0 = time.monotonic()
    stepper.step()              # its dispatch closes the interval open now
    time.sleep(0.01)
    stepper.step(3)
    t1 = time.monotonic()
    rows = trace.step_rows()[before + 1:]
    assert len(rows) == 3
    assert rows[0]["t"] >= t0
    for a, b in zip(rows, rows[1:]):
        assert b["t"] == pytest.approx(a["t"] + a["interval_s"], abs=1e-9)
    assert rows[-1]["t"] + rows[-1]["interval_s"] <= t1
    assert sum(r["interval_s"] for r in rows) >= 0.01


def test_a_save_between_two_steps_is_named_not_blocked(stepper):
    from dlrover_tpu.observability import trace

    with trace.span("ckpt_save", "save.blocking") as save:
        with trace.span("ckpt_save", "d2h.wait"):   # counted once
            time.sleep(0.05)
    row, late, n = _next_row(stepper)
    assert row["named_s"] == pytest.approx(save.dur)
    assert row["late_s"] >= 0.04 and n == 1
    assert sum(late.values()) == pytest.approx(row["late_s"])
    # the save's seconds are named whatever else the host was doing
    assert late["named"] >= 0.045 and late["blocked"] < late["named"]
    # a span of another thread is not this thread's work
    import threading

    def elsewhere():
        with trace.span("ckpt_save", "stage.background"):
            time.sleep(0.02)

    t = threading.Thread(target=elsewhere)
    t.start()
    t.join()
    row, _, _ = _next_row(stepper)
    assert row["named_s"] == 0.0


def test_a_wait_for_input_is_named(stepper):
    """The dataloader's fetch opens its own ``input_wait`` span: it is
    the row's ``named_s``, the kind's seconds with the ring off, and
    what bootstrap reports to the master."""
    import numpy as np

    from dlrover_tpu.observability import trace
    from dlrover_tpu.train.bootstrap import WorkerContext, WorkerEnv
    from dlrover_tpu.train.data import ElasticDataLoader

    class Slow:
        def __len__(self):
            return 4

        def __getitem__(self, i):
            time.sleep(0.01)
            return np.zeros((2,), np.float32)

    sent = []

    class Client:
        def report_global_step(self, step, **kw):
            sent.append(kw)

    ctx = WorkerContext(WorkerEnv(), Client())
    ctx.step_report_interval = 0.0
    assert trace.trace_ring.kind_seconds().get("input_wait", 0.0) == 0.0
    for _ in ElasticDataLoader(Slow(), batch_size=2, shuffle=False):
        stepper.step()
    waited = trace.trace_ring.kind_seconds()["input_wait"]
    assert waited >= 0.04
    assert trace.counters()["dataloader.next"][0] == 2
    rows = trace.step_rows()[-2:]
    assert sum(r["named_s"] for r in rows) == pytest.approx(waited, abs=1e-6)
    ctx.report_step(5, force=True, digest=stepper.trainer.step_digest)
    (kw,) = sent
    assert kw["digest"]["input_wait_s"] == pytest.approx(waited, abs=1e-5)
    assert "gc_pause_s" in kw["digest"]
    assert kw["digest"]["late_n"] >= 2


def test_a_collection_between_two_steps_is_gc(stepper, gc_hook):
    import gc

    from dlrover_tpu.observability import trace

    # something for the collector to walk: 10 ms and more of a pause
    gc.disable()
    try:
        ballast = [[i] for i in range(400000)]
        stepper.step()          # building it is a row of its own
        gen2_before = trace.counters().get("gc.gen2", (0, 0.0))
        gc.collect()
        row, late, n = _next_row(stepper)
    finally:
        gc.enable()
    del ballast
    assert row["gc_n"] == (0, 0, 1)
    assert row["gc_s"][2] > 0.010
    gen2 = trace.counters()["gc.gen2"]
    assert gen2[0] == gen2_before[0] + 1
    assert gen2[1] - gen2_before[1] == pytest.approx(row["gc_s"][2])
    assert n == 1 and late["named"] == 0.0
    assert late["gc"] == pytest.approx(row["gc_s"][2], rel=0.05)
    assert late["gc"] > late["blocked"]
    assert trace.trace_ring.events() == []      # the ring is off


class _StubAnnotation:
    """What ``jax.profiler.TraceAnnotation`` is to the spine."""

    on = False
    closed = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, dict(stats)

    @classmethod
    def is_enabled(cls):
        return cls.on

    def __enter__(self):
        return self

    def set_metadata(self, **stats):
        self.stats.update(stats)

    def __exit__(self, *exc):
        type(self).closed.append(self)


@pytest.fixture
def session(monkeypatch):
    """A profiler session that a test turns on and off."""
    from dlrover_tpu.observability import trace

    monkeypatch.setattr(_StubAnnotation, "on", False)
    monkeypatch.setattr(_StubAnnotation, "closed", [])
    monkeypatch.setattr(
        trace, "_profiler_annotation",
        lambda: _StubAnnotation if _StubAnnotation.on else None)
    return _StubAnnotation


def test_a_session_names_the_collection_and_the_step_carries_the_account(
        stepper, gc_hook, session):
    import gc

    from dlrover_tpu.observability import trace

    session.on = True
    stepper.step(2)
    gc.collect()
    stepper.step()
    session.on = False
    by_name = {}
    for a in session.closed:
        by_name.setdefault(a.name, []).append(a)
    (pause,) = by_name["dlrover/gc.gen2"]
    assert pause.stats["kind"] == "gc_pause" and "collected" in pause.stats
    steps = by_name["dlrover/train_step"]
    assert len(steps) == 3
    for a in steps:
        assert {"prev_interval_ms", "prev_gc_ms", "prev_runq_ms",
                "prev_cpu_ms", "prev_named_ms"} <= set(a.stats)
    row = trace.step_rows()[-1]
    assert row["traced"] == 1
    assert steps[-1].stats["prev_gc_ms"] == pytest.approx(
        sum(row["gc_s"]) * 1e3)
    assert steps[-1].stats["prev_interval_ms"] == pytest.approx(
        row["interval_s"] * 1e3)


def test_a_bare_sleep_is_blocked(stepper):
    time.sleep(0.05)
    row, late, n = _next_row(stepper)
    assert row["late_s"] >= 0.04 and n == 1
    # asleep, the thread neither ran nor waited for a CPU
    assert late["blocked"] >= 0.045
    assert late["named"] == 0.0 and late["cpu"] < late["blocked"]


def test_a_busy_loop_is_cpu(stepper):
    until = time.thread_time() + 0.05
    while time.thread_time() < until:
        pass
    row, late, n = _next_row(stepper)
    assert row["cpu_s"] >= 0.05
    assert row["late_s"] >= 0.04 and n == 1
    assert late["cpu"] >= 0.045 and late["named"] == 0.0


def test_an_interval_across_a_profiler_start_is_an_edge(stepper, session):
    stepper.trainer.step_digest.snapshot_and_reset()
    seen = []
    session.on = True
    time.sleep(0.1)             # start_trace's seconds
    seen.append(_next_row(stepper))
    seen.append(_next_row(stepper))
    session.on = False
    time.sleep(0.1)             # stop_trace's
    seen.append(_next_row(stepper))
    seen.append(_next_row(stepper))
    assert [(row["traced"], row["edge"]) for row, _, _ in seen] == [
        (0, 1), (1, 0), (0, 1), (0, 0)]
    for row, late, n in (seen[0], seen[2]):
        # an edge holds no step: it is late by no rule, folds into no
        # counter and stays out of the digest
        assert row["interval_s"] > 0.1 and row["late_s"] == 0.0
        assert n == 0 and not any(late.values())
    window = stepper.trainer.step_digest.snapshot_and_reset()
    assert window["count"] == 2 and window["max_s"] < 0.1


def test_the_table_of_rows_is_bounded(untraced):
    from dlrover_tpu.observability import trace

    account = trace.StepAccount()
    for step in range(trace.STEP_ROWS_CAP + 10):
        with trace.span("step", "train_step") as dispatched:
            account.close(step, dispatched)
    rows = trace.step_rows()
    assert len(rows) == trace.STEP_ROWS_CAP == 4096
    assert rows[-1]["step"] == trace.STEP_ROWS_CAP + 9
    assert rows[0]["step"] == 10
    untraced.clear()
    assert trace.step_rows() == []


def test_the_digest_holds_intervals_for_a_loop_that_fetches(stepper):
    """A loop that fetches every loss waits for the device between two
    entries: the digest's window is those intervals, not the dispatches,
    which return before the device has run the step."""
    from dlrover_tpu.observability import trace

    rows = trace.step_rows()
    window = stepper.trainer.step_digest.snapshot_and_reset()
    assert window["count"] == len(rows) == 12
    total = sum(r["interval_s"] for r in rows)
    assert window["mean_s"] == pytest.approx(total / 12, abs=2e-6)
    assert window["max_s"] == pytest.approx(
        max(r["interval_s"] for r in rows), abs=2e-6)
    assert trace.counters()["train_step"][1] < total
    assert window["late_s"] == pytest.approx(
        sum(r["late_s"] for r in rows), abs=2e-6)
    # a late step is in the window it ended in
    time.sleep(0.05)
    row, _, _ = _next_row(stepper)
    window = stepper.trainer.step_digest.snapshot_and_reset()
    assert (window["count"], window["late_n"]) == (1, 1)
    assert window["late_s"] == pytest.approx(row["late_s"], abs=2e-6)


def test_late_account_is_the_counters_rule():
    """Rows and a median row in, the late rows' excess by cause out, in
    the order named, gc, runq, cpu, blocked and never more than is
    left."""
    from dlrover_tpu.observability.trace import baseline, late_account

    def row(interval, named=0.0, gc=0.0, runq=0.0, cpu=0.002, edge=0):
        return {"interval_s": interval, "named_s": named,
                "gc_s": (0.0, 0.0, gc), "runq_s": runq, "cpu_s": cpu,
                "edge": edge}

    rows = [row(0.300)] * 9 + [
        row(0.305),                             # under 10 ms: on time
        row(0.400, named=0.060, gc=0.030),      # 60 named, 30 gc, 10 left
        row(0.350, gc=0.080),                   # gc capped at the 50
        row(0.330, runq=0.010, cpu=0.012),      # 10 runq, 10 cpu, 10 left
        row(9.000, edge=1),
    ]
    base = baseline(rows)
    assert base == {"interval_s": 0.300, "named_s": 0.0, "gc_s": 0.0,
                    "runq_s": 0.0, "cpu_s": 0.002, "cpu_tick_s": 0.0}
    got = late_account(rows, base)
    assert got["n"] == 3
    assert got["named"] == pytest.approx(0.060)
    assert got["gc"] == pytest.approx(0.030 + 0.050)
    assert got["runq"] == pytest.approx(0.010)
    assert got["cpu"] == pytest.approx(0.010)
    assert got["blocked"] == pytest.approx(0.010 + 0.010)
    # 2 % of a long step is more than 10 ms
    slow = [row(1.000)] * 5 + [row(1.015), row(1.030)]
    assert late_account(slow, baseline(slow))["n"] == 1
    # without schedstat runq is None and takes nothing
    blind = [dict(r, runq_s=None) for r in rows]
    got = late_account(blind, baseline(blind))
    assert got["runq"] == 0.0 and got["cpu"] == pytest.approx(0.010)
    assert got["blocked"] == pytest.approx(0.010 + 0.020)
    assert baseline([row(1.0, edge=1)]) is None
    # a thread clock that ticks by 10 ms (a sandboxed kernel's) reads 0
    # or 10 for a step that costs 3: one tick over the median is no
    # reading of more work, two are one tick's worth
    ticking = ([row(0.300, cpu=0.0)] * 6 + [row(0.300, cpu=0.010000000002)] * 3
               + [row(0.320, cpu=0.010), row(0.340, cpu=0.020)])
    base = baseline(ticking)
    assert base["cpu_s"] == 0.0
    assert base["cpu_tick_s"] == pytest.approx(0.010)
    got = late_account(ticking, base)
    assert got["n"] == 2 and got["cpu"] == pytest.approx(0.010)
    assert got["blocked"] == pytest.approx(0.020 + 0.030)


def test_step_account_without_schedstat(untraced, monkeypatch):
    from dlrover_tpu.observability import trace

    monkeypatch.setattr(trace.StepAccount, "_SCHEDSTAT",
                        "/proc/thread-self/no-such-file")
    account = trace.StepAccount()
    with trace.span("step", "train_step") as dispatched:
        assert account.close(1, dispatched) is None
        row = account.close(2, dispatched)
    assert row["runq_s"] is None and row["cpu_s"] >= 0
    assert account._fd == -1


# ---------------------------------------------------------------------------
# job-timeline merge CLI
# ---------------------------------------------------------------------------


def _write_rank_dump(tmp_path, rank: int, monkeypatch):
    from dlrover_tpu.observability.trace import TraceRing

    monkeypatch.setenv("DLROVER_TPU_TRACE", "1")
    r = TraceRing()
    m0 = time.monotonic()
    r.record("compile", "lower_step.w2", m0, 0.4, world=2)
    r.record("step", "train_step", m0 + 0.5, 0.1, host_step=1)
    r.record("ckpt_save", "save.blocking", m0 + 0.7, 0.02, tier="shm")
    return r.dump(
        str(tmp_path / f"trace-worker-n{rank}-p0-{rank}.json"),
        role="worker", node_id=rank, process_id=0,
    )


def test_job_timeline_merges_two_ranks_plus_master(tmp_path, monkeypatch):
    """Acceptance: the CLI merges >=2 ranks + master events into one
    valid chrome trace (per-source pids, process_name metadata, sorted
    timestamps, --check green)."""
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
    from dlrover_tpu.profiler import analysis

    for rank in range(2):
        _write_rank_dump(tmp_path, rank, monkeypatch)
    sm = SpeedMonitor()
    sm.mark_downtime_start(time.time() - 8)
    sm.mark_downtime_end(time.time() - 3)
    with open(tmp_path / "trace-master-9.json", "w") as f:
        json.dump({
            "traceEvents": sm.trace_events(),
            "dlrover": {"role": "master", "clock": "epoch_us"},
        }, f)
    out = tmp_path / "merged" / "job_timeline.json"
    os.makedirs(out.parent)
    rc = analysis.main([
        "job-timeline", str(tmp_path), "-o", str(out), "--check",
    ])
    assert rc == 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    x_pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert len(x_pids) == 3  # 2 ranks + master
    labels = {
        e["args"]["name"] for e in evs if e.get("ph") == "M"
    }
    assert {"worker-n0-p0", "worker-n1-p0", "master"} <= labels
    # one time axis: X timestamps are sorted and epoch-scale
    ts = [e["ts"] for e in evs if e.get("ph") == "X"]
    assert ts == sorted(ts)
    assert min(ts) > 1e15  # epoch us, not relative
    # the master's downtime bracket made it in
    downtime = [e for e in evs if e.get("cat") == "downtime"]
    assert len(downtime) == 1
    assert downtime[0]["dur"] == pytest.approx(5e6, rel=0.05)
    # sources table names every file
    assert len(doc["dlrover"]["merged_from"]) == 3


def test_job_timeline_check_fails_on_invalid_sources(tmp_path, monkeypatch):
    from dlrover_tpu.profiler import analysis

    _write_rank_dump(tmp_path, 0, monkeypatch)
    # partial overlap on one lane
    with open(tmp_path / "trace-worker-bad.json", "w") as f:
        json.dump({
            "traceEvents": [
                {"name": "a", "ph": "X", "ts": 0, "dur": 100, "pid": 1,
                 "tid": 1},
                {"name": "b", "ph": "X", "ts": 50, "dur": 100, "pid": 1,
                 "tid": 1},
            ],
            "dlrover": {"role": "worker", "clock": "epoch_us"},
        }, f)
    out = tmp_path / "out.json"
    rc = analysis.main(
        ["job-timeline", str(tmp_path / "trace-worker-bad.json"),
         str(tmp_path / "trace-worker-n0-p0-0.json"),
         "-o", str(out), "--check"]
    )
    assert rc == 1
    # without --check the merge still lands (debugging a broken dump)
    rc = analysis.main(
        ["job-timeline", str(tmp_path / "trace-worker-bad.json"),
         "-o", str(out)]
    )
    assert rc == 0
    # unparseable source
    with open(tmp_path / "garbage.json", "w") as f:
        f.write("{not json")
    rc = analysis.main(
        ["job-timeline", str(tmp_path / "garbage.json"), "-o", str(out),
         "--check"]
    )
    assert rc == 1


def test_job_timeline_rebases_clockless_interposer_dump(
    tmp_path, monkeypatch
):
    """An interposer /timeline dump (raw monotonic us, no dlrover
    metadata) re-bases onto the epoch sources' axis."""
    from dlrover_tpu.profiler import analysis

    _write_rank_dump(tmp_path, 0, monkeypatch)
    with open(tmp_path / "timeline-device.json", "w") as f:
        json.dump({"traceEvents": [
            {"name": "execute", "cat": "execute", "ph": "X", "ts": 1234,
             "dur": 500, "pid": 1, "tid": 1},
        ]}, f)
    out = tmp_path / "out.json"
    rc = analysis.main(
        ["job-timeline", str(tmp_path), "-o", str(out), "--check"]
    )
    assert rc == 0
    doc = json.load(open(out))
    src = {s["file"]: s for s in doc["dlrover"]["merged_from"]}
    assert src["timeline-device.json"]["clock"] == "rebased"
    execute = [e for e in doc["traceEvents"]
               if e.get("name") == "execute"][0]
    assert execute["ts"] > 1e15  # moved onto the epoch axis


# ---------------------------------------------------------------------------
# emitter integration: checkpoint + resize spans
# ---------------------------------------------------------------------------


def test_checkpoint_engine_emits_save_and_restore_spans(
    traced, tmp_path
):
    import numpy as np

    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    engine = CheckpointEngine(
        str(tmp_path / "ckpt"), job_name="obs-test", node_id=0,
        process_id=0, async_staging=False,
    )
    try:
        state = {"w": np.arange(16, dtype=np.float32)}
        engine.save_to_memory(3, state)
        restored = engine.load(target=state)
        assert restored is not None and restored[0] == 3
    finally:
        engine.close(unlink_shm=True)
    evs = traced.events()
    saves = [e for e in evs if e["name"] == "save.blocking"]
    restores = [e for e in evs if e["kind"] == "ckpt_restore"]
    assert len(saves) == 1 and saves[0]["kind"] == "ckpt_save"
    assert saves[0]["attrs"]["tier"] == "shm"
    assert saves[0]["attrs"]["step"] == 3
    assert saves[0]["attrs"]["mode"] == "sync"
    # the children decompose the pause: the kind's total is the pause
    assert traced.kind_seconds()["ckpt_save"] == pytest.approx(
        saves[0]["dur"])
    assert len(restores) == 1
    assert restores[0]["attrs"]["step"] == 3
    assert restores[0]["attrs"]["ok"] is True
    assert restores[0]["attrs"]["tier"] == "shm"


#: span -> the span it must hang under, per stage mode: the d2h copies
#: block the caller in host_gather and move to the staging thread in
#: device_snapshot
_SAVE_TREE = {
    "host_gather": {
        "save.join_previous": "save.blocking",
        "save.snapshot": "save.blocking",
        "d2h.issue": "save.blocking",
        "d2h.wait": "save.blocking",
        "stage.background": "save.blocking",
        "stage.wait_persist": "stage.background",
        "stage.shm_lock": "stage.background",
        "stage.shm_write": "stage.background",
    },
    "device_snapshot": {
        "save.join_previous": "save.blocking",
        "save.snapshot": "save.blocking",
        "stage.background": "save.blocking",
        "d2h.issue": "stage.background",
        "d2h.wait": "stage.background",
        "stage.wait_persist": "stage.background",
        "stage.shm_lock": "stage.background",
        "stage.shm_write": "stage.background",
    },
}


@pytest.fixture(scope="module", params=sorted(_SAVE_TREE))
def one_async_save(request, tmp_path_factory):
    """One async save of device arrays in the given stage mode, with the
    ring on; yields (mode, ring events by name, gauges, counters)."""
    import jax.numpy as jnp

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.observability import trace

    mode = request.param
    mp = pytest.MonkeyPatch()
    mp.setenv("DLROVER_TPU_TRACE", "1")
    if mode == "host_gather":
        # no room for a second copy: the d2h copies block the caller
        mp.setattr(CheckpointEngine, "_hbm_headroom",
                   staticmethod(lambda arrays: (8, 8)))
    trace.trace_ring.clear()
    engine = CheckpointEngine(
        str(tmp_path_factory.mktemp("ckpt")), job_name=f"obs-{mode}",
        node_id=0, process_id=0, async_staging=True,
    )
    try:
        state = {"w": jnp.arange(32.0).reshape(8, 4), "b": jnp.ones(4)}
        engine.save_to_memory(11, state)
        engine.wait_staging()
        assert engine.last_stage_mode == mode
        events = {e["name"]: e for e in trace.trace_ring.events()}
        yield mode, events, trace.gauges(), trace.counters()
    finally:
        engine.close(unlink_shm=True)
        trace.trace_ring.clear()
        mp.undo()


@pytest.mark.parametrize("name", sorted(_SAVE_TREE["host_gather"]))
def test_engine_save_children_hang_under_the_right_parent(
    one_async_save, name
):
    mode, events, _, counters = one_async_save
    parent = events[_SAVE_TREE[mode][name]]
    attrs = events[name]["attrs"]
    assert events[name]["kind"] == "ckpt_save"
    assert attrs["parent"] == parent["attrs"]["id"]
    # every span of one save carries the checkpoint's step, on either
    # thread
    assert attrs["step"] == 11
    assert counters[name][0] == 1
    on_caller = events[name]["tid"] == events["save.blocking"]["tid"]
    assert on_caller == (
        _SAVE_TREE[mode][name] == "save.blocking"
        and name != "stage.background"
    )


def test_engine_save_spans_say_what_happened(one_async_save):
    mode, events, gauges, _ = one_async_save
    snap = events["save.snapshot"]["attrs"]
    if mode == "host_gather":
        assert (snap["taken"], snap["why"]) == (0, "headroom")
        assert (snap["need_bytes"], snap["free_bytes"]) == (8, 8)
    else:
        assert snap["taken"] == 1 and "why" not in snap
    assert events["save.blocking"]["attrs"]["mode"] == mode
    assert events["save.join_previous"]["attrs"]["joined"] == 0
    assert events["d2h.issue"]["attrs"]["shards"] == 2
    staged = (32 + 4) * 4
    assert events["d2h.wait"]["attrs"]["bytes"] == staged
    assert events["stage.shm_write"]["attrs"]["bytes"] == staged
    assert gauges["ckpt.staged_bytes"] == staged


@pytest.mark.parametrize("enabled,state_kind,why", [
    (False, "device", "off"), (True, "host", "no_arrays"),
])
def test_engine_snapshot_says_why_not(traced, tmp_path, enabled,
                                      state_kind, why):
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    engine = CheckpointEngine(
        str(tmp_path / "ckpt"), job_name=f"obs-why-{why}", node_id=0,
        process_id=0, async_staging=True,
    )
    engine._device_snapshot_enabled = enabled
    state = {"w": jnp.ones(4) if state_kind == "device" else np.ones(4)}
    try:
        engine.save_to_memory(1, state)
        engine.wait_staging()
    finally:
        engine.close(unlink_shm=True)
    (snap,) = [e["attrs"] for e in traced.events()
               if e["name"] == "save.snapshot"]
    assert (snap["taken"], snap["why"]) == (0, why)
    assert "need_bytes" not in snap
    assert engine.last_stage_mode == "host_gather"
