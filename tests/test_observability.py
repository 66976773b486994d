"""The goodput observatory (docs/design/observability.md): trace spine,
per-rank step-time digests, straggler detection, lost-time attribution
and the job-timeline merge CLI."""

import json
import os
import time

import pytest

from dlrover_tpu.common import flags


@pytest.fixture
def traced(monkeypatch):
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
    with r.span("compile"):
        pass
    assert r.events() == []
    assert r.kind_seconds() == {}


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
def untraced(monkeypatch):
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
    assert untraced.events() == [] and untraced.kind_seconds() == {}
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
                                                spine):
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


def test_pytracer_mirrors_into_spine(traced, monkeypatch):
    """GC + user spans adopt the spine's span classification: gc -> gc_pause,
    dataloader -> input_wait, other cats -> host."""
    import gc

    from dlrover_tpu.profiler.py_tracing import PyTracer

    tracer = PyTracer()
    tracer.start()
    try:
        with tracer.span("dataloader.next", cat="dataloader"):
            pass
        with tracer.span("preprocess", cat="user"):
            pass
        gc.collect()
    finally:
        tracer.stop()
    kinds = {e["kind"] for e in traced.events()}
    assert "input_wait" in kinds
    assert "host" in kinds
    assert "gc_pause" in kinds
    # the tracer's own chrome ring still works (back-compat consumers)
    names = [e["name"] for e in tracer.events()]
    assert "dataloader.next" in names


def test_pytracer_capacity_and_enablement_from_flags(monkeypatch):
    from dlrover_tpu.profiler.py_tracing import PyTracer

    monkeypatch.setenv("DLROVER_TPU_PY_TRACING_CAP", "32")
    monkeypatch.delenv("DLROVER_TPU_TRACE", raising=False)
    tracer = PyTracer()
    assert tracer._cap == 32
    monkeypatch.setenv("DLROVER_TPU_PY_TRACING", "0")
    assert tracer.maybe_start() is False
    monkeypatch.setenv("DLROVER_TPU_PY_TRACING", "1")
    assert tracer.maybe_start() is True
    tracer.stop()
    # explicit constructor capacity still wins
    assert PyTracer(capacity=7)._cap == 7


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
    assert "dlrover_tpu_trace_seconds_total" not in text


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
    counters, gauges, the digest's window, kind seconds)."""
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
               trace.trace_ring.kind_seconds())
    finally:
        trace.trace_ring.clear()
        mp.undo()


def test_trainer_emits_step_compile_spans_and_digest(three_trainer_steps):
    events, counters, _, window, kind_seconds = three_trainer_steps
    kinds = [e["kind"] for e in events]
    # warm-compile default on: the AOT build recorded compile spans
    assert "compile" in kinds
    # steps after the first (build) call recorded step spans
    assert kinds.count("step") == 2
    steps = [e["attrs"] for e in events if e["name"] == "train_step"]
    assert [(a["step"], a["host_step"]) for a in steps] == [(2, 2), (3, 3)]
    # the digest folded the same steps
    assert window is not None and window["count"] == 2
    # ... from the span's own seconds (the digest rounds to microseconds)
    assert window["mean_s"] * 2 == pytest.approx(
        counters["train_step"][1], abs=2e-6)
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
    events, counters, _, _, _ = three_trainer_steps
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
    _, _, gauges, _, _ = three_trainer_steps
    assert gauges[gauge] > 0
    assert gauges["step.hbm_peak_bytes"] >= gauges[gauge]


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
