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


@pytest.mark.usefixtures("traced")
def test_trainer_emits_step_compile_spans_and_digest(monkeypatch):
    import jax

    from dlrover_tpu.models import llama
    from dlrover_tpu.observability.trace import trace_ring
    from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
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
    kinds = [e["kind"] for e in trace_ring.events()]
    # warm-compile default on: the AOT build recorded a compile span
    assert "compile" in kinds
    # steps after the first (build) call recorded step spans
    assert kinds.count("step") == 2
    # the digest folded the same steps
    w = trainer.step_digest.snapshot_and_reset()
    assert w is not None and w["count"] == 2


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
    saves = [e for e in evs if e["kind"] == "ckpt_save"]
    restores = [e for e in evs if e["kind"] == "ckpt_restore"]
    assert saves and saves[0]["attrs"]["tier"] == "shm"
    assert saves[0]["attrs"]["step"] == 3
    assert len(restores) == 1
    assert restores[0]["attrs"]["step"] == 3
    assert restores[0]["attrs"]["ok"] is True
    assert restores[0]["attrs"]["tier"] == "shm"
