"""Unified retry/backoff policy + control-plane backpressure
(rpc/policy.py, rpc/transport.py RequestGate, agent/reporter.py)."""

import random
import threading
import time

import pytest

from dlrover_tpu.common import messages as msg
from dlrover_tpu.rpc import policy as rpc_policy
from dlrover_tpu.rpc.policy import (
    AdaptiveInterval,
    BackoffPolicy,
    OverloadedError,
    classify,
    poll_intervals,
)
from dlrover_tpu.rpc.transport import RequestGate, RpcClient, RpcServer


# -- policy units -----------------------------------------------------------


def test_backoff_delays_jitter_and_growth():
    pol = BackoffPolicy(
        base_s=0.1, multiplier=2.0, max_s=1.0, jitter=0.2,
        budget_s=100.0, max_attempts=6,
    )
    delays = list(pol.delays(random.Random(7)))
    assert len(delays) == 5  # one fewer sleep than attempts
    # each delay within +/- jitter of the unjittered schedule (capped)
    expect = [0.1, 0.2, 0.4, 0.8, 1.0]
    for d, e in zip(delays, expect):
        assert 0.8 * e <= d <= 1.2 * e, (d, e)
    # deterministic under the same seed
    assert delays == list(pol.delays(random.Random(7)))


def test_backoff_budget_bounds_total_sleep():
    pol = BackoffPolicy(
        base_s=1.0, multiplier=2.0, max_s=8.0, jitter=0.0,
        budget_s=5.0, max_attempts=50,
    )
    delays = list(pol.delays())
    assert sum(delays) <= 5.0
    assert delays == [1.0, 2.0]  # 1+2+4 would blow the budget


def test_poll_intervals_grow_jittered_and_never_exhaust():
    it = poll_intervals(rng=random.Random(3))
    first = [next(it) for _ in range(40)]
    pol = rpc_policy.POLL
    assert all(d <= pol.max_s * (1 + pol.jitter) for d in first)
    # grows from the fast start toward the cap
    assert first[0] < 0.2
    assert sum(first[-5:]) / 5 > 1.0
    # two pollers with different seeds de-phase
    other = [next(poll_intervals(rng=random.Random(4))) for _ in range(40)]
    assert first != other


def test_classify_error_classes():
    class FakeCode:
        def __init__(self, name):
            self.name = name

    class FakeRpcError(Exception):
        def __init__(self, name):
            self._code = FakeCode(name)

        def code(self):
            return self._code

    assert classify(FakeRpcError("UNAVAILABLE")) == rpc_policy.UNAVAILABLE
    assert classify(FakeRpcError("DEADLINE_EXCEEDED")) == rpc_policy.DEADLINE
    assert classify(FakeRpcError("RESOURCE_EXHAUSTED")) == rpc_policy.OVERLOADED
    assert classify(FakeRpcError("INVALID_ARGUMENT")) == rpc_policy.APPLICATION
    assert classify(ConnectionError()) == rpc_policy.UNAVAILABLE
    assert classify(OverloadedError()) == rpc_policy.OVERLOADED
    assert classify(ValueError()) == rpc_policy.APPLICATION


def test_adaptive_interval_aimd_and_liveness_ceiling():
    ai = AdaptiveInterval(1.0, max_s=64.0, factor=2.0, recovery=0.5)
    assert ai.current_s == 1.0
    ai.widen()
    ai.widen()
    assert ai.current_s == 4.0
    # server hint jumps straight there
    ai.widen(hint_s=10.0)
    assert ai.current_s == 10.0
    # the liveness ceiling bounds widening even below max_s: honoring
    # backpressure must never walk the client into heartbeat eviction
    ai.widen(ceiling_s=12.0)
    assert ai.current_s == 12.0
    ai.widen(ceiling_s=12.0)
    assert ai.current_s == 12.0
    # a ceiling BELOW the current cadence freezes widening — it must
    # never SHRINK the interval (reporting faster under overload would
    # amplify it)
    ai.widen(ceiling_s=5.0)
    assert ai.current_s == 12.0
    # recovery decays back toward base, never below
    for _ in range(20):
        ai.ok()
    assert ai.current_s == 1.0
    assert ai.widen_events == 6


# -- admission gate ---------------------------------------------------------


def test_request_gate_caps_and_counters():
    gate = RequestGate(report_cap=2, get_cap=3)
    assert gate.try_enter("report")
    assert gate.try_enter("report")
    assert not gate.try_enter("report")  # at cap -> shed
    assert gate.try_enter("get")  # gets admit above the report cap
    assert not gate.try_enter("get")
    s = gate.stats()
    assert s["inflight"] == 3 and s["peak_inflight"] == 3
    assert s["served"] == {"get": 1, "report": 2}
    assert s["rejected"] == {"get": 1, "report": 1}
    gate.leave("report")
    gate.leave("report")
    gate.leave("get")
    assert gate.depth == 0
    lines = "\n".join(gate.prometheus_lines())
    assert 'outcome="rejected"} 1' in lines
    assert "dlrover_tpu_master_rpc_inflight 0" in lines


def test_request_gate_gets_cannot_starve_reports():
    """Reports compete only with other reports: a get-heavy episode
    (fleet-wide world polling during a re-rendezvous) must not shed
    100% of heartbeats/failure reports."""
    gate = RequestGate(report_cap=2, get_cap=4)
    for _ in range(4):
        assert gate.try_enter("get")
    assert not gate.try_enter("get")  # total budget exhausted
    # report slots stay reserved regardless of get pressure
    assert gate.try_enter("report")
    assert gate.try_enter("report")
    assert not gate.try_enter("report")  # its own cap, not the gets'
    for _ in range(4):
        gate.leave("get")
    gate.leave("report")
    gate.leave("report")
    assert gate.depth == 0


def test_rpc_server_clamps_operator_cap_below_thread_count():
    """A configured cap at/above the thread pool could never reject
    (in-handler depth is bounded by the threads) — it must clamp, not
    silently disable shedding."""
    from dlrover_tpu.common import flags

    servicer = _BlockingServicer()
    with flags.RPC_INFLIGHT_CAP.scoped("64"):
        server = RpcServer(servicer, port=0, max_workers=32)
    try:
        assert server.gate.report_cap <= 32 - 8
        assert server.gate.get_cap <= 32 - 2
    finally:
        server.stop(grace=0)


def test_gate_overload_reply_carries_liveness_ceiling():
    gate = RequestGate(report_cap=1)
    gate.liveness_ceiling_s = 30.0
    reply = gate.overload_reply("report")
    assert isinstance(reply, msg.OverloadedResponse)
    assert reply.max_interval_s == 30.0
    assert reply.retry_after_s > 0


# -- server sheds, client honors (real gRPC round trip) ---------------------


class _BlockingServicer:
    """report blocks until released; get answers immediately."""

    def __init__(self):
        self.release = threading.Event()

    def get(self, request, context=None):
        return msg.SimpleResponse()

    def report(self, request, context=None):
        self.release.wait(timeout=10)
        return msg.SimpleResponse()


def test_rpc_server_sheds_with_explicit_overloaded_reply():
    servicer = _BlockingServicer()
    gate = RequestGate(report_cap=1, get_cap=8)
    gate.liveness_ceiling_s = 45.0
    server = RpcServer(servicer, port=0, max_workers=8, gate=gate)
    server.start()
    client = RpcClient(f"127.0.0.1:{server.port}")
    try:
        t = threading.Thread(
            target=lambda: client.report(msg.HeartbeatReport(node_id=1)),
            daemon=True,
        )
        t.start()
        deadline = time.time() + 5
        while gate.depth == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert gate.depth == 1
        # second report hits the cap -> explicit Overloaded, not a queue
        t0 = time.time()
        with pytest.raises(OverloadedError) as exc:
            client.report(
                msg.HeartbeatReport(node_id=2), on_overload="raise"
            )
        assert time.time() - t0 < 2.0  # shed fast, never queued
        assert exc.value.max_interval_s == 45.0
        # gets stay admitted under the higher watermark
        resp = client.get(msg.NetworkReadyRequest())
        assert isinstance(resp, msg.SimpleResponse)
        assert gate.stats()["rejected"]["report"] >= 1
        servicer.release.set()
        t.join(timeout=5)
    finally:
        servicer.release.set()
        client.close()
        server.stop(grace=0.2)


def test_status_reporter_honors_overload_by_widening():
    from dlrover_tpu.agent.reporter import StatusReporter

    class ShedClient:
        def __init__(self):
            self.calls = 0

        def report_worker_status(self, **kw):
            self.calls += 1
            if self.calls <= 2:
                raise OverloadedError(
                    retry_after_s=2.0, queue_depth=9, max_interval_s=40.0
                )
            return msg.WorkerReportResponse(
                actions=[msg.DiagnosisAction(action_cls="RestartWorker")]
            )

    seen = []
    reporter = StatusReporter(
        ShedClient(), interval_s=10.0, on_actions=seen.extend
    )
    assert not reporter.report_once()
    assert reporter.current_interval_s == 20.0  # widened, not retried
    assert not reporter.report_once()
    assert reporter.current_interval_s == 40.0  # capped by the ceiling
    assert reporter.report_once()  # served: decays + actions delivered
    assert reporter.current_interval_s < 40.0
    assert reporter.reports_shed == 2 and reporter.reports_sent == 1
    assert [a.action_cls for a in seen] == ["RestartWorker"]


# -- master /metrics --------------------------------------------------------


def test_master_metrics_endpoint_exposes_gate_and_goodput():
    from urllib.request import urlopen

    from dlrover_tpu.common import flags
    from dlrover_tpu.master.local_master import start_local_master

    with flags.MASTER_METRICS_PORT.scoped("0"):
        master = start_local_master(node_num=1)
    try:
        assert master._metrics_server is not None
        port = master._metrics_server.port
        client = RpcClient(f"127.0.0.1:{master.port}")
        client.report(msg.HeartbeatReport(node_id=0))
        client.close()
        body = urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "dlrover_tpu_master_rpc_inflight 0" in body
        assert 'method="report",outcome="served"} 1' in body
        assert "dlrover_tpu_master_goodput" in body
        assert "dlrover_tpu_master_running_workers" in body
    finally:
        master.stop()


# -- folded WorkerReport through the real wire ------------------------------


def test_worker_report_folds_heartbeat_digest_resource(master_client):
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.master.node.job_context import get_job_context

    resp = master_client.report_worker_status(
        step=7,
        digest={"count": 5, "mean_s": 1.0, "p50_s": 1.0, "p95_s": 1.1,
                "max_s": 1.2, "input_wait_s": 0.05},
        cpu_percent=0.4,
        memory_mb=2048.0,
        tpu_duty_cycle=0.8,
    )
    assert isinstance(resp, msg.WorkerReportResponse)
    node = get_job_context().get_node(NodeType.WORKER, 0)
    assert node is not None and node.heartbeat_time > 0
    assert node.used_resource.memory_mb == 2048.0


def test_worker_report_heartbeat_only_does_not_close_downtime(local_master):
    from dlrover_tpu.agent.master_client import MasterClient

    client = MasterClient(f"127.0.0.1:{local_master.port}", node_id=0)
    sm = local_master.speed_monitor
    try:
        client.report_worker_status(step=3, digest={
            "count": 3, "mean_s": 1.0, "p50_s": 1.0, "p95_s": 1.0,
            "max_s": 1.0,
        })
        client.report_failure("preempted", timestamp=time.time())
        assert sm._downtime_start > 0
        # a stalled worker's heartbeat (no step, no digest) must NOT
        # close the bracket...
        client.report_worker_status()
        assert sm._downtime_start > 0
        # ...but a report carrying actual progress does
        client.report_worker_status(step=4, digest={
            "count": 1, "mean_s": 1.0, "p50_s": 1.0, "p95_s": 1.0,
            "max_s": 1.0,
        })
        assert sm._downtime_start == 0.0
        assert sm.total_downtime() > 0.0
    finally:
        client.close()
