"""Diagnosis subsystem: inference chain, operators, manager, agent decision.

Mirrors the reference's canned-data approach
(``python/tests/test_inference_chain.py``, ``test_diagnosis_agent.py``).
"""

import json
import time

from dlrover_tpu.agent.diagnosis_agent import (
    DiagnosisAgent,
    WorkerAction,
    WorkerFailure,
)
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.constants import NodeStatus, NodeType
from dlrover_tpu.common.node import Node
from dlrover_tpu.diagnosis import actions
from dlrover_tpu.diagnosis.data import (
    DiagnosisDataManager,
    DiagnosisDataType,
    TpuMetricsRecord,
    TrainingLogRecord,
    parse_report,
)
from dlrover_tpu.diagnosis.inference import (
    Inference,
    InferenceAttribute,
    InferenceChain,
    InferenceDescription,
    InferenceName,
)
from dlrover_tpu.diagnosis.operators import (
    FAILURE_PROBLEM,
    HANG_PROBLEM,
    CheckFailureNodeOperator,
    CheckTrainingHangOperator,
    ResolveFailureNodeOperator,
    ResolveTrainingHangOperator,
    classify_log,
)
from dlrover_tpu.master.diagnosis.manager import DiagnosisManager
from dlrover_tpu.master.node.job_context import JobContext, get_job_context


def make_manager():
    from dlrover_tpu.master.job_container import JobContainer

    JobContainer.fresh()
    return DiagnosisManager(interval_secs=3600)


def test_classify_log():
    assert classify_log("") is None
    assert classify_log("RESOURCE_EXHAUSTED: HBM OOM") == "retryable"
    assert classify_log("worker preempted, SIGTERM") == "hardware"
    assert classify_log("hbm ecc error on chip 3") == "hardware"
    assert (
        classify_log("Traceback (most recent call last):\n  ValueError") == "fatal"
    )
    assert classify_log("all good, step 100 loss 2.3") is None
    # JAX's coordination-service peer-death text mentions "preempted" but the
    # local host is healthy: it must classify retryable, not hardware.
    peer_death = (
        "Terminating process because the JAX distributed service detected "
        "fatal errors. This most likely indicates that another task died; "
        "Either the leader task was preempted/died/restarted unexpectedly"
    )
    assert classify_log(peer_death) == "retryable"
    # ...but a genuine local preemption notice must still read as hardware
    assert (
        classify_log("SIGTERM received, reporting preemption notice")
        == "hardware"
    )
    # and a real hardware fault alongside routine teardown chatter stays
    # hardware (peer patterns are message-specific, not component names)
    assert (
        classify_log(
            "hbm ecc uncorrectable error\n"
            "coordination_service_agent.cc: agent shutting down"
        )
        == "hardware"
    )


def test_data_manager_window_and_latest():
    dm = DiagnosisDataManager(expire_time_secs=60)
    dm.store_data(TrainingLogRecord(node_id=0, logs=["a"]))
    dm.store_data(TrainingLogRecord(node_id=0, logs=["b"]))
    dm.store_data(TrainingLogRecord(node_id=1, logs=["c"]))
    assert len(dm.get_data(DiagnosisDataType.TRAINING_LOG)) == 3
    latest = dm.latest_per_node(DiagnosisDataType.TRAINING_LOG)
    assert latest[0].data_content == "b"
    assert latest[1].data_content == "c"
    # expiry
    old = TrainingLogRecord(node_id=2, logs=["old"])
    old.timestamp = time.time() - 120
    dm.store_data(old)
    assert 2 not in dm.latest_per_node(DiagnosisDataType.TRAINING_LOG)


def test_hang_operator_confirms_and_denies():
    dm = DiagnosisDataManager()
    op = CheckTrainingHangOperator(dm)
    # no data -> not hang
    (fact,) = op.infer([HANG_PROBLEM])
    assert fact.attribution == InferenceAttribute.NOT
    dm.store_data(TpuMetricsRecord(node_id=0, hang=True))
    dm.store_data(TpuMetricsRecord(node_id=1, hang=True))
    (fact,) = op.infer([HANG_PROBLEM])
    assert fact.attribution == InferenceAttribute.IS
    # one healthy node vetoes the hang verdict
    dm.store_data(TpuMetricsRecord(node_id=1, hang=False))
    (fact,) = op.infer([HANG_PROBLEM])
    assert fact.attribution == InferenceAttribute.NOT


def test_handled_warnings_are_not_failures():
    """Seen on the v5e: the checkpoint engine's handled warning about
    HBM headroom matched OOM ignoring case, and the master restarted a
    healthy worker every cycle."""
    assert classify_log(
        "insufficient HBM headroom for a device-side checkpoint "
        "snapshot; blocking for the d2h transfer instead"
    ) is None
    assert classify_log("worker hit OOM in step 3") == "retryable"
    assert classify_log("oom-killer: killed process 12") == "retryable"


def test_a_log_report_is_judged_once():
    dm = DiagnosisDataManager()
    op = CheckFailureNodeOperator(dm)
    dm.store_data(
        TrainingLogRecord(node_id=3, logs=["XlaRuntimeError: RESOURCE_EXHAUSTED"])
    )
    (fact,) = op.infer([FAILURE_PROBLEM])
    assert fact.attribution == InferenceAttribute.IS
    # the same report, still the latest on the next cycle: no new verdict
    (fact,) = op.infer([FAILURE_PROBLEM])
    assert fact.attribution == InferenceAttribute.NOT
    # the restarted worker fails the same way: a new report, a new verdict
    dm.store_data(
        TrainingLogRecord(node_id=3, logs=["XlaRuntimeError: RESOURCE_EXHAUSTED"],
                          timestamp=time.time() + 1)
    )
    (fact,) = op.infer([FAILURE_PROBLEM])
    assert fact.attribution == InferenceAttribute.IS


def test_agent_log_tail_starts_at_this_incarnation(tmp_path):
    """The worker log is appended to across restarts; what the stopped
    worker wrote (the TPU runtime prints "SIGTERM received") is not the
    new worker's failure signature."""
    from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerProc

    log = tmp_path / "worker-0-restart0.log"
    log.write_text("*** SIGTERM received by PID 1229 ***\n")
    start = log.stat().st_size
    with open(log, "a") as f:
        f.write("step 7 loss 12.5\n")
    worker = WorkerProc(0, 0, None, str(log), start)
    tail = ElasticAgent._tail_log(None, worker)
    assert tail == "step 7 loss 12.5\n"
    assert classify_log(tail) is None
    assert ElasticAgent._tail_log(None, worker, max_bytes=5) == "12.5\n"


def test_full_chain_failure_to_action():
    dm = DiagnosisDataManager()
    dm.store_data(
        TrainingLogRecord(node_id=3, logs=["XlaRuntimeError: RESOURCE_EXHAUSTED"])
    )
    ops = [
        CheckTrainingHangOperator(dm),
        CheckFailureNodeOperator(dm),
        ResolveTrainingHangOperator(dm),
        ResolveFailureNodeOperator(dm),
    ]
    facts = InferenceChain([HANG_PROBLEM, FAILURE_PROBLEM], ops).infer()
    action_facts = [f for f in facts if f.name == InferenceName.ACTION]
    assert len(action_facts) == 1
    assert action_facts[0].description == "restart"
    assert action_facts[0].config()["node_id"] == "3"


def test_manager_enqueues_actions_for_heartbeat():
    mgr = make_manager()
    ctx = get_job_context()
    node = Node(NodeType.WORKER, 5, status=NodeStatus.RUNNING)
    ctx.update_node(node)
    mgr.collect_diagnosis_data(
        msg.DiagnosisReportData(
            data_cls="TrainingLogRecord",
            data_content=TrainingLogRecord(node_id=5, logs=["chip failure on host"]).to_json(),
            node_id=5,
        )
    )
    facts = mgr.diagnose_once()
    assert any(f.description == "relaunch" for f in facts)
    action = ctx.next_action(5)
    assert action is not None
    assert action.action_cls == actions.ActionCls.RELAUNCH_WORKER


def test_manager_hang_restarts_all():
    mgr = make_manager()
    ctx = get_job_context()
    for i in range(2):
        ctx.update_node(Node(NodeType.WORKER, i, status=NodeStatus.RUNNING))
        mgr.collect_diagnosis_data(
            msg.DiagnosisReportData(
                data_cls="TpuMetricsRecord",
                data_content=json.dumps({"hang": True}),
                node_id=i,
            )
        )
    # phase 1: the master orchestrates a synchronized all-rank dump
    mgr.diagnose_once()
    for i in range(2):
        action = ctx.next_action(i)
        assert action is not None
        assert action.action_cls == actions.ActionCls.COLLECT_DUMP
    # agents ship their dumps back
    for i in range(2):
        mgr.collect_diagnosis_data(
            msg.DiagnosisReportData(
                data_cls="HangDumpRecord",
                data_content=json.dumps({
                    "reason": "master_request",
                    "stacks": {str(100 + i): (
                        'Current thread 0x1 (most recent call first):\n'
                        '  File "c.py", line 1 in psum\n'
                    )},
                    "pending": {},
                }),
                node_id=i,
            )
        )
    # phase 2: every reporting node's dump arrived -> restart with stacks
    mgr.diagnose_once()
    for i in range(2):
        action = ctx.next_action(i)
        assert action is not None and action.action_cls == actions.ActionCls.RESTART_WORKER
        assert "psum" in action.action_content  # all-rank stacks attached


def test_parse_report_types():
    rec = parse_report("TpuMetricsRecord", json.dumps({"hang": True}), node_id=7)
    assert isinstance(rec, TpuMetricsRecord)
    assert rec.node_id == 7
    rec2 = parse_report("Unknown", "free text", node_id=1)
    assert rec2.data_content == "free text"


def test_agent_failure_decision():
    agent = DiagnosisAgent()
    # retryable with budget -> restart
    f = WorkerFailure(0, restart_count=0, max_restarts=3, log_tail="OOM on step")
    assert agent.diagnose_training_failure(f) == WorkerAction.RESTART_WORKER
    # hardware signature -> relaunch even with budget
    f = WorkerFailure(0, 0, 3, log_tail="ICI link down; DATA_LOSS")
    assert agent.diagnose_training_failure(f) == WorkerAction.RELAUNCH_WORKER
    # budget exhausted -> relaunch
    f = WorkerFailure(0, 3, 3, log_tail="Traceback (most recent call last)")
    assert agent.diagnose_training_failure(f) == WorkerAction.RELAUNCH_WORKER
    # fatal with budget -> restart (transient corruption retried)
    f = WorkerFailure(0, 1, 3, log_tail="Traceback (most recent call last)")
    assert agent.diagnose_training_failure(f) == WorkerAction.RESTART_WORKER


def test_action_expiry():
    ctx = JobContext()
    a = actions.restart_worker(1, expiry=-5)  # already expired
    a.expired_ts = time.time() - 1
    ctx.enqueue_action(a)
    assert ctx.next_action(1) is None
    ctx.enqueue_action(actions.restart_worker(1, reason="x"))
    got = ctx.next_action(1)
    assert got is not None and got.action_cls == "RestartWorker"


def test_hang_resolver_summarizes_hang_dumps():
    from dlrover_tpu.diagnosis.data import HangDumpRecord

    stack = (
        'Thread 0x1 (most recent call first):\n'
        '  File "/app/dlrover_tpu/ops/ring_attention.py", line 88 in _ring_step\n'
        '  File "/app/train.py", line 80 in main\n'
    )
    bundle = {
        "reason": "tpu_timer_hang",
        "stacks": {"101": stack, "102": stack},
        "pending": {"9200": {"hang": True, "pending": [
            {"name": "jit_train_step", "age_us": 9_000_000}]}},
    }
    rec = parse_report("HangDumpRecord", json.dumps(bundle), node_id=0)
    assert isinstance(rec, HangDumpRecord)
    assert rec.data_type == DiagnosisDataType.HANG_DUMP

    dm = DiagnosisDataManager()
    dm.store_data(rec)
    op = ResolveTrainingHangOperator(dm)
    (first,) = op.infer([])
    assert first.description == "collect_dumps"  # phase 1: orchestrate
    (fact,) = op.infer([])  # dump already present and fresh -> resolve
    cfg = fact.config()
    assert fact.description == "restart_all"
    assert cfg["stuck_at"].startswith("_ring_step")
    assert cfg["pending_programs"] == "jit_train_step"
    assert cfg["hang_dump_hosts"] == "1"


def test_hang_resolver_without_dumps_keeps_plain_action():
    dm = DiagnosisDataManager()
    op = ResolveTrainingHangOperator(dm, dump_wait_secs=0.0)
    (first,) = op.infer([])
    assert first.description == "collect_dumps"
    (fact,) = op.infer([])  # wait budget 0 and nothing arrived -> restart
    assert fact.description == "restart_all"
    assert "stuck_at" not in fact.config()


def test_cross_node_dump_orchestration_e2e(tmp_path):
    """VERDICT r3 #8 end to end over the real RPC stack: two hosts with
    genuinely wedged worker processes report hang metrics; the master
    broadcasts CollectHangDump on heartbeats; each agent SIGUSR2-dumps its
    real workers and ships the bundle; the master's diagnosis record then
    contains BOTH ranks' stacks and the restart names the wedge frame."""
    import os
    import subprocess
    import sys
    import textwrap
    import time as _time

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from dlrover_tpu.agent.diagnosis_agent import DiagnosisAgent
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.local_master import start_local_master
    from dlrover_tpu.profiler.hang_dump import HangDumper

    master = start_local_master(node_num=2)
    workers = []
    try:
        agents = {}
        for node_id in range(2):
            stack_dir = str(tmp_path / f"node{node_id}")
            prog = textwrap.dedent(f"""
                import sys, time
                sys.path.insert(0, {repr(str(REPO))})
                from dlrover_tpu.profiler.hang_dump import install_stack_dump_handler
                install_stack_dump_handler({stack_dir!r})
                def wedged_collective():
                    time.sleep(120)
                print('READY', flush=True)
                wedged_collective()
            """)
            p = subprocess.Popen(
                [sys.executable, "-c", prog], stdout=subprocess.PIPE,
                text=True,
            )
            assert p.stdout.readline().strip() == "READY"
            workers.append(p)
            client = MasterClient(
                f"127.0.0.1:{master.port}", node_id=node_id
            )
            agent = DiagnosisAgent(client=client, node_id=node_id)
            agent.set_hang_dumper(HangDumper(
                stack_dir, worker_pids=[p.pid], settle_secs=1.0,
            ))
            agent.set_metrics_source(lambda: {"hang": True, "mfu": 0.0})
            agents[node_id] = (agent, client)
            # ship hang metrics (would normally come from the interposer);
            # the dumper's cooldown blocks the LOCAL auto-dump path so the
            # dumps in this test can only come from the master's broadcast
            agents[node_id][0]._hang_dumper._last_dump = _time.time()
            client.report_diagnosis_data(
                "TpuMetricsRecord",
                json.dumps({"hang": True, "mfu": 0.05 + 0.1 * node_id}),
            )

        # agents' heartbeat loops register the nodes with the master
        for _, client in agents.values():
            client.report_heartbeat()

        # phase 1: hang confirmed -> master broadcasts the dump request
        master.diagnosis_manager.diagnose_once()
        for node_id, (agent, client) in agents.items():
            actions_out = client.report_heartbeat()
            kinds = [a.action_cls for a in actions_out]
            assert "CollectHangDump" in kinds, kinds
            # the elastic agent would dispatch this; call the same handler
            agent.collect_and_ship_dump(reason="master_request")

        # both ranks' dumps are now in the master's diagnosis record
        dm = master.diagnosis_manager.data_manager
        from dlrover_tpu.diagnosis.data import DiagnosisDataType

        dumps = dm.latest_per_node(DiagnosisDataType.HANG_DUMP)
        assert set(dumps) == {0, 1}, dumps.keys()
        for rec in dumps.values():
            assert any(
                "wedged_collective" in text for text in rec.stacks.values()
            ), rec.stacks

        # phase 2: resolution restarts all with the wedge frame + ranking
        master.diagnosis_manager.diagnose_once()
        from dlrover_tpu.master.node.job_context import get_job_context

        restart_seen = 0
        for node_id in range(2):
            while True:
                action = get_job_context().next_action(node_id)
                if action is None:
                    break
                if action.action_cls == "RestartWorker":
                    restart_seen += 1
                    assert "wedged_collective" in action.action_content
        assert restart_seen == 2
    finally:
        for p in workers:
            p.kill()
        master.stop()


def test_hang_resolver_new_episode_discards_stale_dumps():
    """Code-review r4: a hang that clears without a restart must not leak
    its dumps into a later, unrelated hang — the resolver re-orchestrates
    collection for the new episode."""
    from dlrover_tpu.diagnosis.data import HangDumpRecord

    dm = DiagnosisDataManager()
    op = ResolveTrainingHangOperator(dm, dump_wait_secs=0.0)
    (first,) = op.infer([])
    assert first.description == "collect_dumps"
    # stale dump from this (soon aborted) episode
    old = HangDumpRecord(stacks={"1": (
        'Current thread 0x1 (most recent call first):\n'
        '  File "old.py", line 1 in old_wedge\n')})
    old.node_id = 0
    old.timestamp = time.time() - 500.0
    dm.store_data(old)

    # episode clears: resolver silent for > 2*wait+60 seconds
    op._last_hang_seen = time.time() - 200.0
    op._dump_requested_at = time.time() - 500.0

    # new hang: phase 1 again (no stale summarize)
    (fact,) = op.infer([])
    assert fact.description == "collect_dumps"
    # wait budget 0, nothing fresh arrived -> restart WITHOUT old frames
    (fact,) = op.infer([])
    assert fact.description == "restart_all"
    assert "old_wedge" not in fact.config().get("stuck_at", "")
