"""Chaos / goodput end-to-end scenarios (reference
``docs/tech_report/fault_tolerance_exps.md:23-396`` — which was a manual
walkthrough; these are automated):

1. **Faulty node excluded via the ``--network-check`` CLI path**: two
   agents run the real pre-training health check; one has an injected
   chip failure; the master's 2-round fault localization names it, the
   faulty agent exits for relaunch, and the healthy node trains alone on
   the elastic (min 1) world.
2. **Kill worker mid-training → goodput ledger**: the 2-node crash
   scenario asserts the SpeedMonitor's downtime ledger actually moved —
   downtime recorded at the failure report, ended at the next step
   report, goodput computed in (0, 1].
"""

import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(REPO, "tests", "e2e", "train_toy.py")
NOOP = os.path.join(REPO, "tests", "e2e", "train_noop.py")


def _agent_cmd(addr, job_name, node_id, extra=None, nnodes="1:2",
               script=TOY):
    return [
        sys.executable, "-m", "dlrover_tpu.run.elastic_run",
        f"--master_addr={addr}",
        f"--nnodes={nnodes}",
        "--accelerator=cpu",
        f"--job_name={job_name}",
        "--monitor_interval=0.5",
        "--max_restarts=2",
        "--rdzv_join_timeout=120",
        f"--node_id={node_id}",
    ] + (extra or []) + [script]


def _env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("DLROVER_TPU_TEST_CRASH_STEP", None)
    env.pop("DLROVER_TPU_MOCK_ERR_NODE", None)
    if extra:
        env.update(extra)
    return env


def _agent_logs(job_name, node_id=0):
    log_dir = f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job_name}/node-{node_id}"
    out = ""
    if os.path.isdir(log_dir):
        for f in sorted(os.listdir(log_dir)):
            if os.path.isdir(os.path.join(log_dir, f)):
                continue
            out += open(os.path.join(log_dir, f), errors="replace").read()
    return out


@pytest.mark.slow
def test_network_check_cli_excludes_faulty_node():
    """Weak #6 closure: ``--network-check`` end to end with an injected
    faulty node. 4 nodes, because 2-round fault localization works by
    re-pairing: a faulty node's round-1 partner must succeed with a
    different partner in round 2 for the intersection to isolate the
    fault (with 2 nodes both rounds pair the same two and neither can be
    blamed — also true of the reference's scheme). The faulty agent exits
    for relaunch; the 3 healthy nodes pass and bring up the elastic
    world without it."""
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(
        node_num=4, min_node_num=1, rdzv_waiting_timeout=8
    )
    faulty = 3
    try:
        addr = f"127.0.0.1:{master.port}"
        job = "chaos-netcheck"
        # shrink the doomed collectives: partners of the faulty node fail
        # their round after this init timeout instead of 120s
        common_env = {"DLROVER_TPU_DIST_INIT_TIMEOUT": "20"}
        procs = {}
        for node_id in range(4):
            env = dict(common_env)
            if node_id == faulty:
                env["DLROVER_TPU_MOCK_ERR_NODE"] = str(faulty)
            procs[node_id] = subprocess.Popen(
                _agent_cmd(addr, job, node_id, ["--network-check"],
                           nnodes="1:4", script=NOOP),
                env=_env(env), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        outs = {i: p.communicate(timeout=420)[0] for i, p in procs.items()}

        # the injected-fault node was localized and exited for relaunch
        assert procs[faulty].returncode != 0, outs[faulty][-3000:]
        assert "failed network check" in outs[faulty], outs[faulty][-3000:]
        from dlrover_tpu.common.constants import RendezvousName

        check_mgr = master.rdzv_managers[RendezvousName.NETWORK_CHECK]
        assert check_mgr._fault_nodes == [faulty], (
            check_mgr._fault_nodes, outs[faulty][-1500:],
        )
        # every healthy node passed the check and brought up the world
        for node_id in range(3):
            logs = _agent_logs(job, node_id)
            assert procs[node_id].returncode == 0, (
                f"agent{node_id}:\n{outs[node_id][-3000:]}\n"
                f"workers:\n{logs[-2000:]}"
            )
            assert "[noop] done" in logs, logs[-1500:]
    finally:
        master.stop()


@pytest.mark.slow
def test_network_check_cli_excludes_straggler():
    """Straggler exclusion via ``--exclude-straggler``: 6 nodes, one
    slowed by an injected sleep. A slow node drags its collective
    partners to the same elapsed time, so each single round flags the
    whole pair — the re-paired second round's intersection must isolate
    exactly the slow node, which exits; the other 5 bring up the world."""
    from dlrover_tpu.master.local_master import start_local_master

    n_nodes, slow = 6, 5
    master = start_local_master(
        node_num=n_nodes, min_node_num=1, rdzv_waiting_timeout=8
    )
    try:
        addr = f"127.0.0.1:{master.port}"
        job = "chaos-straggler"
        procs = {}
        for node_id in range(n_nodes):
            env = {
                "DLROVER_TPU_DIST_INIT_TIMEOUT": "30",
                # tiny benchmark: 6 contending agents on one CPU core make
                # the default 1024^3 matmul chain take ~60s, drowning the
                # injected sleep; the straggler ratio needs the sleep to
                # dominate the baseline
                "DLROVER_TPU_CHECK_MATMUL_SIZE": "128",
                "DLROVER_TPU_CHECK_MATMUL_ITERS": "4",
                "DLROVER_TPU_CHECK_PSUM_BYTES": "4096",
            }
            if node_id == slow:
                env["DLROVER_TPU_MOCK_SLOW_NODE"] = str(slow)
                env["DLROVER_TPU_MOCK_SLOW_SECS"] = "20"
            procs[node_id] = subprocess.Popen(
                _agent_cmd(
                    addr, job, node_id,
                    ["--network-check", "--exclude-straggler"],
                    nnodes=f"1:{n_nodes}", script=NOOP,
                ),
                env=_env(env), stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        outs = {i: p.communicate(timeout=420)[0] for i, p in procs.items()}

        assert procs[slow].returncode != 0, outs[slow][-3000:]
        assert "excluded as straggler" in outs[slow], outs[slow][-3000:]
        from dlrover_tpu.common.constants import RendezvousName

        check_mgr = master.rdzv_managers[RendezvousName.NETWORK_CHECK]
        stragglers, _ = check_mgr.get_straggler()
        assert stragglers == [slow], (stragglers, outs[slow][-1500:])
        for node_id in range(n_nodes - 1):
            logs = _agent_logs(job, node_id)
            assert procs[node_id].returncode == 0, (
                f"agent{node_id}:\n{outs[node_id][-3000:]}\n"
                f"workers:\n{logs[-2000:]}"
            )
            assert "[noop] done" in logs, logs[-1500:]
    finally:
        master.stop()


@pytest.mark.slow
def test_worker_kill_moves_goodput_ledger():
    """Kill a worker mid-epoch; after recovery the SpeedMonitor ledger
    must show real downtime bracketed by step reports, and a goodput
    fraction in (0, 1]. (BASELINE north star is ≥95% over a week with
    sparse failures; a seconds-long test with one crash asserts the
    ledger *mechanics*, with a loose ≥20% floor.)"""
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(node_num=2)
    try:
        addr = f"127.0.0.1:{master.port}"
        job = "chaos-goodput"
        p0 = subprocess.Popen(
            _agent_cmd(addr, job, 0),
            env=_env({"DLROVER_TPU_TEST_CRASH_STEP": "2"}),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        p1 = subprocess.Popen(
            _agent_cmd(addr, job, 1),
            env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        out0, _ = p0.communicate(timeout=420)
        out1, _ = p1.communicate(timeout=420)
        logs = _agent_logs(job, 0) + _agent_logs(job, 1)
        assert p0.returncode == 0, f"{out0[-3000:]}\n{logs[-2000:]}"
        assert p1.returncode == 0, f"{out1[-3000:]}\n{logs[-2000:]}"
        assert "injected crash" in logs
        assert "[toy] done" in logs

        sm = master.speed_monitor
        # downtime started at the failure report and ended at a step
        # report after recovery (not still dangling)
        assert sm.total_downtime() > 0.0
        assert sm._downtime_start == 0.0, "downtime never closed"
        g = sm.goodput()
        assert 0.2 <= g <= 1.0, f"goodput={g}"
        # lost-time attribution contract: every second of wall time is
        # accounted (categories sum to elapsed) and the unattributed
        # residual obeys the same bound the goodput floor implies —
        # these toy workers report no digests/breakdowns, so the whole
        # crash downtime lands in `unattributed` (a trainer-based run
        # attributes it; a sustained run drives the fraction toward 0)
        attr = sm.attribution()
        cats = attr["categories"]
        assert sum(cats.values()) == pytest.approx(
            attr["elapsed_wall_s"], rel=0.01
        )
        assert cats["unattributed"] <= 0.8 * attr["elapsed_wall_s"] + 1.0, (
            attr
        )
    finally:
        master.stop()


@pytest.mark.slow
def test_master_sigkill_resumes_shards_exactly_once(tmp_path):
    """VERDICT r3 #3: SIGKILL the master mid-training; the operator(-like
    harness) relaunches it on the same address with the same durable state
    backend. The surviving worker keeps training through the gap, no data
    shard is processed twice, every shard is processed, and the goodput
    ledger carries across the relaunch (downtime recorded, global step
    monotonic)."""
    import re
    import signal
    import socket
    import time

    MASTER = os.path.join(REPO, "tests", "e2e", "master_proc.py")
    SHARDS = os.path.join(REPO, "tests", "e2e", "train_shards.py")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    job = "chaos-master-kill"
    import shutil

    shutil.rmtree(f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job}", ignore_errors=True)
    state_env = {
        "DLROVER_TPU_STATE_BACKEND": "file",
        "DLROVER_TPU_STATE_DIR": str(tmp_path / "state"),
        "DLROVER_TPU_JOB_NAME": job,
    }

    def spawn_master(tag):
        # stdout to a file (not a PIPE): a failing run leaves the
        # master's own log readable next to the agent logs
        log = tmp_path / f"master-{tag}.log"
        p = subprocess.Popen(
            [sys.executable, MASTER, str(port), "1"],
            env=_env(state_env), stdout=open(log, "w"),
            stderr=subprocess.STDOUT, text=True,
        )
        deadline = time.time() + 60
        while time.time() < deadline:
            text = log.read_text() if log.exists() else ""
            if "READY" in text or p.poll() is not None:
                break
            time.sleep(0.1)
        ready = [l for l in log.read_text().splitlines() if "READY" in l]
        assert ready, log.read_text()[-2000:]
        assert f"port={port}" in ready[0], ready[0]
        return p

    m1 = spawn_master("m1")

    agent = subprocess.Popen(
        _agent_cmd(f"127.0.0.1:{port}", job, 0, nnodes="1:1", script=SHARDS),
        env=_env({**state_env,
                  "DLROVER_TPU_TEST_DATASET_SIZE": "256",
                  "DLROVER_TPU_TEST_SHARD_SIZE": "8",
                  "DLROVER_TPU_TEST_SHARD_SLEEP": "0.8"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    m2 = None
    try:
        # wait until a few shards are in flight, then kill the master
        deadline = time.time() + 180
        while time.time() < deadline:
            logs = _agent_logs(job, 0)
            if logs.count("[shards] processing") >= 3:
                break
            time.sleep(0.5)
        else:
            pytest.fail(f"no shards processed:\n{_agent_logs(job, 0)[-3000:]}")

        os.kill(m1.pid, signal.SIGKILL)
        m1.wait(timeout=30)
        time.sleep(1.0)  # real relaunch gap; client retries bridge it
        m2 = spawn_master("m2")

        out, _ = agent.communicate(timeout=300)
        logs = _agent_logs(job, 0)
        assert agent.returncode == 0, f"{out[-3000:]}\n{logs[-3000:]}"
        assert "[shards] done" in logs, logs[-2000:]

        # exactly-once: every shard range processed exactly one time
        ranges = re.findall(r"\[shards\] processing (\d+):(\d+)", logs)
        ranges = [(int(a), int(b)) for a, b in ranges]
        assert len(ranges) == len(set(ranges)), (
            f"double-processed shards: "
            f"{[r for r in set(ranges) if ranges.count(r) > 1]}"
        )
        assert set(ranges) == {(i, i + 8) for i in range(0, 256, 8)}, (
            sorted(set(ranges))
        )

        # the relaunched master concludes the job and its ledger carried
        # across: global step from before the kill, downtime recorded
        m2.wait(timeout=120)
        mout = (tmp_path / "master-m2.log").read_text()
        m = re.search(
            r"MASTER_EXIT global_step=(\d+) downtime=([\d.]+) "
            r"goodput=([\d.]+)", mout,
        )
        assert m, mout[-2000:]
        assert m2.returncode == 0, mout[-2000:]
        gstep, downtime, goodput = (
            int(m.group(1)), float(m.group(2)), float(m.group(3)),
        )
        assert gstep == 32, mout[-1000:]       # 256/8 tasks, one step each
        assert downtime > 0.0                  # the relaunch gap was billed
        assert 0.0 < goodput <= 1.0
    finally:
        for p in (agent, m1, m2):
            if p is not None and p.poll() is None:
                p.kill()
