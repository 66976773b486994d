"""End-to-end launcher tests: CLI -> master -> agent -> JAX workers.

The reference's first demo target (SURVEY.md §7 stage 2): standalone run,
worker-crash recovery, and a 2-node elastic world with a mid-training crash
+ membership-change restart — all on CPU devices.
"""

import os
import subprocess
import sys
import tempfile
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = os.path.join(REPO, "tests", "e2e", "train_toy.py")


def _run_cli(args, env_extra=None, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("DLROVER_TPU_TEST_CRASH_STEP", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.run.elastic_run"] + args,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _agent_logs(job_name, node_id=0):
    log_dir = f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job_name}/node-{node_id}"
    out = ""
    if os.path.isdir(log_dir):
        for f in sorted(os.listdir(log_dir)):
            if os.path.isdir(os.path.join(log_dir, f)):
                continue  # e.g. hang/ stack-dump dir
            out += open(os.path.join(log_dir, f), errors="replace").read()
    return out


def test_standalone_run_succeeds():
    r = _run_cli(
        [
            "--standalone",
            "--nnodes=1",
            "--accelerator=cpu",
            "--job_name=e2e-ok",
            "--monitor_interval=0.5",
            TOY,
        ]
    )
    logs = _agent_logs("e2e-ok")
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}\nworker:\n{logs[-2000:]}"
    assert "[toy] done" in logs


def test_standalone_worker_crash_restarts_and_recovers():
    r = _run_cli(
        [
            "--standalone",
            "--nnodes=1",
            "--accelerator=cpu",
            "--job_name=e2e-crash",
            "--monitor_interval=0.5",
            "--max_restarts=2",
            TOY,
        ],
        env_extra={"DLROVER_TPU_TEST_CRASH_STEP": "2"},
    )
    logs = _agent_logs("e2e-crash")
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}\nworker:\n{logs[-2000:]}"
    assert "injected crash" in logs
    assert "[toy] done" in logs


@pytest.mark.slow
def test_two_node_elastic_world_with_crash():
    """2 agents form a world over gloo; node 0's worker crashes mid-run;
    both re-rendezvous (membership change on node 1) and finish."""
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(node_num=2)
    try:
        addr = f"127.0.0.1:{master.port}"
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        base = [
            sys.executable,
            "-m",
            "dlrover_tpu.run.elastic_run",
            f"--master_addr={addr}",
            "--nnodes=2",
            "--accelerator=cpu",
            "--job_name=e2e-2node",
            "--monitor_interval=0.5",
            "--max_restarts=2",
            "--rdzv_join_timeout=120",
        ]
        env0 = dict(env)
        env0["DLROVER_TPU_TEST_CRASH_STEP"] = "2"
        p0 = subprocess.Popen(
            base + ["--node_id=0", TOY], env=env0, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        p1 = subprocess.Popen(
            base + ["--node_id=1", TOY], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        out0, _ = p0.communicate(timeout=420)
        out1, _ = p1.communicate(timeout=420)
        logs = _agent_logs("e2e-2node", 0) + _agent_logs("e2e-2node", 1)
        assert p0.returncode == 0, f"agent0:\n{out0[-3000:]}\nworkers:\n{logs[-2000:]}"
        assert p1.returncode == 0, f"agent1:\n{out1[-3000:]}\nworkers:\n{logs[-2000:]}"
        assert "injected crash" in logs
        assert "[toy] done" in logs
    finally:
        master.stop()
