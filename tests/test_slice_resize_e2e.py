"""Elastic slice-count resize, end to end (VERDICT r4 weak #5/next #5).

The multislice analogue of the reference's ``_periodic_adjust_worker``
(``job_auto_scaler.py:315``): the world loses a slice mid-training, the
surviving agents re-rendezvous, the mesh rebuilds slice-major with the
new slice count, the flash checkpoint restores onto the resized world,
and the loss continues; then the slice comes back and the world regrows
the same way. Each agent node stands in for one TPU slice (its
``TPU_SLICE_NAME``); 4 virtual CPU devices per node.
"""

import os
import re
import signal
import subprocess
import sys
import tempfile
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "e2e", "train_slice_resize.py")


def _agent_cmd(addr, job, node_id):
    return [
        sys.executable, "-m", "dlrover_tpu.run.elastic_run",
        f"--master_addr={addr}",
        "--nnodes=1:2",
        "--accelerator=cpu",
        f"--job_name={job}",
        "--monitor_interval=0.5",
        "--max_restarts=3",
        "--rdzv_join_timeout=180",
        f"--node_id={node_id}",
        SCRIPT,
    ]


def _env(slice_name, ckpt_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["TPU_SLICE_NAME"] = slice_name
    env["DLROVER_TPU_TEST_CKPT_DIR"] = ckpt_dir
    env["DLROVER_TPU_TEST_STEPS"] = "14"
    env["DLROVER_TPU_TEST_STEP_SLEEP"] = "0.5"
    env["DLROVER_TPU_DIST_INIT_TIMEOUT"] = "60"
    return env


def _worker_log(job, node_id):
    log_dir = f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job}/node-{node_id}"
    out = ""
    if os.path.isdir(log_dir):
        for f in sorted(os.listdir(log_dir)):
            p = os.path.join(log_dir, f)
            if os.path.isfile(p):
                out += open(p, errors="replace").read()
    return out


def _kill_node_processes(agent_proc, job, node_id):
    """SIGKILL one node wholesale: the agent's own process group plus
    its worker processes (which run in separate sessions). Worker pids
    come from /proc cmdline+environ so only THIS node's workers die."""
    try:
        os.killpg(os.getpgid(agent_proc.pid), signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    agent_proc.wait(timeout=30)
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            cmd = open(f"/proc/{pid}/cmdline", "rb").read().decode(
                errors="replace"
            )
            if "train_slice_resize.py" not in cmd:
                continue
            environ = open(f"/proc/{pid}/environ", "rb").read().decode(
                errors="replace"
            )
            if f"DLROVER_TPU_NODE_ID={node_id}\x00" in environ:
                os.kill(int(pid), signal.SIGKILL)
        except (OSError, ValueError):
            continue


def _wait_for(pattern, job, node_id=0, timeout=420):
    deadline = time.time() + timeout
    while time.time() < deadline:
        logs = _worker_log(job, node_id)
        m = re.search(pattern, logs)
        if m:
            return m, logs
        time.sleep(1.0)
    raise AssertionError(
        f"pattern {pattern!r} not seen in node-{node_id} logs:\n"
        f"{_worker_log(job, node_id)[-3000:]}"
    )


@pytest.mark.slow
def test_slice_count_resize_2_1_2(tmp_path):
    from dlrover_tpu.master.local_master import start_local_master

    master = start_local_master(
        node_num=2, min_node_num=1, rdzv_waiting_timeout=15
    )
    job = "slice-resize"
    ckpt_dir = str(tmp_path / "ckpt")
    # stale logs from a previous run would satisfy _wait_for patterns
    import shutil

    shutil.rmtree(f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job}", ignore_errors=True)
    try:
        addr = f"127.0.0.1:{master.port}"
        # start_new_session so killing an agent's group never touches the
        # test runner's own process group
        p0 = subprocess.Popen(
            _agent_cmd(addr, job, 0), env=_env("slice-a", ckpt_dir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True,
        )
        p1 = subprocess.Popen(
            _agent_cmd(addr, job, 1), env=_env("slice-b", ckpt_dir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True,
        )

        # phase A: both slices seated, slice-major 2-slice mesh
        _wait_for(r"world: 8 devices, 2 slices", job, 0)
        m, _ = _wait_for(r"step=(\d+) slices=2", job, 0)

        # slice-b dies abruptly: SIGKILL the agent AND its workers (the
        # agent launches workers in their own sessions, so kill both)
        _kill_node_processes(p1, job, 1)

        # phase B: survivor re-rendezvouses into a 1-slice world and
        # RESUMES from the persisted step - not from zero
        _wait_for(r"world: 4 devices, 1 slices", job, 0)
        m_res, logs0 = _wait_for(r"resumed step (\d+) onto 1-slice", job, 0)
        assert int(m_res.group(1)) >= 1
        _wait_for(r"step=\d+ slices=1", job, 0)

        # phase C: the slice returns (autoscaler-style grow): new agent
        # process for node 1, same slice name
        p1b = subprocess.Popen(
            _agent_cmd(addr, job, 1), env=_env("slice-b", ckpt_dir),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True,
        )
        _wait_for(r"resumed step (\d+) onto 2-slice", job, 0, timeout=480)
        out0, _ = p0.communicate(timeout=600)
        out1b, _ = p1b.communicate(timeout=120)
        logs0 = _worker_log(job, 0)
        assert p0.returncode == 0, f"{out0[-3000:]}\n{logs0[-3000:]}"
        assert p1b.returncode == 0, out1b[-3000:]

        # loss continuity: the final loss (post two resizes) is below the
        # cold-start loss, and steps are monotonic through both resizes
        done = re.search(
            r"done: step=14 slices=2 loss ([\d.]+)->([\d.]+)", logs0
        )
        assert done, logs0[-2000:]
        steps = [int(s) for s in re.findall(r"step=(\d+) slices=\d+",
                                            logs0)]
        assert steps[-1] == 14
        # monotonic within each incarnation; across a resume the counter
        # legally rewinds by the commit lag (the resized restore reads
        # the last COMMITTED disk step, and those steps replay with the
        # same shard data) — but never jumps forward
        for seg in re.split(r"resumed step \d+ onto", logs0):
            seg_steps = [int(s)
                         for s in re.findall(r"step=(\d+) slices=\d+", seg)]
            assert seg_steps == sorted(seg_steps), seg_steps
        for m in re.finditer(r"resumed step (\d+) onto \d+-slice", logs0):
            resumed = int(m.group(1))
            # never forward past data already trained: the resumed step
            # must have been reached before this resume (+1 because a
            # kill can land between save(N)'s commit and the step=N
            # print, so the commit may lead the printed max by one)
            prior = [int(s) for s in re.findall(
                r"step=(\d+) slices=\d+", logs0[: m.start()]
            )]
            assert prior and resumed <= max(prior) + 1, (m.group(0), prior)
            # the incarnation continues at resumed+1 (bound the search to
            # this incarnation: a kill can land before any step prints)
            nxt_resume = re.search(r"resumed step \d+ onto",
                                   logs0[m.end():])
            segment = logs0[m.end(): m.end() + nxt_resume.start()] \
                if nxt_resume else logs0[m.end():]
            nxt = re.search(r"step=(\d+) slices=\d+", segment)
            if nxt:
                assert int(nxt.group(1)) == resumed + 1, (
                    m.group(0), nxt.group(0),
                )
        cold = re.search(r"step=1 slices=2 loss=([\d.]+)", logs0)
        assert cold, logs0[:2000]
        # the state survived both resizes: the final loss sits clearly
        # below the cold-start loss (fixed-batch memorization curve)
        assert float(done.group(2)) < float(cold.group(1)) - 0.1, (
            cold.group(1), done.group(2),
        )
        # all three world shapes actually happened
        assert "world: 8 devices, 2 slices" in logs0
        assert "world: 4 devices, 1 slices" in logs0
    finally:
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
        try:
            if p1b.poll() is None:
                p1b.kill()
        except NameError:
            pass
        master.stop()
