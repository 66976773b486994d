"""E2E: flash checkpoint under the elastic agent survives a worker crash.

The worker stages memory checkpoints every step; it crashes at step 7 (a
step whose persist was memory-only). Two crash models:

- process crash (uncaught exception): the engine's crash drain joins the
  in-flight device-snapshot stage during interpreter teardown, so the shm
  segment that outlives the worker holds step 7 exactly — recovery resumes
  from the crash step (reference guarantee, flash_checkpoint engine).
- hard kill (``os._exit``): nothing in the process runs; the device
  snapshot for step 7 dies with it. Recovery resumes from the last DRAINED
  step (>= 6: save(7) joined step 6's stage before snapshotting) and the
  replayed step produces the exact same final state — at-most-one-step
  loss with exactly-once data semantics.
"""

import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "e2e", "train_ckpt.py")


def _run(tmp_path, job_name, crash_mode):
    import shutil

    # worker logs append under a fixed path; stale lines from a previous
    # pytest invocation would satisfy the resume asserts spuriously
    shutil.rmtree(f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job_name}", ignore_errors=True)
    ckpt_dir = str(tmp_path / "ckpt")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_TPU_TEST_CRASH_STEP"] = "7"
    env["DLROVER_TPU_TEST_CKPT_DIR"] = ckpt_dir
    env["DLROVER_TPU_TEST_CRASH_MODE"] = crash_mode
    r = subprocess.run(
        [
            sys.executable,
            "-m",
            "dlrover_tpu.run.elastic_run",
            "--standalone",
            "--nnodes=1",
            "--accelerator=cpu",
            f"--job_name={job_name}",
            "--monitor_interval=0.5",
            "--max_restarts=2",
            SCRIPT,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    log_dir = f"{tempfile.gettempdir()}/dlrover_tpu_logs/{job_name}/node-0"
    logs = ""
    for f in sorted(os.listdir(log_dir)):
        if os.path.isdir(os.path.join(log_dir, f)):
            continue  # e.g. hang/ stack-dump dir
        logs += open(os.path.join(log_dir, f), errors="replace").read()
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}\nworker:\n{logs[-2000:]}"
    assert "injected crash at step 7" in logs
    assert "[ckpt-e2e] done: step=12 w0=12.0" in logs
    return logs


def test_crash_resume_from_flash_checkpoint(tmp_path):
    logs = _run(tmp_path, "e2e-ckpt", "exc")
    # teardown drain landed the crash step in shm: resume is exact
    assert "resumed from step 7" in logs, logs[-2000:]


def test_hard_kill_resume_at_most_one_step(tmp_path):
    logs = _run(tmp_path, "e2e-ckpt-kill", "exit")
    # the in-flight stage dies with the process. Typical: the kill lands
    # before the drain thread reaches the shm write, so step 6 (joined
    # before step 7's snapshot) is intact. Narrow windows: the drain wins
    # (7), or the kill tears the shm write itself — the invalidated
    # header forces fallback to the disk persist at step 4. Never a cold
    # start, and the final state (asserted in _run) proves replay from
    # any of these points is exact.
    m = re.search(r"resumed from step (\d+)", logs)
    assert m, logs[-2000:]
    assert int(m.group(1)) >= 4, logs[-2000:]
