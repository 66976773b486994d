"""TPU-or-fail: with no TPU the default ``--accelerator=tpu`` path
raises instead of training on the CPU, and ``chip_smoke.py`` reports
``"ok": false`` with a non-zero exit — here, where there is no chip, and
alone in a directory without the program."""

import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path, **extra):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        **extra,
    )
    return env


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_init_raises_without_a_tpu(tmp_path):
    code = (
        "import dlrover_tpu.train as dtrain\n"
        "dtrain.init(connect_master=False)\n"
    )
    run = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=_env(tmp_path, DLROVER_TPU_ACCELERATOR="tpu"),
    )
    assert run.returncode != 0
    assert "accelerator=tpu but JAX's backend is 'cpu'" in run.stderr


def test_init_cpu_is_the_test_mode(tmp_path):
    code = (
        "import dlrover_tpu.train as dtrain\n"
        "dtrain.init(connect_master=False)\n"
        "import jax; print('backend', jax.default_backend())\n"
    )
    run = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env=_env(tmp_path, DLROVER_TPU_ACCELERATOR="cpu"),
    )
    assert run.returncode == 0, run.stderr
    assert "backend cpu" in run.stdout


def test_chip_smoke_fails_without_a_chip(tmp_path):
    tmp = tmp_path / "tmp"
    tmp.mkdir()
    run = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=300, env=_env(tmp_path, TMPDIR=str(tmp)),
    )
    assert run.returncode != 0
    assert _last_json(run.stdout) == {"ok": False, "device": None}
    # the worker's own error is shown, not swallowed
    assert "accelerator=tpu but JAX's backend is 'cpu'" in run.stdout
    # the launcher's logs and ipc socket went under TMPDIR, into the
    # smoke's work dir, and left with it; the job name is the run's own
    job = re.search(r"--job_name (smoke-A-\S{8}) ", run.stdout).group(1)
    assert os.listdir(tmp) == []
    assert not os.path.exists(f"/tmp/dlrover_tpu_logs/{job}")
    assert not os.path.exists(f"/tmp/dlrover_tpu/{job}")


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    run = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=alone, capture_output=True,
        text=True, timeout=60, env=_env(tmp_path),
    )
    assert run.returncode != 0
    assert _last_json(run.stdout) == {"ok": False, "device": None}
