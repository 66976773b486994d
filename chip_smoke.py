#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry point a user calls: the
elastic launcher (``dlrover_tpu.run.elastic_run --standalone``) starts a
master, an agent and one worker running ``examples/llama_pretrain.py``
at Llama-3-8B's published widths (dim 4096, 32 q / 8 kv heads, ffn
14336, vocab 128256, seq 2048, bf16), cut in depth to what one 16 GB
chip holds, weights random from a fixed seed.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: one chip vs --fsdp 4

- phase A: 6 optimizer steps, a flash checkpoint (device -> shm) every
  step and a persist to both checkpoint tiers at step 6. Every loss
  finite, step-1 loss what random weights give
  (ln(vocab) + dim * std^2 / 2), worker exit 0.
- phase B: the launcher runs again on the same checkpoint dir to step
  8: the state comes back through the Checkpointer from the node-local
  disk tier, as it does for a restarted job, at step 6; 2 more finite
  steps.
- phase R: the six steps of phase A again with the fused-CE kernel
  switched off (``DLROVER_TPU_FUSED_CE=0``: the chunked scan, plain
  XLA): the kernel's losses agree with that reference (its forward),
  and so do the norms of adam's first moment per parameter group after
  step 6 (its backward).
- ``--chips 4``: only the same batch of 4 sequences for 3 steps on one
  chip and on four with ``--fsdp 4``; losses and first-moment norms
  agree in the same way (the backward here runs through the kernels'
  ``shard_map`` wrappers), and the four-chip worker holds parameter
  shards on four devices.

This process never imports JAX: a chip belongs to one process, the
worker. It reads the worker's log. The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``; anything that failed makes it ``"ok": false`` and the
exit code 1. Without a TPU the worker's ``dtrain.init()`` raises, so
this fails. ``--rehearse-cpu`` walks the same phases with the tiny model
on the CPU to check the control flow; it never reports ok.
"""

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
EXAMPLE = os.path.join("examples", "llama_pretrain.py")
LAUNCH_TIMEOUT_S = 500

# Llama-3-8B widths are llama_pretrain's ``--model 8b``; the cut is the
# depth. A layer is 218 M params, embedding + head 1.05 B; params and
# both adam moments in bf16 are 6 B/param, so 2 layers hold 8.9 GB of
# state, and the step program compiled for a v5e peaks at 12.0 GiB.
MODEL = ["--model", "8b", "--layers", "2", "--param-dtype", "bfloat16",
         "--seq", "2048"]
MODEL_REHEARSAL = ["--model", "tiny", "--layers", "1",
                   "--param-dtype", "bfloat16"]
# Host memory decides how often the smoke persists. The one-chip
# machine has 40 GiB and keeps files in memory too. A save holds the
# state twice (shm + the host copies of the device buffers), a persisted
# step twice more (node-local tier + shared tier), and three steps are
# retained: persisting at steps 3 and 6 came to 6 copies of 8.9 GB and
# the run was killed. One persist, at step 6, keeps it to 4.
SAVE_EVERY = "6"
# Two programs on the same weights and batches (fused vs chunked CE; one
# chip vs four): |difference| of each step's losses. Operands are bf16
# (2^-8 relative, 0.05 at a loss of 12.5); the programs differ in tile
# and reduction order only, and the loss is an f32 mean over thousands
# of tokens, so they are held well inside that.
LOSS_TOLERANCE = 0.02
# ... and relative difference of the norms of adam's first moment, per
# parameter group, after the last of those steps. The moments are kept
# in bf16 and a norm averages the rounding of millions of elements; a
# backward that drops, doubles or mis-scales a term moves a group's
# share of the clipped gradient by tens of percent. Printed on the
# v5e: 1e-6 fused vs chunked CE, 8e-5 at most one chip vs four.
MOMENT_TOLERANCE = 0.005
# models/llama.py init_params: every weight matrix is N(0, 0.02^2).
INIT_STD = 0.02


class PhaseFailed(Exception):
    pass


def check(cond, what):
    print(f"  [{'pass' if cond else 'FAIL'}] {what}", flush=True)
    if not cond:
        raise PhaseFailed(what)


def child_env():
    env = dict(os.environ)
    # The compile cache is placed from outside; only when nobody placed
    # it, at a fixed path in the checkout (the path is part of the key).
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    return env


def descendants(root):
    """Pids below ``root`` in the process tree. The launcher stops its
    master and workers itself; one that has to be killed leaves them
    behind, in sessions of their own, so no process group holds them."""
    children = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        children.setdefault(ppid, []).append(int(pid))
    found, stack = [], [root]
    while stack:
        for child in children.get(stack.pop(), []):
            found.append(child)
            stack.append(child)
    return found


def launch(args, name, work, script_args, env, local_devices=None):
    """One launcher run; returns the worker's log text."""
    # the job name keys the launcher's shm segments, which every
    # process on the host shares: make it this run's own. Logs and the
    # ipc socket go under the children's TMPDIR, which is ``work``.
    job = f"smoke-{name}-{os.path.basename(work)[-8:]}"
    log_dir = os.path.join(work, "dlrover_tpu_logs", job)
    env = dict(env, TMPDIR=work)
    if local_devices:
        env["LOCAL_DEVICES"] = str(local_devices)
    cmd = [sys.executable, "-m", "dlrover_tpu.run.elastic_run",
           "--standalone", "--nnodes=1", "--nproc_per_node=1",
           f"--accelerator={args.accelerator}", "--job_name", job,
           "--max_restarts", "0", EXAMPLE, "--"] + script_args
    print(f"[{name}] {' '.join(cmd[1:])}", flush=True)
    t0 = time.time()
    with open(os.path.join(work, f"launcher-{name}.log"), "wb") as out:
        proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=LAUNCH_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            rc = None
            below = descendants(proc.pid)
            proc.terminate()  # the agent stops its workers on SIGTERM
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            for pid in below:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass  # already gone
    print(f"[{name}] launcher exit {rc} after {time.time() - t0:.1f}s",
          flush=True)
    log_path = os.path.join(log_dir, "node-0", "worker-0-restart0.log")
    try:
        with open(log_path, errors="replace") as f:
            log = f.read()
    except OSError:
        log = ""
    if args.logs_to:
        os.makedirs(args.logs_to, exist_ok=True)
        shutil.copy(os.path.join(work, f"launcher-{name}.log"),
                    args.logs_to)
        with open(os.path.join(args.logs_to, f"worker-{name}.log"),
                  "w") as f:
            f.write(log)
    for line in log.splitlines():
        if re.match(r"device |config |param_bytes|restored from|step \d"
                    r"|first_moment|peak_bytes|DONE", line) \
                or "step build:" in line:
            print(f"  {line[:400]}", flush=True)
    if rc != 0 or "\nDONE" not in log:
        print(f"[{name}] worker log tail:", flush=True)
        for line in log.splitlines()[-30:]:
            print(f"  | {line[:400]}", flush=True)
        with open(os.path.join(work, f"launcher-{name}.log"),
                  errors="replace") as f:
            for line in f.read().splitlines()[-15:]:
                print(f"  > {line[:400]}", flush=True)
    check(rc == 0, f"{name}: launcher and worker exit 0 (got {rc})")
    check("\nDONE" in log, f"{name}: worker printed DONE")
    return log


def device_of(log):
    m = re.search(r"^device platform=(\S+) kind='([^']*)' count=(\d+)$",
                  log, re.M)
    check(m is not None, "worker printed its device line")
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3))}


def losses_of(log):
    return {int(s): float(v) for s, v in
            re.findall(r"^step (\d+) loss (\S+)", log, re.M)}


def moments_of(log):
    m = re.search(r"^first_moment_norms (\{.*\})$", log, re.M)
    return json.loads(m.group(1)) if m else {}


def config_of(log, key):
    return int(re.search(rf"^config .* {key}=(\d+)", log, re.M).group(1))


def check_losses(name, log, steps):
    losses = losses_of(log)
    check(sorted(losses) == list(steps),
          f"{name}: steps {list(steps)} ran (got {sorted(losses)})")
    check(all(math.isfinite(v) for v in losses.values()),
          f"{name}: every loss finite")
    return losses


def check_random_init_loss(name, log, loss):
    """Random weights: the final norm gives the head unit-RMS inputs, so
    the logits are N(0, dim * std^2) and the expected loss is
    ln(vocab) + dim * std^2 / 2 (12.58 at Llama-3-8B widths, of which
    ln 128256 is 11.76; 5.56 for the tiny model)."""
    want = math.log(config_of(log, "vocab")) \
        + config_of(log, "dim") * INIT_STD ** 2 / 2
    check(abs(loss - want) <= 0.25,
          f"{name}: step-1 loss {loss:.4f} within 0.25 of "
          f"ln(vocab) + dim*std^2/2 = {want:.4f}")


def check_same_run(name_a, losses_a, moments_a, name_b, losses_b, moments_b):
    """Two programs given the same weights and batches: the losses of
    every step agree (the forward), and so do the norms of adam's first
    moment per parameter group at the end (the backward)."""
    for step in sorted(losses_a):
        diff = abs(losses_a[step] - losses_b[step])
        check(diff <= LOSS_TOLERANCE,
              f"step-{step} loss {name_a} {losses_a[step]:.4f} vs {name_b} "
              f"{losses_b[step]:.4f}: |diff| {diff:.4f} <= {LOSS_TOLERANCE}")
    check(len(moments_a) > 0 and sorted(moments_a) == sorted(moments_b),
          f"both printed first-moment norms ({sorted(moments_a)})")
    for group in sorted(moments_a):
        a, b = moments_a[group], moments_b[group]
        rel = abs(a - b) / max(a, 1e-30)
        check(a > 0 and rel <= MOMENT_TOLERANCE,
              f"adam first-moment norm of {group}: {name_a} {a:.6g} vs "
              f"{name_b} {b:.6g}: relative diff {rel:.1e} <= "
              f"{MOMENT_TOLERANCE}")


def check_room(state_bytes, ckpt_dir):
    """The flash checkpoint stages the whole state in /dev/shm and
    persists it to two tiers; say so when there is no room, do not
    shrink."""
    for path, need in (("/dev/shm", state_bytes),
                       (ckpt_dir, 2 * state_bytes)):
        free = shutil.disk_usage(path).free
        print(f"  {path}: {free / 2**30:.1f} GiB free, checkpoint needs "
              f"{need / 2**30:.1f} GiB", flush=True)
        check(free > 1.1 * need, f"{path} can hold the checkpointed state")


def build_report(name, log, env):
    """Step-build (trace + compile, or a read from the persistent
    cache) seconds as the trainer logged them, and the cache's size."""
    m = re.search(r"step build: (.*)$", log, re.M)
    d = env["JAX_COMPILATION_CACHE_DIR"]
    n = len(os.listdir(d)) if os.path.isdir(d) else 0
    print(f"  {name}: step build {m.group(1) if m else 'not logged'}; "
          f"compile cache {d} holds {n} files", flush=True)


def one_chip(args, work, env):
    ckpt = os.path.join(work, "ckpt")
    os.makedirs(ckpt)
    if not args.rehearse_cpu:
        # 2 layers of Llama-3-8B: 1.487 B params x 6 B (bf16 params and
        # both adam moments)
        check_room(1_486_901_248 * 6, ckpt)
    common = args.model + ["--micro-batch", "1", "--global-batch", "1",
                           "--save-every", SAVE_EVERY, "--ckpt-dir", ckpt]
    log_a = launch(args, "A", work, common + ["--steps", "6"], env)
    device = device_of(log_a)
    losses = check_losses("A", log_a, range(1, 7))
    check_random_init_loss("A", log_a, losses[1])
    check("restored from step" not in log_a, "A: started from scratch")
    build_report("A", log_a, env)

    log_b = launch(args, "B", work, common + ["--steps", "8"], env)
    m = re.search(r"^restored from step (\d+) tier=(\S*)", log_b, re.M)
    check(m is not None, "B: worker restored a checkpoint")
    n, tier = int(m.group(1)), m.group(2)
    print(f"  restored step {n} from tier {tier!r}", flush=True)
    check(n == 6, f"B: restored the last persisted step (6, got {n})")
    check(tier == "disk", "B: restored from the node-local disk tier "
          f"(got {tier!r})")
    check_losses("B", log_b, range(n + 1, 9))
    check(device_of(log_b) == device, "B: same device as A")
    build_report("B", log_b, env)

    shutil.rmtree(ckpt)
    log_r = launch(
        args, "R", work, args.model + [
            "--micro-batch", "1", "--global-batch", "1", "--steps", "6",
            "--save-every", "100",
            "--ckpt-dir", os.path.join(work, "ckpt_ref")],
        dict(env, DLROVER_TPU_FUSED_CE="0"))
    ref = check_losses("R", log_r, range(1, 7))
    check_same_run("fused CE", losses, moments_of(log_a),
                   "chunked reference", ref, moments_of(log_r))
    return device


def four_chips(args, work, env):
    virtual = 4 if args.rehearse_cpu else None
    common = args.model + ["--global-batch", "4", "--steps", "3",
                      "--save-every", "100"]
    # the worker sees all four chips of the host; --devices 1 builds its
    # mesh from the first
    log_1 = launch(
        args, "one-chip", work, common + [
            "--micro-batch", "4", "--devices", "1",
            "--ckpt-dir", os.path.join(work, "ckpt1")],
        env, local_devices=virtual)
    check("mesh={'dp': 1, " in log_1 and "'fsdp': 1" in log_1,
          "one-chip: mesh of one device")
    l1 = check_losses("one-chip", log_1, range(1, 4))
    check_random_init_loss("one-chip", log_1, l1[1])
    log_4 = launch(
        args, "four-chips", work, common + [
            "--micro-batch", "1", "--fsdp", "4",
            "--ckpt-dir", os.path.join(work, "ckpt4")],
        env, local_devices=virtual)
    device = device_of(log_4)
    l4 = check_losses("four-chips", log_4, range(1, 4))
    check(device["count"] == 4, f"four devices (got {device['count']})")
    check_same_run("one chip", l1, moments_of(log_1),
                   "four", l4, moments_of(log_4))
    m = re.search(r"^param_bytes_per_device (\{.*\})$", log_4, re.M)
    check(m is not None, "four-chips: worker printed parameter residency")
    per_dev = json.loads(re.sub(r"(\d+):", r'"\1":', m.group(1)))
    check(len(per_dev) == 4 and len(set(per_dev.values())) == 1
          and min(per_dev.values()) > 0,
          f"parameters sharded evenly over four devices: {per_dev}")
    return device


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: only the one-chip vs --fsdp 4 comparison")
    p.add_argument("--logs-to", default="",
                   help="copy the launcher's and worker's logs here")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="walk the phases with the tiny model on the CPU; "
                        "never reports ok")
    args = p.parse_args()
    args.accelerator = "cpu" if args.rehearse_cpu else "tpu"
    args.model = MODEL_REHEARSAL if args.rehearse_cpu else MODEL
    device = None
    ok = False
    t0 = time.time()
    work = tempfile.mkdtemp(prefix="smoke_")  # short: a socket path holds 107 bytes
    try:
        if not os.path.exists(os.path.join(REPO, EXAMPLE)):
            raise PhaseFailed(f"{EXAMPLE} not found beside chip_smoke.py")
        env = child_env()
        print(f"compile cache: {env['JAX_COMPILATION_CACHE_DIR']}",
              flush=True)
        run = four_chips if args.chips == 4 else one_chip
        device = run(args, work, env)
        check(device["platform"] == "tpu",
              f"ran on a TPU (platform {device['platform']!r})")
        check(device["count"] == args.chips,
              f"{args.chips} device(s) (got {device['count']})")
        ok = True
    except PhaseFailed as e:
        print(f"FAILED: {e}", flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"chip_smoke took {time.time() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
