"""Typed registry for ``DLROVER_TPU_*`` environment flags.

The repo grew ~50 scattered ``os.environ`` call sites; each invented
its own default and parse-failure behavior, none were discoverable,
and a typo'd name failed silent. This module is the one place a
runtime knob is *defined* — name, type, default, help — and the one
place it is *read*. graftlint rule JG003 enforces it: raw env reads
outside {this module, common/constants.py, agent/config.py,
train/bootstrap.py} fail the lint gate.

Semantics, kept bit-identical to the call sites this replaced:

- flags re-read the environment on every ``get()`` — tests and benches
  flip kill-switches at runtime, and the jitted-trace caveat ("set it
  before the first trace") is the call site's contract, not this
  module's;
- empty string == unset == default (every migrated site used
  ``os.environ.get(X, d) or d`` or treated "" as absent);
- bool flags are ``raw != "0"`` (``DLROVER_TPU_WARM_COMPILE=0`` is the
  only spelling that disables — matching the kill-switch convention);
- a value that fails to parse logs one warning and returns the
  default: a mistyped knob must never crash a training process.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Dict, List, Optional

from dlrover_tpu.common import constants
from dlrover_tpu.common.log import logger


@dataclasses.dataclass(frozen=True)
class EnvFlag:
    """One typed environment flag. ``kind``: bool | int | float | str."""

    name: str
    default: Any
    kind: str
    help: str = ""

    def raw(self) -> Optional[str]:
        return os.environ.get(self.name)

    def present(self) -> bool:
        """Set to a non-empty value (empty string counts as unset)."""
        raw = self.raw()
        return raw is not None and raw != ""

    def get(self) -> Any:
        """Current typed value; re-reads the environment every call."""
        raw = self.raw()
        if raw is None or raw == "":
            return self.default
        if self.kind == "bool":
            return raw != "0"
        if self.kind == "str":
            return raw
        try:
            return int(raw) if self.kind == "int" else float(raw)
        except ValueError:
            logger.warning(
                "%s=%r is not a valid %s; using default %r",
                self.name, raw, self.kind, self.default,
            )
            return self.default

    def _stringify(self, value: Any) -> str:
        # bools must round-trip through "1"/"0": str(False) == "False"
        # reads back TRUE under the raw != "0" parse, so a scoped(False)
        # pin would silently leave the flag on (explicit raw strings
        # pass through untouched)
        if self.kind == "bool" and not isinstance(value, str):
            return "1" if value else "0"
        return str(value)

    def propagate(self, value: Any) -> None:
        """Write the flag back into ``os.environ`` so CHILD processes
        (speculative compile helpers, restarted workers forked from
        this env) inherit it. The registry is the only sanctioned env
        *writer* for its own flags, same as it is the only reader."""
        os.environ[self.name] = self._stringify(value)

    @contextlib.contextmanager
    def scoped(self, value: Optional[Any]):
        """Temporarily pin the flag (``None`` clears it → unset), then
        restore the previous environment on exit. For builds whose
        value an explicit knob decides — contract lowering — where an
        operator's exported override must not leak in and silently
        flip which program gets built."""
        prev = os.environ.get(self.name)
        try:
            if value is None:
                os.environ.pop(self.name, None)
            else:
                os.environ[self.name] = self._stringify(value)
            yield
        finally:
            if prev is None:
                os.environ.pop(self.name, None)
            else:
                os.environ[self.name] = prev


def env_snapshot() -> Dict[str, str]:
    """The sanctioned raw clone of the current process environment, for
    call sites that must hand a subprocess the *whole* inherited
    environment (the dryrun stress spawn). This is deliberately the
    only place the clone happens: the registry is the one
    reader/writer of its flags, and a site that
    needs the full environment says so by calling here instead of
    scattering ``dict(os.environ)`` (graftlint JG003)."""
    return dict(os.environ)


def child_env(overrides: Optional[Dict[str, Any]] = None) -> Dict[str, str]:
    """The sanctioned environment clone for spawning child processes
    (worker launch, node-check workloads): the parent's environment —
    which :meth:`EnvFlag.propagate` writes sanctioned flag values into
    — plus per-child overrides, stringified. This is the subprocess
    face of the ``propagate()`` path: call sites build their child env
    here instead of cloning ``os.environ`` raw (graftlint JG003)."""
    env = env_snapshot()
    if overrides:
        for k, v in overrides.items():
            flag = _REGISTRY.get(k)
            env[k] = flag._stringify(v) if flag is not None else str(v)
    return env


_REGISTRY: Dict[str, EnvFlag] = {}


def _define(name: str, default: Any, kind: str, help: str = "") -> EnvFlag:
    flag = EnvFlag(name, default, kind, help)
    _REGISTRY[name] = flag
    return flag


def all_flags() -> List[EnvFlag]:
    """The full catalog, for docs and ``describe()``."""
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def describe() -> str:
    lines = []
    for f in all_flags():
        lines.append(f"{f.name} ({f.kind}, default {f.default!r}): {f.help}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the catalog
# ---------------------------------------------------------------------------

WARM_COMPILE = _define(
    "DLROVER_TPU_WARM_COMPILE", True, "bool",
    "Warm-path elasticity kill-switch: 0 restores the plain jax.jit "
    "rebuild path (train/warm_compile.py).",
)
COMPILE_CACHE_DIR = _define(
    "DLROVER_TPU_COMPILE_CACHE_DIR", "", "str",
    "Persistent XLA compile cache dir (agent-injected; checkpoint "
    "engine defaults it under the checkpoint dir).",
)
COMPILE_CACHE_MIN_S = _define(
    "DLROVER_TPU_COMPILE_CACHE_MIN_S", 1.0, "float",
    "Minimum compile seconds for an executable to enter the "
    "persistent cache.",
)
WARM_COMPILE_MAX_TARGETS = _define(
    "DLROVER_TPU_WARM_COMPILE_MAX_TARGETS", 2, "int",
    "Upper bound on speculative neighbor-world compiles per build.",
)
WARM_COMPILE_EXIT_JOIN_S = _define(
    "DLROVER_TPU_WARM_COMPILE_EXIT_JOIN_S", 60.0, "float",
    "Interpreter-exit join bound for the speculative compile thread.",
)
LIVE_RESHARD = _define(
    "DLROVER_TPU_LIVE_RESHARD", True, "bool",
    "Live state resharding kill-switch: 0 makes remesh(state=...) "
    "ignore the passed state so callers restore through the "
    "checkpoint round-trip exactly as before (train/live_reshard.py).",
)
FUSED_CE = _define(
    "DLROVER_TPU_FUSED_CE", True, "bool",
    "Fused-CE Pallas kernel switch: 0 runs the scan-based chunked-CE "
    "path even on TPU (ops/fused_ce.py); chip_smoke.py phase R sets "
    "it to select the reference its fused run is held to. Off-TPU "
    "the dispatcher takes the chunked path regardless. Read at trace "
    "time.",
)
COMM_METRICS_PORT = _define(
    "DLROVER_TPU_COMM_METRICS_PORT", None, "int",
    "Worker /metrics port for the per-collective comm ledger "
    "(0 = ephemeral port; unset = disabled).",
)
ASYNC_STAGING = _define(
    "DLROVER_TPU_ASYNC_STAGING", True, "bool",
    "Checkpoint staging kill-switch: 0 stages shm copies synchronously "
    "on the training thread (checkpoint/engine.py).",
)
DEVICE_SNAPSHOT = _define(
    "DLROVER_TPU_DEVICE_SNAPSHOT", True, "bool",
    "0 disables the on-device state snapshot before async staging "
    "(falls back to blocking for the d2h transfer).",
)
DRAIN_TIMEOUT = _define(
    "DLROVER_TPU_DRAIN_TIMEOUT", 20.0, "float",
    "Seconds to wait for in-flight checkpoint staging at teardown; "
    "pair with terminationGracePeriodSeconds (deploy/k8s/README.md).",
)
CKPT_REPLICA = _define(
    "DLROVER_TPU_CKPT_REPLICA", "", "str",
    "Agent-set replica mode: exactly '1' streams staged checkpoints "
    "to the backup peer (checkpoint/replica.py).",
)
CKPT_DEDUP = _define(
    "DLROVER_TPU_CKPT_DEDUP", True, "bool",
    "Replica-deduplicated tiered checkpointing kill-switch "
    "(checkpoint/ownership.py, docs/design/checkpoint_tiers.md): 0 "
    "restores the one-full-copy-per-process stage/persist and the "
    "two-rung shm->storage restore.",
)
CKPT_LOCAL_DIR = _define(
    "DLROVER_TPU_CKPT_LOCAL_DIR", "", "str",
    "Root of the node-local disk checkpoint tier (tier 1; a node-local "
    "SSD/emptyDir volume — deploy/k8s/README.md). Empty: "
    "<ckpt_dir>/_local. Each node writes under <root>/node-<id>.",
)
CKPT_PERSIST_WORKERS = _define(
    "DLROVER_TPU_CKPT_PERSIST_WORKERS", 4, "int",
    "Concurrent leaf-file writers in the persist pool (local-tier "
    "writes and object-tier fanout run this many files in parallel).",
)
REPLICA_MAX_BYTES = _define(
    "DLROVER_TPU_REPLICA_MAX_BYTES", 64 << 30, "int",
    "Replica server per-payload size bound (memory-DoS refusal).",
)
SHARDCHECK = _define(
    "DLROVER_TPU_SHARDCHECK", 0, "int",
    "IR-level step-program analysis at lower time (lint/shardcheck.py):"
    " 0 off, 1 warn on violations, 2 strict (reject the build). Runs "
    "on every lowering, including speculative neighbor worlds.",
)
SHARDCHECK_CONTRACTS = _define(
    "DLROVER_TPU_SHARDCHECK_CONTRACTS", "", "str",
    "Directory of SC001 collective-census contracts for the lower-time "
    "hook (default: the checked-in dlrover_tpu/lint/contracts).",
)
MEMCHECK = _define(
    "DLROVER_TPU_MEMCHECK", 0, "int",
    "Static per-device memory analysis at lower time (lint/memcheck.py):"
    " 0 off, 1 warn on violations, 2 strict (reject the build before it "
    "enters the executable cache). Diffs the compiled step's "
    "memory_analysis() + the analytic avatar model against the "
    "checked-in mem-<spec>.json contract and, with a budget configured, "
    "the device-class HBM budget. Runs on every lowering, including "
    "speculative neighbor worlds.",
)
MEMCHECK_CONTRACTS = _define(
    "DLROVER_TPU_MEMCHECK_CONTRACTS", "", "str",
    "Directory of MC001 per-device memory contracts for the lower-time "
    "hook (default: the checked-in dlrover_tpu/lint/contracts, "
    "mem-<spec>.json next to the SC001 files).",
)
MEMCHECK_DEVICE_CLASS = _define(
    "DLROVER_TPU_MEMCHECK_DEVICE_CLASS", "", "str",
    "Device class whose HBM budget gates the memcheck headroom oracle "
    "(v5e | v5p | cpu-host — the ROADMAP item 5 vocabulary). Empty = "
    "no class budget; DLROVER_TPU_MEMCHECK_BUDGET_GB still applies "
    "when set.",
)
MEMCHECK_BUDGET_GB = _define(
    "DLROVER_TPU_MEMCHECK_BUDGET_GB", 0.0, "float",
    "Explicit per-device HBM budget (GB) for the memcheck headroom "
    "oracle — overrides the device-class table (tests, odd SKUs). "
    "0 = defer to DLROVER_TPU_MEMCHECK_DEVICE_CLASS; with neither "
    "set the MC002 budget gate and the speculation filter are off.",
)
RETRACE_GUARD = _define(
    "DLROVER_TPU_RETRACE_GUARD", 0, "int",
    "Silent-recompile guard (lint/retrace_guard.py): 0 off, 1 on with "
    "defaults, N>=2 on with max N distinct compile signatures per "
    "jitted function.",
)

# -- observability: unified trace spine + straggler policy
# (dlrover_tpu/observability, docs/design/observability.md)

TRACE = _define(
    "DLROVER_TPU_TRACE", False, "bool",
    "Unified trace spine (observability/trace.py): record typed spans "
    "(step/compile/rendezvous/state_transfer/ckpt_save/ckpt_restore/"
    "input_wait/gc_pause/eval) into the process-wide ring. Off by "
    "default; recording is lock+append only, never a host sync. The "
    "spans' counters, gauges and profiler annotations do not depend "
    "on it.",
)
TRACE_DIR = _define(
    "DLROVER_TPU_TRACE_DIR", "", "str",
    "Directory where traced processes dump their span ring at exit "
    "(trace-<role>-*.json, merged by `profiler.analysis job-timeline`)."
    " Empty: <TMPDIR>/dlrover_tpu_logs/<job>/traces.",
)
TRACE_RING_CAP = _define(
    "DLROVER_TPU_TRACE_RING_CAP", 200_000, "int",
    "Bound on the trace spine's span ring; the oldest half is dropped "
    "on overflow (per-kind seconds totals keep counting).",
)
STRAGGLER_RATIO = _define(
    "DLROVER_TPU_STRAGGLER_RATIO", 1.5, "float",
    "Straggler policy (master/monitor/straggler.py): a rank is slow "
    "when its windowed step-time p50 exceeds ratio x the fleet median.",
)
# -- fleet-scale control plane (rpc/transport.py, master/node/job_manager.py,
# docs/design/fleet_harness.md)

RPC_INFLIGHT_CAP = _define(
    "DLROVER_TPU_RPC_INFLIGHT_CAP", 0, "int",
    "Master RPC admission cap: reports beyond this many in-flight "
    "requests are shed with an explicit Overloaded reply (gets shed at "
    "2x). 0 = auto (half the server thread pool). Clamped below the "
    "server thread count — a cap at/above it could never reject and "
    "would silently disable shedding.",
)
MASTER_METRICS_PORT = _define(
    "DLROVER_TPU_MASTER_METRICS_PORT", None, "int",
    "Master /metrics port (goodput, RPC queue depth + shed counters, "
    "straggler count; 0 = ephemeral port; unset = disabled).",
)
EVICT_HYSTERESIS = _define(
    "DLROVER_TPU_EVICT_HYSTERESIS", 2, "int",
    "Consecutive heartbeat-monitor sweeps a RUNNING worker must stay "
    "past the heartbeat timeout before it is evicted (rendezvous slot "
    "released, straggler/digest state forgotten). >=1; the extra "
    "sweep(s) absorb clock jumps and one lost report window.",
)
STRAGGLER_WINDOWS = _define(
    "DLROVER_TPU_STRAGGLER_WINDOWS", 3, "int",
    "Consecutive slow digest windows before a rank is flagged as a "
    "straggler (and a StragglerRecord enters the diagnosis pipeline).",
)

# -- leased data plane + collective-hang watchdog
# (master/shard/, master/monitor/hang_watchdog.py,
# docs/design/data_plane.md)

SHARD_LEASE_TTL_S = _define(
    "DLROVER_TPU_SHARD_LEASE_TTL_S", 120.0, "float",
    "Seconds a batch shard lease stays valid without renewal; every "
    "folded WorkerReport renews it (zero extra RPCs), and expiry "
    "re-enqueues the undone shards at-least-once with the fence "
    "bumped so the zombie's late reports cannot double-count.",
)
SHARD_LEASE_COUNT = _define(
    "DLROVER_TPU_SHARD_LEASE_COUNT", 16, "int",
    "Shards per lease_shards batch the worker's ShardingClient "
    "prefetches (completions of the previous batch ride the same "
    "RPC). 0 restores the one-task-per-get_task legacy protocol.",
)
HANG_WATCHDOG = _define(
    "DLROVER_TPU_HANG_WATCHDOG", True, "bool",
    "Master-side collective-hang watchdog "
    "(master/monitor/hang_watchdog.py): 0 disables the sweep thread. "
    "A round where every live worker is seated but step reports "
    "stopped fleet-wide for the window is declared a collective hang: "
    "downtime bracket opened, attributed to `collective_hang`, and "
    "the seated cohort re-rendezvoused without its silent members.",
)
HANG_WATCHDOG_WINDOW_S = _define(
    "DLROVER_TPU_HANG_WATCHDOG_WINDOW_S", 300.0, "float",
    "Fleet-wide no-progress window before a seated round is declared "
    "a collective hang. Must comfortably exceed the step-report "
    "cadence and the longest legitimate pause (checkpoint save, "
    "eval); one slow RANK never trips it (that is the straggler "
    "detector's job).",
)
# -- goodput planner (brain/planner.py; docs/design/brain_planner.md)

PLANNER = _define(
    "DLROVER_TPU_PLANNER", False, "bool",
    "Arm the goodput planner (brain/planner.py): scale decisions are "
    "driven by the measured goodput ledger (digest p50s, per-link comm "
    "bytes, resize-downtime breakdown, straggler flags) with "
    "payback-amortized scoring, hysteresis and cooldown, instead of "
    "the legacy CPU/memory heuristics. Off by default; the fleet "
    "harness arms it per scenario.",
)
PLANNER_COOLDOWN_S = _define(
    "DLROVER_TPU_PLANNER_COOLDOWN_S", 300.0, "float",
    "Seconds after an executed plan during which every decision is "
    "HOLD — at most one executed plan per cooldown window, so a noisy "
    "signal can never flap the fleet.",
)
PLANNER_HORIZON_S = _define(
    "DLROVER_TPU_PLANNER_HORIZON_S", 1800.0, "float",
    "Payback horizon: a resize is accepted only if its predicted "
    "throughput gain amortizes the measured resize downtime within "
    "this many seconds (ElasWave-style payback scoring).",
)
PLANNER_HYSTERESIS = _define(
    "DLROVER_TPU_PLANNER_HYSTERESIS", 2, "int",
    "Consecutive decisions the SAME winning candidate must survive "
    "before it becomes a plan; instability (stragglers, open downtime) "
    "resets the streak, so one healthy window never flips a decision.",
)
PLANNER_INTERVAL_S = _define(
    "DLROVER_TPU_PLANNER_INTERVAL_S", 30.0, "float",
    "Decision cadence: planner.sweep() no-ops until this many seconds "
    "passed since the last decision.",
)
PLANNER_HBM_GB = _define(
    "DLROVER_TPU_PLANNER_HBM_GB", 0.0, "float",
    "Per-device HBM capacity (GB) for the planner's shrink-feasibility "
    "gate: with it set, a candidate whose projected occupancy "
    "(reported used x world/world') lands inside the headroom reserve "
    "is rejected. 0 (default) = unknown — the gate is off and the "
    "trainer's own OOM recovery remains the backstop.",
)
PLANNER_DCN_GBPS = _define(
    "DLROVER_TPU_PLANNER_DCN_GBPS", 25.0, "float",
    "Assumed DCN bandwidth (GB/s) for converting the measured "
    "per-step dcn bytes into predicted step seconds at candidate "
    "worlds (the ICI/DCN byte model from ops/hier_collectives).",
)

LOCK_TRACKER = _define(
    "DLROVER_TPU_LOCK_TRACKER", False, "bool",
    "Runtime lock-discipline tracker (lint/lock_tracker.py): wraps the "
    "hot-path master locks and raises with BOTH acquisition stacks on "
    "any acquisition that contradicts the checked-in "
    "lint/lock_order.json acquisition graph. Off by default (zero "
    "overhead); the fleet harness arms it programmatically for the "
    "schedule-perturbation scenarios.",
)

# -- agent/master wiring (NodeEnv names; injected by the agent/launcher)

NODE_ID = _define(
    constants.NodeEnv.NODE_ID, 0, "int",
    "This worker's node id (agent-injected; node-check workloads and "
    "the master client identify themselves with it).",
)
PROCESS_ID = _define(
    constants.NodeEnv.PROCESS_ID, 0, "int",
    "This worker's process index within its node (agent-injected).",
)
MASTER_ADDR = _define(
    constants.NodeEnv.MASTER_ADDR, "", "str",
    "host:port of the job master's gRPC endpoint (agent-injected).",
)
JOB_NAME = _define(
    constants.NodeEnv.JOB_NAME, "local", "str",
    "Job name — keys the shm segments and the master's state backend.",
)
NODE_IP = _define(
    "DLROVER_TPU_NODE_IP", "", "str",
    "Override for this node's advertised IP (utils/net.py discovery).",
)
BRAIN_ADDR = _define(
    "DLROVER_TPU_BRAIN_ADDR", "", "str",
    "host:port of the brain optimizer service; empty = local heuristics.",
)
DIAG_INTERVAL = _define(
    "DLROVER_TPU_DIAG_INTERVAL", 60.0, "float",
    "Seconds between agent diagnosis collections.",
)
METRIC_ENDPOINTS = _define(
    "DLROVER_TPU_METRIC_ENDPOINTS", "", "str",
    "Comma-separated worker /metrics endpoints the agent scrapes.",
)
PARAL_CONFIG_PATH = _define(
    "DLROVER_TPU_PARAL_CONFIG_PATH", "", "str",
    "Path of the master-pushed runtime parallel-config JSON file.",
)
STATE_BACKEND = _define(
    "DLROVER_TPU_STATE_BACKEND", "", "str",
    "Master state backend kind (memory | file | configmap); empty "
    "picks the platform default.",
)
STATE_DIR = _define(
    "DLROVER_TPU_STATE_DIR", "", "str",
    "Root directory of the file state backend (master relaunch state).",
)
ELASTICJOB_NAME = _define(
    "ELASTICJOB_NAME", "", "str",
    "Name of this job's ElasticJob custom resource (k8s "
    "operator-injected; the master pod reads its own CR through it).",
)
POD_NAMESPACE = _define(
    "POD_NAMESPACE", "default", "str",
    "Kubernetes namespace this pod runs in (downward-API-injected).",
)
POD_IP = _define(
    "POD_IP", "", "str",
    "This pod's IP (downward-API-injected; master-address fallback "
    "when the job Service cannot be created).",
)
HOSTNAME = _define(
    "HOSTNAME", "", "str",
    "Pod hostname (k8s default env; last-resort master-address "
    "fallback after POD_IP).",
)
KUBERNETES_SERVICE_HOST = _define(
    "KUBERNETES_SERVICE_HOST", "", "str",
    "Kubernetes apiserver host (injected into every pod by kubelet; "
    "the in-cluster REST client's default endpoint).",
)
KUBERNETES_SERVICE_PORT = _define(
    "KUBERNETES_SERVICE_PORT", "443", "str",
    "Kubernetes apiserver port paired with KUBERNETES_SERVICE_HOST.",
)
TPU_LIBRARY_PATH = _define(
    "TPU_LIBRARY_PATH", "", "str",
    "Explicit libtpu path (JAX's own resolution variable); the "
    "profiler interposer reads it to find the real plugin to "
    "delegate to.",
)
K8S_INSECURE_TLS = _define(
    "DLROVER_TPU_K8S_INSECURE_TLS", "", "str",
    "Exactly '1' disables TLS verification toward the k8s apiserver "
    "(dev clusters with self-signed certs only).",
)
PEAK_TFLOPS = _define(
    "DLROVER_TPU_PEAK_TFLOPS", 0.0, "float",
    "Override for the accelerator's peak TFLOPs in MFU accounting "
    "(0 = use the built-in per-chip table).",
)
ACCELERATOR = _define(
    "DLROVER_TPU_ACCELERATOR", "", "str",
    "Override for the accelerator kind the profiler assumes "
    "(tpu | gpu | cpu; empty = autodetect).",
)

# -- node-check workload knobs (agent/node_check_workload.py)

CHECK_OUT = _define(
    "DLROVER_TPU_CHECK_OUT", "", "str",
    "File the node-check workload writes its result JSON to.",
)
CHECK_MATMUL_SIZE = _define(
    "DLROVER_TPU_CHECK_MATMUL_SIZE", 1024, "int",
    "Square matmul dimension of the node-check compute probe.",
)
CHECK_MATMUL_ITERS = _define(
    "DLROVER_TPU_CHECK_MATMUL_ITERS", 50, "int",
    "Iterations of the node-check compute probe.",
)
CHECK_PSUM_BYTES = _define(
    "DLROVER_TPU_CHECK_PSUM_BYTES", 1 << 22, "int",
    "Payload bytes of the node-check collective probe.",
)
MOCK_ERR_NODE = _define(
    "DLROVER_TPU_MOCK_ERR_NODE", "", "str",
    "Chaos hook: node id whose check should fail (tests).",
)
MOCK_SLOW_NODE = _define(
    "DLROVER_TPU_MOCK_SLOW_NODE", "", "str",
    "Chaos hook: node id whose check should straggle (tests).",
)
MOCK_SLOW_SECS = _define(
    "DLROVER_TPU_MOCK_SLOW_SECS", 5.0, "float",
    "Chaos hook: seconds the mock-slow node sleeps.",
)
