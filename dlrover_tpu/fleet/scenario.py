"""Declarative chaos scenarios: what the fleet looks like and what goes
wrong when (docs/design/fleet_harness.md, "scenario schema").

A scenario is data, not code — checked in (``fleet/scenarios.py``), or
loaded from a JSON file — so a failure model is reviewable, replayable
and diffable. All times are *virtual seconds* (``_vs``): the runner
advances a virtual clock tick by tick, so a 25-virtual-minute job with a
preemption storm replays in well under a real minute on CPU, and the
verdict is deterministic given ``seed``.

Fault classification (``FaultEvent.kind``):

- ``preempt`` — nodes report a preemption failure (the agent's SIGTERM
  grace path), die, and rejoin after ``duration_vs``;
- ``crash`` — like preempt but a worker-process crash (nonzero exit,
  restart-in-place); with ``at_step`` set it triggers when the global
  step crosses that step instead of at ``at_vs``;
- ``heartbeat_loss`` — nodes go silent without a failure report (hung
  process / dead host): the master must *evict* them by heartbeat
  timeout, and reconcile them if they return after ``duration_vs``;
- ``partition`` — the node's RPC link drops (reports raise): the node
  keeps trying; master-side it is indistinguishable from heartbeat
  loss, worker-side the client's backoff path is exercised;
- ``slow_link`` — delayed delivery: the node's messages are QUEUED and
  arrive ``factor`` virtual seconds late (± 25% jitter) on the
  master's clock — a latency distribution, not cadence stretching, so
  a lease renewal or heartbeat can genuinely arrive after its
  deadline;
- ``straggle`` — nodes' per-step wall time inflates by ``factor`` for
  ``duration_vs`` (their digests must trip the straggler detector, and
  one recovered window must unflag them);
- ``master_relaunch`` — the master process "dies" (SIGKILL semantics:
  whatever the last periodic state snapshot had is what survives) and a
  fresh master takes over ``duration_vs`` later on the same durable
  state backend.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

FAULT_KINDS = (
    "preempt",
    "crash",
    "heartbeat_loss",
    "partition",
    "slow_link",
    "straggle",
    "master_relaunch",
)


@dataclasses.dataclass
class FaultEvent:
    kind: str
    at_vs: float = 0.0
    #: explicit node ids; empty + count>0 -> seeded-random pick
    nodes: List[int] = dataclasses.field(default_factory=list)
    count: int = 0
    duration_vs: float = 0.0
    factor: float = 1.0
    at_step: int = -1  # crash-on-step trigger (kind "crash")

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; one of {FAULT_KINDS}"
            )

    def resolve_nodes(self, n_nodes: int, rng) -> List[int]:
        if self.nodes:
            return [i for i in self.nodes if 0 <= i < n_nodes]
        k = min(max(0, self.count), n_nodes)
        return sorted(rng.sample(range(n_nodes), k))


@dataclasses.dataclass
class Scenario:
    name: str = "scenario"
    seed: int = 0
    nodes: int = 100
    duration_vs: float = 600.0
    tick_vs: float = 1.0
    #: base per-step wall seconds (every worker's digest baseline)
    step_time_s: float = 1.0
    #: folded WorkerReport cadence (heartbeat + digest + resource)
    report_interval_vs: float = 15.0
    #: how often workers poll num_nodes_waiting (membership changes)
    membership_poll_vs: float = 10.0
    #: master-side eviction policy, in virtual seconds / sweeps
    heartbeat_timeout_vs: float = 60.0
    eviction_hysteresis: int = 2
    monitor_sweep_vs: float = 5.0
    #: master durable-state snapshot cadence (what a relaunch restores)
    state_save_vs: float = 5.0
    #: rendezvous: min nodes for a round (max is ``nodes``)
    min_nodes: Optional[int] = None
    #: admission gate cap for the loopback wire (reports; gets shed at 2x)
    gate_report_cap: int = 64
    #: >1 issues worker ticks from a thread pool (overload scenarios —
    #: exercises servicer concurrency at the cost of strict determinism)
    parallelism: int = 1
    # -- data plane (0 = off): the fleet leases a dataset through the
    # batched shard-lease protocol while training
    dataset_name: str = "fleet-train"
    dataset_size: int = 0
    shard_size: int = 100
    #: shards per lease_shards batch (the worker's prefetch depth)
    lease_count: int = 16
    #: lease TTL in virtual seconds (renewed by every WorkerReport)
    lease_ttl_vs: float = 60.0
    #: records each worker consumes per training step
    records_per_step: int = 0
    #: collective-hang watchdog window in virtual seconds (0 = the
    #: watchdog is not swept — PR 9 behavior)
    hang_window_vs: float = 0.0
    # -- goodput planner (brain/planner.py): armed, the master's scale
    # decisions come from the measured goodput ledger; scale-OUT waits
    # for an executed plan (rendezvous growth gate) and the runner
    # drives the autoscaler sweep on the virtual clock
    planner: bool = False
    #: cooldown between executed plans (at most one per window)
    planner_cooldown_vs: float = 120.0
    #: payback horizon the throughput gain must amortize the measured
    #: resize cost within
    planner_horizon_vs: float = 600.0
    #: consecutive decisions the same winning candidate must survive
    planner_hysteresis: int = 2
    #: decision cadence on the virtual clock
    planner_interval_vs: float = 15.0
    #: the job's parallel layout as a contract spec ("dp4xpp2") —
    #: reported to the master's SpeedMonitor, where the planner reads
    #: it: a pp fleet's resize candidates preserve the stage axis
    #: (per-stage dp rebalance), and every re-form re-reports the
    #: stage-preserving layout of the re-seated size. "" = the pure-dp
    #: default (pre-pp scenarios unchanged).
    layout_spec: str = ""
    # -- memcheck headroom oracle (lint/memcheck.py, the static OOM
    # veto): >0 arms the planner with a per-device HBM budget — every
    # candidate world is priced by the analytic component model and
    # over-budget candidates are refused with decision reason
    # ``oom_veto`` before any plan can admit them
    hbm_budget_gb: float = 0.0
    #: sharded model-state GB per CURRENT node (the oracle's global
    #: total is ``hbm_model_gb_per_node * nodes`` — a shrink packs it
    #: onto fewer devices, which is what makes a world over-budget)
    hbm_model_gb_per_node: float = 0.0
    #: fixed per-device arena GB (temp — does not shrink with world)
    hbm_fixed_gb: float = 0.0
    #: per-device HBM occupancy (MB) workers report in their folded
    #: WorkerReport (``tpu_hbm_used_mb`` — the measured leg)
    hbm_used_mb: float = 0.0
    # -- version skew (docs/design/wirecheck.md): simulate an N-1
    # binary on one side of the wire via the serde-level shim
    # (lint/skew_shim.py). "old_master": the master behaves like the
    # previous version — response fields it never knew are stripped
    # and request types it never knew are answered SimpleResponse
    # (workers must fall back, e.g. lease_shards -> get_task).
    # "old_workers": the fleet behaves like N-1 workers — they speak
    # the legacy control/data RPCs (heartbeat + per-task dispatch) and
    # their requests/responses are stripped of post-baseline fields.
    # Gates: exactly-once convergence and ZERO raw decode errors.
    skew_mode: str = ""
    #: message -> [fields] the N-1 side does not know; empty = derived
    #: from wire_schema.json's skew_guarded marks
    skew_drop: Dict = dataclasses.field(default_factory=dict)
    #: request message types the old master does not know at all
    skew_unknown: List[str] = dataclasses.field(default_factory=list)
    # -- adversarial schedule exploration (docs/design/racecheck.md):
    # drive the master's sweeps (deadline sweep, hang watchdog,
    # heartbeat evictor, shard-state writer drain, training-status
    # probe) at seeded-random points MID-RPC instead of only at tick
    # boundaries — interleavings the tick loop alone never exercises
    perturb_schedule: bool = False
    #: per-injection-point fire probability (two points per served RPC)
    perturb_prob: float = 0.02
    #: arm the runtime LockTracker (lint/lock_tracker.py) around the
    #: whole run; the verdict then gates on zero lock-order violations
    lock_tracker: bool = False
    faults: List[FaultEvent] = dataclasses.field(default_factory=list)
    #: verdict gates: the CLI exits nonzero when any fails
    expect: Dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.faults = [
            f if isinstance(f, FaultEvent) else FaultEvent(**f)
            for f in self.faults
        ]
        if self.skew_mode not in ("", "old_master", "old_workers"):
            raise ValueError(
                f"unknown skew_mode {self.skew_mode!r}; one of "
                "'', 'old_master', 'old_workers'"
            )

    @classmethod
    def from_dict(cls, d: Dict) -> "Scenario":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown scenario keys: {sorted(unknown)}")
        return cls(**d)

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def load_scenario(name_or_path: str) -> Scenario:
    """A built-in scenario name (``fleet/scenarios.py``) or a JSON file
    path with the same schema."""
    from dlrover_tpu.fleet.scenarios import BUILTIN

    if name_or_path in BUILTIN:
        return Scenario.from_dict(BUILTIN[name_or_path])
    if name_or_path.endswith(".json"):
        with open(name_or_path) as f:
            return Scenario.from_dict(json.load(f))
    raise ValueError(
        f"unknown scenario {name_or_path!r}; built-ins: "
        f"{sorted(BUILTIN)} (or a .json path)"
    )
