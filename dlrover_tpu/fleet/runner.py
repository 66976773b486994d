"""The fleet scenario runner: real master, virtual clock, injected
faults, goodput verdict.

Architecture (docs/design/fleet_harness.md):

- **Real master.** A :class:`LocalJobMaster` — the production servicer,
  rendezvous managers, SpeedMonitor/StragglerDetector, diagnosis
  manager and durable state backend — built with an injected *virtual*
  clock, so every goodput bracket, eviction decision and relaunch
  snapshot is stamped in scenario time and the verdict is deterministic
  given the scenario seed.
- **Simulated fleet.** ~1k :class:`SimWorker` state machines speaking
  the real serde wire through the real servicer via the in-process
  loopback (one admission gate shared fleet-wide, same class the gRPC
  server runs).
- **Tick loop.** Each tick advances the virtual clock, applies due
  fault events, advances the synchronous-training model (progress only
  while every live worker is seated in the current round), drives the
  due workers, runs the master's heartbeat-eviction sweep, and
  periodically snapshots master state (what a relaunch restores —
  SIGKILL semantics).
- **Verdict.** ``goodput`` + the lost-time ``attribution`` (must sum to
  elapsed), straggler flags, eviction/reconcile events, admission-gate
  stats and wire latency — checked against the scenario's ``expect``
  block. Trace artifacts (master downtime spans + fleet fault/stall
  lanes) dump for ``profiler.analysis job-timeline --check``.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.brain.planner import LEDGER_CAP
from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger
from dlrover_tpu.fleet.loopback import MasterEndpoint, RpcStats
from dlrover_tpu.fleet.scenario import FaultEvent, Scenario
from dlrover_tpu.fleet.worker import SimWorker
from dlrover_tpu.rpc.transport import RequestGate


#: how much planner ledger the runner tracks/verdicts — the planner's
#: own cap (imported), so the two can never drift: a smaller local cap
#: would silently drop decisions from the event log and digest
LEDGER_TRACK = LEDGER_CAP


class VirtualClock:
    """The scenario's "now": absolute epoch seconds (so trace artifacts
    merge like real ranks'), advanced only by the tick loop."""

    def __init__(self, start: Optional[float] = None):
        self._now = float(start if start is not None else time.time())

    def now(self) -> float:
        return self._now

    def set(self, t: float):
        self._now = float(t)


class FleetView:
    """What a worker may know of the job without private master state."""

    def __init__(self):
        self.global_step = 0
        self.training_active = False


class SchedulePerturber:
    """Adversarial schedule exploration (docs/design/racecheck.md).

    The tick loop runs every master sweep at tick boundaries, when no
    RPC is mid-flight — so the loopback proves the control plane's
    *logic*, never its interleavings. This hook runs on the loopback's
    pre/post-dispatch points and, with seeded probability, fires one of
    the master's background operations (the deadline sweep, the hang
    watchdog, the heartbeat evictor, the shard-state writer drain, the
    training-status probe) right there — in the middle of a logical
    RPC, on the virtual clock, with the LockTracker armed. Any lock
    acquisition the perturbed schedule makes in an order inconsistent
    with the global graph raises with both stacks and fails the
    verdict. Deterministic given the scenario seed (parallelism=1).

    ``ops`` is a plain list of (name, thunk) so a regression test can
    append a known-bad shape and prove the explorer + tracker catch it.
    """

    def __init__(self, runner: "FleetRunner", seed: int, prob: float):
        import random

        self._runner = runner
        self._rng = random.Random(seed ^ 0x5EED)
        self.prob = float(prob)
        self.fired: Dict[str, int] = {}
        self.errors: List[str] = []
        self._inside = False
        self.ops: List[Tuple[str, object]] = [
            ("deadline_sweep", self._deadline_sweep),
            ("hang_watchdog", self._hang_watchdog),
            ("heartbeat_evictor", self._evictor),
            ("writer_drain", self._writer_drain),
            ("finished_probe", self._finished_probe),
        ]

    # -- the injectable master ops -------------------------------------

    def _deadline_sweep(self, vt: float):
        self._runner.master.task_manager.sweep_deadlines(now=vt)

    def _hang_watchdog(self, vt: float):
        if self._runner.sc.hang_window_vs > 0:
            ev = self._runner.master.hang_watchdog.sweep(now=vt)
            if ev is not None:
                self._runner.note_hang(vt, ev)

    def _evictor(self, vt: float):
        evicted = self._runner.master.job_manager.sweep_heartbeats(now=vt)
        self._runner.note_evicted(vt, evicted)

    def _writer_drain(self, vt: float):
        self._runner.master.task_manager.flush_state()

    def _finished_probe(self, vt: float):
        # the TrainingStatusRequest path: TaskManager lock, then every
        # dataset's lock — the acquisition chain worth perturbing
        self._runner.master.task_manager.finished()

    # -- the loopback hook ---------------------------------------------

    def __call__(self, point: str, kind: str):
        if self._inside or self._runner.master is None:
            return
        if self._rng.random() >= self.prob:
            return
        name, op = self.ops[self._rng.randrange(len(self.ops))]
        self._inside = True  # an op's own RPCs must not recurse
        try:
            op(self._runner.clock.now())
            self.fired[name] = self.fired.get(name, 0) + 1
        except Exception as e:
            # a LockOrderViolation lands in tracker.violations too; the
            # perturber records the op so the verdict can attribute it
            self.errors.append(f"{name}@{point}/{kind}: {e}")
            self.fired[name] = self.fired.get(name, 0) + 1
        finally:
            self._inside = False

    def stats(self) -> Dict:
        return {
            "prob": self.prob,
            "fired": dict(sorted(self.fired.items())),
            "total": sum(self.fired.values()),
            "errors": list(self.errors[:16]),
        }


class FleetRunner:
    def __init__(self, scenario: Scenario, out_dir: Optional[str] = None):
        self.sc = scenario
        if scenario.perturb_schedule and scenario.parallelism > 1:
            # the perturber's seeded rng, recursion guard and fired
            # counters are single-threaded by design; a thread-pool
            # tick loop would silently break seed-determinism.
            # Validated before ANY side effect (tracker arming below)
            raise ValueError(
                "perturb_schedule requires parallelism=1 "
                f"(scenario has parallelism={scenario.parallelism})"
            )
        self.out_dir = out_dir or os.path.join(
            tempfile.gettempdir(), "dlrover_tpu_fleet", scenario.name
        )
        os.makedirs(self.out_dir, exist_ok=True)
        #: armed BEFORE anything below constructs a lock: the gate,
        #: endpoint and stats locks are born here in __init__, and a
        #: tracker installed later would miss them (maybe_track returns
        #: the raw lock). run() disarms on exit.
        self.tracker = None
        if scenario.lock_tracker:
            from dlrover_tpu.lint import lock_tracker as _lt

            self.tracker = _lt.LockTracker.from_lock_order()
            # record-only: a violation must land in the verdict, not
            # die inside a servicer handler's catch-all
            self.tracker.raise_on_violation = False
            _lt.install_tracker(self.tracker)
        self.clock = VirtualClock()
        self._base = self.clock.now()
        gate = RequestGate(report_cap=scenario.gate_report_cap)
        # same liveness-ceiling contract the real masters set on their
        # gate: backpressure never widens a worker past eviction
        gate.liveness_ceiling_s = scenario.heartbeat_timeout_vs / 3.0
        self.endpoint = MasterEndpoint(gate)
        self.stats = RpcStats()
        #: version-skew shim (docs/design/wirecheck.md): makes every
        #: worker's wire behave like an N-1 peer sits on the other end.
        #: Default drop set = the schema registry's skew_guarded fields
        #: — the checked-in record of what the previous version knew.
        self.shim = None
        if scenario.skew_mode:
            from dlrover_tpu.lint import wirecheck
            from dlrover_tpu.lint.skew_shim import SkewShim

            self.shim = SkewShim(
                scenario.skew_drop or wirecheck.skew_baseline_drops(),
                scenario.skew_unknown,
                label=scenario.skew_mode,
            )
        self.master = None
        self.workers: List[SimWorker] = []
        self.view = FleetView()
        self._progress = 0.0
        self._was_active = False
        self._stall_started_vt: Optional[float] = None
        self._stall_spans: List[Tuple[float, float, str]] = []
        self._fault_spans: List[Tuple[float, float, str]] = []
        self._events: List[str] = []
        self._evicted_ever: Dict[int, float] = {}
        self._reconciled: Dict[int, float] = {}
        self._stragglers_seen: set = set()
        self._hang_events: List[Dict] = []
        self._resumed_after_hang = False
        #: goodput-planner bookkeeping: decisions/executions already
        #: surfaced into the event log, and the seated-world timeline
        #: (vt, size) the adoption checks read
        self._planner_seen = 0
        self._executed_seen = 0
        self._world_timeline: List[Tuple[float, int]] = []
        self._relaunches = 0
        self._master_gap: Optional[Tuple[float, float]] = None
        self._archived_master_events: List[Dict] = []
        self._pool = (
            ThreadPoolExecutor(max_workers=scenario.parallelism)
            if scenario.parallelism > 1
            else None
        )
        #: mid-RPC schedule perturber (racecheck)
        self.perturber = (
            SchedulePerturber(self, scenario.seed, scenario.perturb_prob)
            if scenario.perturb_schedule
            else None
        )
        if self.perturber is not None:
            self.endpoint.perturb = self.perturber
        import random

        self._rng = random.Random(scenario.seed)
        # resolve the fault schedule up front (deterministic picks)
        self._schedule: List[Tuple[float, FaultEvent, List[int]]] = []
        self._step_triggers: List[Tuple[int, FaultEvent, List[int]]] = []
        for ev in scenario.faults:
            nodes = ev.resolve_nodes(scenario.nodes, self._rng)
            if ev.kind == "crash" and ev.at_step >= 0:
                self._step_triggers.append((ev.at_step, ev, nodes))
            else:
                self._schedule.append((ev.at_vs, ev, nodes))
        self._schedule.sort(key=lambda x: x[0])
        self._recoveries: List[Tuple[float, str, List[int]]] = []

    # -- lifecycle -----------------------------------------------------

    def _event(self, vt: float, text: str):
        line = f"{vt - self._base:9.1f}  {text}"
        self._events.append(line)
        logger.info("fleet: %s", line)

    def _boot_master(self):
        from dlrover_tpu.master.local_master import start_local_master

        master = start_local_master(
            node_num=self.sc.nodes,
            min_node_num=self.sc.min_nodes or self.sc.nodes,
            rdzv_waiting_timeout=5.0,
            heartbeat_timeout=self.sc.heartbeat_timeout_vs,
            clock=self.clock.now,
            eviction_hysteresis=self.sc.eviction_hysteresis,
            lease_ttl=self.sc.lease_ttl_vs,
            hang_window_s=self.sc.hang_window_vs or None,
            planner=self.sc.planner or None,
            planner_kwargs=self._planner_kwargs(),
        )
        # the runner drives every sweep on the virtual clock; second
        # wall-clock sweepers would add nondeterministic strikes,
        # expiries and hang declarations
        master.job_manager.pause_monitor()
        master.task_manager.pause_scan()
        master.hang_watchdog.pause()
        # the fleet's wire is the loopback: shed-aware liveness must
        # consult the gate the workers actually hit, stamped in
        # virtual time
        self.endpoint.gate.clock = self.clock.now
        master.job_manager.attach_gate(self.endpoint.gate)
        if self.sc.layout_spec:
            # seed the seated layout (what a real launcher passes the
            # master): the planner's candidates preserve its stage axis
            master.speed_monitor.report_layout(
                self._seated_layout(self.sc.nodes)
            )
        return master

    def _seated_layout(self, size: int) -> str:
        """The stage-preserving layout of a seated world of ``size``
        nodes, derived from the scenario's declared layout: a pp
        layout keeps its stage count and rebalances dp within stages
        (the engine's per-stage reshard), any other layout — or a size
        the stage count does not divide — degrades to pure dp."""
        from dlrover_tpu.common.world import WorldDescriptor

        try:
            declared = WorldDescriptor.parse(self.sc.layout_spec)
        except Exception:
            return f"dp{size}"
        pp = declared.pp
        if pp > 1 and size % pp == 0:
            return WorldDescriptor.from_axis_sizes(
                {"dp": size // pp, "pp": pp}
            ).spec
        return f"dp{size}"

    def _planner_kwargs(self):
        if not self.sc.planner:
            return None
        kwargs = {
            "cooldown_s": self.sc.planner_cooldown_vs,
            "horizon_s": self.sc.planner_horizon_vs,
            "hysteresis": self.sc.planner_hysteresis,
            "decide_interval_s": self.sc.planner_interval_vs,
        }
        if self.sc.hbm_budget_gb > 0:
            kwargs["headroom_oracle"] = self._headroom_oracle()
        return kwargs

    def _headroom_oracle(self):
        """The scenario-shaped static OOM veto (lint/memcheck.py): the
        sharded model state totals ``hbm_model_gb_per_node * nodes``
        globally (zero1-packed moments — a shrink divides it across
        fewer devices) on top of a fixed per-device arena. Candidate
        worlds whose per-device sum exceeds the budget less headroom
        are refused with decision reason ``oom_veto``."""
        from dlrover_tpu.common.world import WorldDescriptor
        from dlrover_tpu.lint.memcheck import HeadroomOracle

        sc = self.sc
        return HeadroomOracle(
            totals={
                "moments": sc.hbm_model_gb_per_node * sc.nodes * 1e9,
                "temp": sc.hbm_fixed_gb * 1e9,
            },
            base=WorldDescriptor.parse(f"dp{sc.nodes}"),
            budget_gb=sc.hbm_budget_gb,
            assume_zero1=True,
        )

    def _save_master_state(self):
        try:
            self.master.state_manager.save_speed(
                self.master.speed_monitor.export_state()
            )
            if self.master.planner is not None:
                # the decision ledger rides the same snapshot cadence:
                # a SIGKILLed master's successor resumes the cooldown
                # window instead of re-executing the last plan
                self.master.state_manager.save_planner(
                    self.master.planner.export_state()
                )
        except Exception:
            logger.exception("fleet: master state save failed")

    # -- fault application ---------------------------------------------

    def _apply_fault(self, vt: float, ev: FaultEvent, nodes: List[int]):
        off = vt - self._base
        if ev.kind == "master_relaunch":
            self._master_down(vt, ev.duration_vs)
            return
        self._event(
            vt, f"fault {ev.kind} nodes={_fmt_nodes(nodes)} "
            f"dur={ev.duration_vs:g} factor={ev.factor:g}"
        )
        self._fault_spans.append(
            (vt, vt + max(ev.duration_vs, self.sc.tick_vs),
             f"fault.{ev.kind}")
        )
        for nid in nodes:
            w = self.workers[nid]
            if ev.kind == "preempt":
                w.preempt(vt, vt + max(1.0, ev.duration_vs))
            elif ev.kind == "crash":
                w.crash(vt, vt + max(1.0, ev.duration_vs))
            elif ev.kind == "heartbeat_loss":
                w.go_silent(vt + ev.duration_vs)
            elif ev.kind == "partition":
                w.partition(vt + ev.duration_vs)
            elif ev.kind == "slow_link":
                # delayed delivery: factor virtual seconds of one-way
                # queued latency (±25% jitter), NOT cadence stretching
                w.set_link_latency(ev.factor, ev.factor / 4.0)
                self._recoveries.append(
                    (off + ev.duration_vs, "slow_link", [nid])
                )
            elif ev.kind == "straggle":
                w.set_straggle(ev.factor)
                self._recoveries.append(
                    (off + ev.duration_vs, "straggle", [nid])
                )

    def _apply_recoveries(self, off: float, vt: float):
        due = [r for r in self._recoveries if r[0] <= off]
        self._recoveries = [r for r in self._recoveries if r[0] > off]
        for _, kind, nodes in due:
            self._event(vt, f"recover {kind} nodes={_fmt_nodes(nodes)}")
            for nid in nodes:
                if kind == "slow_link":
                    self.workers[nid].set_link_latency(0.0)
                elif kind == "straggle":
                    self.workers[nid].set_straggle(1.0)

    def _master_down(self, vt: float, gap_vs: float):
        """SIGKILL semantics: the last periodic snapshot is all the next
        master gets; the gap is billed as downtime, backdated to that
        snapshot (the real relaunch path in ``prepare()``)."""
        self._event(vt, f"master killed (relaunch in {gap_vs:g} vs)")
        # archive the dying master's downtime spans for the timeline
        # (its own dump is overwritten by the relaunched master's in
        # this single-process harness)
        self._archived_master_events = self.master.speed_monitor.trace_events()
        self.endpoint.set_down()
        self.master.stop()
        # SIGKILL semantics: nothing of the dead master survives except
        # the last periodic snapshot — no further saves or sweeps
        self.master = None
        self._master_gap = (vt, vt + max(1.0, gap_vs))
        self._relaunches += 1

    def _maybe_master_up(self, vt: float):
        if self._master_gap is None or vt < self._master_gap[1]:
            return
        self._master_gap = None
        self.master = self._boot_master()
        self.endpoint.set_master(self.master.servicer)
        self._event(
            vt,
            f"master relaunched (restored step="
            f"{self.master.speed_monitor.completed_global_step})",
        )

    # -- training model ------------------------------------------------

    def _update_training(self, vt: float):
        # synchronous training: the CURRENT round's collective advances
        # only when every member of that round is seated AND healthy —
        # a member that died, partitioned or hung stalls everyone
        # (exactly the seated-but-stalled mode PR 9's model masked by
        # letting partitioned members keep "stepping"). Workers seated
        # in an OLDER round are hung in a dead collective: they neither
        # step nor block the re-formed world (they re-join via the
        # stale-round guard once reachable).
        seated = [w for w in self.workers if w.seated]
        members = []
        active = False
        if seated:
            cur = max(w.seated_round for w in seated)
            members = [w for w in seated if w.seated_round == cur]
            active = (
                len(members) == members[0].world_size
                and all(m.healthy_member for m in members)
            )
        if active and not self._was_active:
            for w in members:
                w.start_stepping()
            chief = next((w for w in members if w.is_chief), None)
            if chief is not None:
                # the bracket-closing report: the chief reports the step
                # the moment training resumes (sync_host_step parity)
                chief.force_report(vt)
            if self._stall_started_vt is not None:
                self._stall_spans.append(
                    (self._stall_started_vt, vt, "training.stall")
                )
                self._event(
                    vt,
                    f"training resumed after "
                    f"{vt - self._stall_started_vt:.1f} vs stall",
                )
                self._stall_started_vt = None
                if self._hang_events:
                    self._resumed_after_hang = True
            else:
                self._event(vt, "training started")
        elif not active and self._was_active:
            for w in self.workers:
                w.stop_stepping()
            self._stall_started_vt = vt
            self._event(vt, "training stalled (membership change)")
        self._was_active = active
        self.view.training_active = active
        if active:
            size = len(members)
            if (
                not self._world_timeline
                or self._world_timeline[-1][1] != size
            ):
                # the seated-world timeline the planner verdicts read
                # (capacity loss, gated waiting, adoption)
                self._world_timeline.append((vt, size))
                if self.sc.layout_spec and self.master is not None:
                    # every re-seated world re-reports its
                    # stage-preserving layout — the planner's next
                    # decision round scores candidates against the
                    # mesh the fleet actually re-formed to
                    self.master.speed_monitor.report_layout(
                        self._seated_layout(size)
                    )
            steps = self.sc.tick_vs / self.sc.step_time_s
            self._progress += steps
            self.view.global_step = int(self._progress)
            for w in members:
                if w.stepping:
                    w.accrue_steps(steps)

    # -- tick loop -----------------------------------------------------

    def run(self) -> Dict:
        sc = self.sc
        t_real0 = time.time()
        stack = contextlib.ExitStack()
        if self.tracker is not None:
            from dlrover_tpu.lint import lock_tracker as _lt

            stack.callback(_lt.install_tracker, None)
        with stack:
            # pinned runtime environment: durable file state backend for
            # relaunch continuity, trace spine into the run's out_dir —
            # an operator's exported values must not leak in
            stack.enter_context(
                flags.JOB_NAME.scoped(f"fleet-{sc.name}")
            )
            stack.enter_context(flags.STATE_BACKEND.scoped("file"))
            stack.enter_context(
                flags.STATE_DIR.scoped(os.path.join(self.out_dir, "state"))
            )
            stack.enter_context(flags.TRACE.scoped("1"))
            stack.enter_context(
                flags.TRACE_DIR.scoped(os.path.join(self.out_dir, "traces"))
            )
            # fresh state dir per run: SIGKILL continuity is within a
            # run, not across runs
            import shutil

            shutil.rmtree(
                os.path.join(self.out_dir, "state"), ignore_errors=True
            )
            shutil.rmtree(
                os.path.join(self.out_dir, "traces"), ignore_errors=True
            )
            self.master = self._boot_master()
            self.endpoint.set_master(self.master.servicer)
            if sc.dataset_size > 0:
                # the data plane under test: the fleet leases this
                # dataset through the batched shard-lease protocol (a
                # relaunched master restores it from the state backend)
                from dlrover_tpu.common.messages import DatasetShardParams

                self.master.task_manager.new_dataset(DatasetShardParams(
                    dataset_name=sc.dataset_name,
                    dataset_size=sc.dataset_size,
                    shard_size=sc.shard_size,
                ))
            self.workers = [
                SimWorker(i, sc, self.endpoint, self.stats,
                          shim=self.shim)
                for i in range(sc.nodes)
            ]
            self._event(self._base, f"fleet up: {sc.nodes} workers")
            try:
                verdict = self._loop(t_real0)
            finally:
                if self.master is not None:
                    self._save_master_state()
                    self.master.stop()
                self._dump_fleet_trace()
                if self._pool is not None:
                    self._pool.shutdown(wait=False)
        return verdict

    def _loop(self, t_real0: float) -> Dict:
        sc = self.sc
        next_sweep = sc.monitor_sweep_vs
        next_save = sc.state_save_vs
        n_ticks = int(sc.duration_vs / sc.tick_vs)
        schedule = list(self._schedule)
        for tick in range(n_ticks):
            off = (tick + 1) * sc.tick_vs
            vt = self._base + off
            self.clock.set(vt)
            while schedule and schedule[0][0] <= off:
                _, ev, nodes = schedule.pop(0)
                self._apply_fault(vt, ev, nodes)
            for at_step, ev, nodes in list(self._step_triggers):
                if self.view.global_step >= at_step:
                    self._step_triggers.remove((at_step, ev, nodes))
                    self._event(vt, f"crash-on-step {at_step}")
                    self._apply_fault(vt, ev, nodes)
            self._apply_recoveries(off, vt)
            self._maybe_master_up(vt)
            self._update_training(vt)
            self._tick_workers(vt)
            if self.master is not None:
                # lease/task deadline sweep (the deadline heap: O(due)
                # per tick, not a walk of every in-flight shard)
                self.master.task_manager.sweep_deadlines(now=vt)
                if self.sc.hang_window_vs > 0:
                    ev = self.master.hang_watchdog.sweep(now=vt)
                    if ev is not None:
                        self.note_hang(vt, ev)
                # drain the coalescing shard-state writer at the tick
                # boundary: models its sub-ms drain deterministically,
                # so a SIGKILL between ticks restores exactly the acked
                # counts the workers observed (the exactly-once gate
                # across a master relaunch depends on this ordering)
                self.master.task_manager.flush_state()
            if self.master is not None and off >= next_sweep:
                next_sweep += sc.monitor_sweep_vs
                evicted = self.master.job_manager.sweep_heartbeats(now=vt)
                self.note_evicted(vt, evicted)
                self._track_reconciles(vt)
                for nid in self.master.speed_monitor.stragglers():
                    self._stragglers_seen.add(nid)
                if self.master.auto_scaler is not None:
                    # the planner's decide→act cycle on the virtual
                    # clock (throttled internally by its interval)
                    self.master.auto_scaler.sweep(now=vt)
                    self._track_planner(vt)
            if self.master is not None and off >= next_save:
                next_save += sc.state_save_vs
                self._save_master_state()
        return self._verdict(self._base + n_ticks * sc.tick_vs, t_real0)

    def _tick_workers(self, vt: float):
        if self._pool is None:
            for w in self.workers:
                w.tick(vt, self.view)
        else:
            # shuffled issue order: real fleets have no global arrival
            # order; a fixed id-ordered map would systematically land
            # the tail of the list on a full admission gate every tick
            # and starve the same workers into eviction
            order = list(self.workers)
            self._rng.shuffle(order)
            list(self._pool.map(lambda w: w.tick(vt, self.view), order))

    def _track_planner(self, vt: float):
        """Surface new planner decisions/executions into the event log
        (and so into the determinism digest): the goodput planner's
        choices must be as replayable as the faults that provoked them."""
        planner = self.master.planner if self.master else None
        if planner is None:
            return
        rep = planner.report(last_n=LEDGER_TRACK)
        new = rep["total"] - self._planner_seen
        if new > 0:
            for rec in rep["last"][-new:]:
                if rec["verdict"] != "hold":
                    self._event(
                        vt,
                        f"planner {rec['verdict'].upper()} "
                        f"{rec['current_world']} -> {rec['target']} "
                        f"({rec['reason']})",
                    )
            self._planner_seen = rep["total"]
        if len(rep["executed"]) > self._executed_seen:
            for ex in rep["executed"][self._executed_seen:]:
                self._event(
                    vt,
                    f"planner plan executed: workers -> "
                    f"{ex['target_world']} ({ex['target']})",
                )
            self._executed_seen = len(rep["executed"])

    def note_hang(self, vt: float, ev: Dict):
        """Record one hang-watchdog declaration (tick loop or a
        perturbed mid-RPC sweep — same bookkeeping either way)."""
        self._hang_events.append({**ev, "off": round(vt - self._base, 1)})
        self._event(
            vt,
            f"collective hang declared (stall {ev['stall_s']:.0f} vs, "
            f"silent members {ev['silent'] or 'none'})",
        )

    def note_evicted(self, vt: float, evicted):
        for nid in evicted:
            # FIRST eviction only: under sustained overload a
            # reconciled worker whose every report is shed can be
            # legitimately re-evicted (the gate sheds before
            # deserializing, so the master cannot know who it
            # silenced) — the hysteresis-latency check measures the
            # original silence episode
            self._evicted_ever.setdefault(nid, vt)
            from dlrover_tpu.common.constants import NodeType
            from dlrover_tpu.master.node.job_context import get_job_context

            node = get_job_context().get_node(NodeType.WORKER, nid)
            hb_off = (
                round(node.heartbeat_time - self._base, 1)
                if node is not None else None
            )
            self._event(
                vt, f"master evicted node {nid} (last hb {hb_off})"
            )

    def _track_reconciles(self, vt: float):
        from dlrover_tpu.common.constants import NodeStatus, NodeType
        from dlrover_tpu.master.node.job_context import get_job_context

        ctx = get_job_context()
        for nid in self._evicted_ever:
            if nid in self._reconciled:
                continue
            node = ctx.get_node(NodeType.WORKER, nid)
            if node is not None and node.status == NodeStatus.RUNNING:
                self._reconciled[nid] = vt
                self._event(vt, f"master reconciled node {nid}")

    # -- verdict -------------------------------------------------------

    def _verdict(self, end_vt: float, t_real0: float) -> Dict:
        sm = self.master.speed_monitor if self.master else None
        attribution = sm.attribution(now=end_vt) if sm else {}
        goodput = sm.goodput(now=end_vt) if sm else 0.0
        downtime = sm.total_downtime(now=end_vt) if sm else 0.0
        cats = attribution.get("categories", {})
        cat_sum = sum(cats.values())
        elapsed = attribution.get("elapsed_wall_s", 0.0)
        planner_section = self._planner_verdict()
        digest = hashlib.sha256()
        for line in self._events:
            digest.update(line.encode())
        digest.update(f"goodput={goodput:.4f}".encode())
        digest.update(f"downtime={downtime:.1f}".encode())
        if planner_section:
            # the decision ledger is part of the replayable record: a
            # planner whose decisions drift across identical seeds
            # fails the determinism gate, not just the timing checks
            digest.update(planner_section["ledger_digest"].encode())
        verdict = {
            "scenario": self.sc.name,
            "seed": self.sc.seed,
            "nodes": self.sc.nodes,
            "duration_vs": self.sc.duration_vs,
            "wall_real_s": round(time.time() - t_real0, 1),
            "goodput": round(goodput, 6),
            "downtime_vs": round(downtime, 3),
            "global_step": sm.completed_global_step if sm else 0,
            "attribution": attribution,
            "attribution_sum_error": (
                round(abs(cat_sum - elapsed) / elapsed, 6)
                if elapsed > 0 else 0.0
            ),
            "downtime_breakdown": sm.downtime_breakdown() if sm else {},
            "stragglers_flagged": sorted(self._stragglers_seen),
            "straggler_report": sm.straggler_report() if sm else {},
            "evictions": {
                str(k): round(v - self._base, 1)
                for k, v in sorted(self._evicted_ever.items())
            },
            "reconciled": {
                str(k): round(v - self._base, 1)
                for k, v in sorted(self._reconciled.items())
            },
            "master_relaunches": self._relaunches,
            "hangs": {
                "events": list(self._hang_events),
                "recovered": self._resumed_after_hang,
            },
            "data_plane": self._data_verdict(),
            "version_skew": self._skew_verdict(),
            "planner": planner_section,
            "lock_tracker": self._tracker_verdict(),
            "schedule_perturbation": (
                self.perturber.stats() if self.perturber else {}
            ),
            "gate": self.endpoint.gate.stats(),
            "rpc": self.stats.snapshot(),
            "worker_reports": {
                "sent": sum(w.reports_sent for w in self.workers),
                "failed": sum(w.reports_failed for w in self.workers),
                "widened_intervals": sum(
                    1 for w in self.workers if w.interval.widen_events > 0
                ),
                "max_interval_s": round(
                    max(w.interval.current_s for w in self.workers), 2
                ) if self.workers else 0.0,
            },
            "events": self._events,
            "determinism_digest": digest.hexdigest()[:16],
        }
        verdict["checks"] = self._checks(verdict)
        verdict["ok"] = all(c["ok"] for c in verdict["checks"].values())
        return verdict

    def _data_verdict(self) -> Dict:
        """The data plane's ledger: every worker records a shard range
        into ``acked_ranges`` only when the master's fenced ack
        confirmed the count. Exactly-once = the sorted ranges tile
        [0, dataset_size) with no overlap and no gap, AND the master's
        ``completed_records`` agrees."""
        sc = self.sc
        if sc.dataset_size <= 0:
            return {}
        ranges = sorted(
            r for w in self.workers for r in w.acked_ranges
        )
        overlaps = gaps = 0
        pos = 0
        for s, e in ranges:
            if s < pos:
                overlaps += 1
            elif s > pos:
                gaps += 1
            pos = max(pos, e)
        completed = (
            self.master.task_manager.completed_records(sc.dataset_name)
            if self.master is not None else -1
        )
        shards = -(-sc.dataset_size // sc.shard_size)  # ceil
        rpcs = sum(w.data_rpcs for w in self.workers)
        baseline = 2 * shards  # one get_task + one report per shard
        return {
            "dataset_size": sc.dataset_size,
            "shards": shards,
            "acked_ranges": len(ranges),
            "acked_records": pos if not gaps and not overlaps else sum(
                e - s for s, e in ranges
            ),
            "overlaps": overlaps,
            "gaps": gaps,
            "master_completed_records": completed,
            "rpcs": rpcs,
            "baseline_rpcs": baseline,
            "rpc_ratio": round(rpcs / baseline, 4) if baseline else 0.0,
            "workers_exhausted": sum(
                1 for w in self.workers if w.exhausted
            ),
        }

    def _skew_verdict(self) -> Dict:
        """The version_skew evidence: what the shim actually stripped
        and refused, how many workers fell back to the legacy
        protocols, and — the headline gate — how many RAW decode
        errors the client side of the wire saw (must be zero: every
        skewed exchange degrades through a typed path)."""
        if self.shim is None:
            return {}
        s = self.shim.stats()
        return {
            "mode": self.sc.skew_mode,
            "stripped_fields": s["stripped_fields"],
            "unknown_replies": s["unknown_replies"],
            "drop_rules": s["drop_rules"],
            "unknown_types": s["unknown_types"],
            "lease_fallbacks": sum(
                w.lease_fallbacks for w in self.workers
            ),
            "legacy_data_workers": sum(
                1 for w in self.workers if w.legacy_data
            ),
            "legacy_control_workers": sum(
                1 for w in self.workers if w.legacy_control
            ),
            "decode_errors": self.stats.snapshot()["decode_errors"],
        }

    def _planner_verdict(self) -> Dict:
        """The goodput planner's ledger as verdict evidence: decision
        counts, every execution, the seated-world timeline, and a
        content digest of the full decision ledger (the bit-determinism
        gate hashes it)."""
        if not self.sc.planner:
            return {}
        planner = self.master.planner if self.master else None
        if planner is None:
            return {"armed": True, "ledger_digest": "no-master"}
        rep = planner.report(last_n=LEDGER_TRACK)
        state = planner.export_state()

        def rebased(rec):
            # the ledger stamps absolute virtual-epoch seconds (so it
            # merges with trace artifacts); the determinism digest must
            # hash OFFSETS — the epoch base is wall-sampled per run
            rec = json.loads(json.dumps(rec))
            if "ts" in rec:
                rec["ts"] = round(rec["ts"] - self._base, 3)
            if isinstance(rec.get("inputs"), dict) and "ts" in rec["inputs"]:
                rec["inputs"]["ts"] = round(
                    rec["inputs"]["ts"] - self._base, 3
                )
            return rec

        ledger_digest = hashlib.sha256(
            json.dumps(
                [rebased(r) for r in state["ledger"]], sort_keys=True
            ).encode()
        ).hexdigest()[:16]
        # the memcheck OOM-veto evidence (.get: pre-veto ledgers and
        # records restored from an old snapshot carry no "vetoes" key)
        veto_recs = [
            v for r in state["ledger"] for v in (r.get("vetoes") or [])
        ]
        return {
            "armed": True,
            "decisions_total": rep["total"],
            "counts": rep["counts"],
            "oom_vetoes": len(veto_recs),
            "vetoed_worlds": sorted(
                {int(v["world"]) for v in veto_recs}
            ),
            "executed": [
                {
                    "target": ex["target"],
                    "target_world": ex["target_world"],
                    "off": round(ex["ts"] - self._base, 1),
                }
                for ex in rep["executed"]
            ],
            "intent": rep["intent"],
            # the seated layout the monitor is reporting at verdict
            # time (stage-preserving across re-forms when the scenario
            # declares a pp layout)
            "layout": (
                self.master.speed_monitor.layout_spec()
                if self.master else ""
            ),
            "ledger_digest": ledger_digest,
            "world_timeline": [
                [round(vt - self._base, 1), size]
                for vt, size in self._world_timeline
            ],
        }

    def _tracker_verdict(self) -> Dict:
        if self.tracker is None:
            return {}
        snap = self.tracker.snapshot()
        return {
            "armed": True,
            "acquisitions": snap["acquisitions"],
            "observed_edges": len(snap["observed_edges"]),
            "violations": snap["violations"],
        }

    def _checks(self, v: Dict) -> Dict:
        exp = self.sc.expect or {}
        checks: Dict[str, Dict] = {}

        def check(name, ok, got, want):
            checks[name] = {"ok": bool(ok), "got": got, "want": want}

        tol = float(exp.get("attribution_sum_tol", 0.01))
        check(
            "attribution_sums_to_elapsed",
            v["attribution_sum_error"] <= tol,
            v["attribution_sum_error"], f"<= {tol}",
        )
        if "goodput_min" in exp:
            check(
                "goodput", v["goodput"] >= exp["goodput_min"],
                v["goodput"], f">= {exp['goodput_min']}",
            )
        if "max_rpc_latency_s" in exp:
            check(
                "rpc_latency_bounded",
                v["rpc"]["max_latency_s"] <= exp["max_rpc_latency_s"],
                round(v["rpc"]["max_latency_s"], 4),
                f"<= {exp['max_rpc_latency_s']}",
            )
        if "max_p99_latency_s" in exp:
            # the SpeedMonitor lock-split evidence: servicer p99 under
            # combined report+lease load stays flat at fleet scale
            check(
                "rpc_p99_bounded",
                v["rpc"]["p99_latency_s"] <= exp["max_p99_latency_s"],
                v["rpc"]["p99_latency_s"],
                f"<= {exp['max_p99_latency_s']}",
            )
        dp = v.get("data_plane") or {}
        if exp.get("data_exactly_once"):
            ok = (
                dp.get("overlaps", 1) == 0
                and dp.get("gaps", 1) == 0
                and dp.get("acked_records") == dp.get("dataset_size")
                and dp.get("master_completed_records")
                == dp.get("dataset_size")
            )
            check(
                "records_delivered_exactly_once", ok,
                {k: dp.get(k) for k in (
                    "acked_records", "overlaps", "gaps",
                    "master_completed_records",
                )},
                f"every record of {dp.get('dataset_size')} counted once",
            )
        if "max_data_rpc_ratio" in exp:
            check(
                "data_plane_rpc_budget",
                dp.get("rpc_ratio", 1.0) <= exp["max_data_rpc_ratio"],
                dp.get("rpc_ratio"),
                f"<= {exp['max_data_rpc_ratio']} of the per-task baseline",
            )
        vs = v.get("version_skew") or {}
        if vs:
            # the wirecheck runtime gates: every skewed exchange must
            # degrade through a typed path — a single raw decode error
            # client-side fails the scenario — and the shim must have
            # actually exercised the skew (a drop map that never fires
            # proves nothing)
            check(
                "skew_no_raw_decode_errors",
                vs["decode_errors"] == 0,
                vs["decode_errors"], "== 0",
            )
            check(
                "skew_exercised", vs["stripped_fields"] > 0,
                vs["stripped_fields"], "> 0 fields stripped",
            )
        if "min_lease_fallbacks" in exp:
            check(
                "lease_fallback_engaged",
                vs.get("lease_fallbacks", 0) >= exp["min_lease_fallbacks"],
                vs.get("lease_fallbacks", 0),
                f">= {exp['min_lease_fallbacks']}",
            )
        if "min_unknown_replies" in exp:
            check(
                "unknown_types_answered_old_way",
                vs.get("unknown_replies", 0) >= exp["min_unknown_replies"],
                vs.get("unknown_replies", 0),
                f">= {exp['min_unknown_replies']}",
            )
        hangs = v.get("hangs") or {}
        if "min_hangs" in exp:
            check(
                "collective_hang_detected",
                len(hangs.get("events", [])) >= exp["min_hangs"],
                len(hangs.get("events", [])), f">= {exp['min_hangs']}",
            )
        if "hang_detect_within_vs" in exp:
            stall_at = min(
                (ev.at_vs for ev in self.sc.faults
                 if ev.kind in ("partition", "heartbeat_loss")),
                default=0.0,
            )
            first = (
                hangs["events"][0]["off"] if hangs.get("events")
                else float("inf")
            )
            check(
                "hang_detected_within_window",
                first - stall_at <= exp["hang_detect_within_vs"],
                round(first - stall_at, 1),
                f"<= {exp['hang_detect_within_vs']}",
            )
        if exp.get("require_hang_recovery"):
            check(
                "round_recovered_after_hang",
                bool(hangs.get("recovered")),
                hangs.get("recovered"), True,
            )
        cats = v["attribution"].get("categories", {})
        if "min_collective_hang_s" in exp:
            check(
                "hang_attributed_not_unattributed",
                cats.get("collective_hang", 0.0)
                >= exp["min_collective_hang_s"]
                and cats.get("unattributed", 0.0)
                <= cats.get("collective_hang", 0.0),
                {
                    "collective_hang": round(
                        cats.get("collective_hang", 0.0), 1
                    ),
                    "unattributed": round(
                        cats.get("unattributed", 0.0), 1
                    ),
                },
                f"collective_hang >= {exp['min_collective_hang_s']} "
                f"and >= unattributed",
            )
        if "min_sheds" in exp:
            total_rej = sum(v["gate"]["rejected"].values())
            check(
                "gate_shed_load", total_rej >= exp["min_sheds"],
                total_rej, f">= {exp['min_sheds']}",
            )
        if "min_widened_workers" in exp:
            check(
                "overload_honored",
                v["worker_reports"]["widened_intervals"]
                >= exp["min_widened_workers"],
                v["worker_reports"]["widened_intervals"],
                f">= {exp['min_widened_workers']}",
            )
        if "evict_nodes" in exp:
            want = sorted(int(n) for n in exp["evict_nodes"])
            got = sorted(int(n) for n in v["evictions"])
            missing = [n for n in want if n not in got]
            check(
                "evicted_silent_workers", not missing, got,
                f"includes {want}",
            )
            # under sustained TOTAL overload the shed-blind evictor can
            # starve an occasional live worker into eviction (the gate
            # sheds before it can see who it silenced — known gap,
            # docs/design/fleet_harness.md); the designed guarantee is
            # that such evictions are rare and self-heal by
            # reconciliation, so the verdict bounds them instead of
            # pretending they cannot happen
            spurious = [n for n in got if n not in want]
            cap = int(exp.get("max_spurious_evictions", 0))
            check(
                "spurious_evictions_bounded", len(spurious) <= cap,
                spurious, f"<= {cap} nodes",
            )
        if "evict_within_vs" in exp and "evict_nodes" in exp:
            # eviction latency of the TARGETED silent nodes relative to
            # the fault that silenced them
            silence_at = min(
                ev.at_vs for ev in self.sc.faults
                if ev.kind in ("heartbeat_loss", "partition")
            )
            times = [
                v["evictions"][str(n)]
                for n in exp["evict_nodes"]
                if str(n) in v["evictions"]
            ]
            worst = (max(times) - silence_at) if times else float("inf")
            check(
                "evicted_within_hysteresis_window",
                worst <= exp["evict_within_vs"],
                round(worst, 1), f"<= {exp['evict_within_vs']}",
            )
        if exp.get("require_reconcile"):
            # a worker evicted in the last moments has no time left to
            # land the reconciling report; only settled evictions gate
            settled = {
                n for n, t in v["evictions"].items()
                if t <= self.sc.duration_vs - 10
            }
            missing = sorted(settled - set(v["reconciled"]))
            check("evicted_workers_reconciled", not missing, missing, [])
        if "stragglers" in exp:
            want = sorted(int(n) for n in exp["stragglers"])
            check(
                "stragglers_flagged",
                v["stragglers_flagged"] == want,
                v["stragglers_flagged"], want,
            )
        if "relaunches" in exp:
            check(
                "master_relaunches",
                v["master_relaunches"] == exp["relaunches"],
                v["master_relaunches"], exp["relaunches"],
            )
        lt = v.get("lock_tracker") or {}
        if lt.get("armed"):
            # the tracker-clean gate: a perturbed schedule that takes
            # any lock against the global order fails the scenario,
            # with the offending pair named in the verdict
            check(
                "lock_discipline_clean",
                not lt["violations"] and lt["acquisitions"] > 0,
                {"violations": lt["violations"],
                 "acquisitions": lt["acquisitions"]},
                "0 violations over >0 tracked acquisitions",
            )
        sp = v.get("schedule_perturbation") or {}
        if sp:
            # every perturbed op must have RUN clean: an op that raised
            # still counts toward `fired`, so without this gate a
            # crashing mid-RPC sweep would pass CI invisibly
            check(
                "perturbed_ops_clean", not sp.get("errors"),
                sp.get("errors"), "no perturbed op raised",
            )
        if "min_perturbations" in exp:
            # the explorer actually explored: sweeps fired mid-RPC, not
            # just at tick boundaries
            check(
                "schedule_explored",
                sp.get("total", 0) >= exp["min_perturbations"],
                sp.get("total", 0), f">= {exp['min_perturbations']}",
            )
        pl = v.get("planner") or {}
        if pl.get("armed"):
            executed = pl.get("executed") or []
            # one plan per cooldown window, by construction AND by
            # evidence: consecutive executions must be >= cooldown apart
            gaps = [
                round(b["off"] - a["off"], 1)
                for a, b in zip(executed, executed[1:])
            ]
            check(
                "one_plan_per_cooldown_window",
                all(g >= self.sc.planner_cooldown_vs for g in gaps),
                {"executed_offs": [e["off"] for e in executed],
                 "gaps": gaps},
                f"gaps >= {self.sc.planner_cooldown_vs}",
            )
            if "min_oom_vetoes" in exp:
                # the static headroom oracle actually refused work: at
                # least this many over-budget candidates were priced
                # out with decision reason oom_veto
                check(
                    "oom_candidates_vetoed",
                    pl.get("oom_vetoes", 0) >= exp["min_oom_vetoes"],
                    pl.get("oom_vetoes", 0),
                    f">= {exp['min_oom_vetoes']}",
                )
            if exp.get("no_oom_world_admitted"):
                # ZERO OOM-class admissions: no executed plan ever
                # targeted a world the oracle vetoed in ANY round
                vetoed_worlds = set(pl.get("vetoed_worlds") or [])
                admitted = [
                    e for e in executed
                    if e["target_world"] in vetoed_worlds
                ]
                check(
                    "no_oom_world_admitted", not admitted, admitted,
                    f"no executed plan into {sorted(vetoed_worlds)}",
                )
            if "max_executed_plans" in exp:
                check(
                    "executed_plans_bounded",
                    len(executed) <= exp["max_executed_plans"],
                    len(executed), f"<= {exp['max_executed_plans']}",
                )
            if "min_executed_plans" in exp:
                check(
                    "planner_actually_acted",
                    len(executed) >= exp["min_executed_plans"],
                    len(executed), f">= {exp['min_executed_plans']}",
                )
            if "executed_target_specs" in exp:
                # every executed plan named EXACTLY the layout the
                # scenario demands, in order — a pp fleet's readopt
                # must target the stage-preserving spec (per-stage dp
                # rebalance), never a flattened pure-dp world
                got = [e["target"] for e in executed]
                check(
                    "executed_plans_target_declared_layouts",
                    got == exp["executed_target_specs"],
                    got, f"== {exp['executed_target_specs']}",
                )
            if "unstable_windows" in exp:
                # NO plan may execute while the fleet is unstable (the
                # scenario names its instability windows explicitly so
                # the gate is reviewable)
                bad = [
                    e["off"] for e in executed
                    if any(
                        s <= e["off"] <= t
                        for s, t in exp["unstable_windows"]
                    )
                ]
                check(
                    "no_scaleout_while_unstable", not bad, bad,
                    f"no execution inside {exp['unstable_windows']}",
                )
            timeline = pl.get("world_timeline") or []
            full_at = None
            dropped = False
            for off, size in timeline:
                if size < self.sc.nodes:
                    dropped = True
                elif dropped and size >= self.sc.nodes:
                    full_at = off
                    break
            if "readopt_by_vs" in exp:
                check(
                    "restored_capacity_adopted_in_time",
                    full_at is not None
                    and full_at <= exp["readopt_by_vs"],
                    full_at, f"<= {exp['readopt_by_vs']}",
                )
            if "readopt_not_before_vs" in exp:
                # the growth gate's evidence: waiting capacity was NOT
                # adopted during the instability window — full world
                # reappears only after the planner approved it
                check(
                    "growth_gated_until_stable",
                    full_at is None
                    or full_at >= exp["readopt_not_before_vs"],
                    full_at, f">= {exp['readopt_not_before_vs']}",
                )
        if exp.get("master_survives"):
            served = sum(v["gate"]["served"].values())
            check(
                "master_stayed_live",
                self.master is not None and served > 0
                and v["global_step"] > 0,
                {"served": served, "step": v["global_step"]},
                "served > 0 and step > 0",
            )
        return checks

    # -- trace artifacts -----------------------------------------------

    def _dump_fleet_trace(self):
        """The harness's own job-timeline source: training-stall spans
        and fault windows, each fault on its own lane so spans nest
        trivially; plus the pre-relaunch master's archived downtime
        brackets (its file was overwritten by the relaunched master)."""
        from dlrover_tpu.observability import trace

        events: List[Dict] = []
        for s, e, name in self._stall_spans:
            events.append({
                "name": name, "cat": "downtime", "ph": "X",
                "ts": int(s * 1e6), "dur": int(max(0.0, e - s) * 1e6),
                "pid": 0, "tid": 1, "args": {"kind": "downtime"},
            })
        for i, (s, e, name) in enumerate(self._fault_spans):
            events.append({
                "name": name, "cat": "fault", "ph": "X",
                "ts": int(s * 1e6), "dur": int(max(0.0, e - s) * 1e6),
                "pid": 0, "tid": 100 + i, "args": {"kind": "host"},
            })
        for i, ev in enumerate(self._archived_master_events):
            ev = dict(ev)
            ev["tid"] = 50  # own lane, clear of the stall lane
            events.append(ev)
        # the goodput planner's decisions as their own timeline lane:
        # HOLDs and RESIZEs on tid 60, executed plans on tid 61 —
        # sequential in virtual time, so spans never overlap per lane
        planner = self.master.planner if self.master else None
        if planner is not None:
            rep = planner.report(last_n=LEDGER_TRACK)
            for rec in rep["last"]:
                events.append({
                    "name": (
                        f"planner.{rec['verdict']}"
                        + (f"->{rec['target']}" if rec["target"] else "")
                    ),
                    "cat": "planner", "ph": "X",
                    "ts": int(rec["ts"] * 1e6),
                    "dur": int(0.5 * 1e6),
                    "pid": 0, "tid": 60,
                    "args": {
                        "kind": "host", "reason": rec["reason"],
                        "current_world": rec["current_world"],
                        "target": rec["target"],
                    },
                })
            for ex in rep["executed"]:
                events.append({
                    "name": f"planner.execute->{ex['target']}",
                    "cat": "planner", "ph": "X",
                    "ts": int(ex["ts"] * 1e6),
                    "dur": int(0.5 * 1e6),
                    "pid": 0, "tid": 61,
                    "args": {"kind": "host",
                             "target_world": ex["target_world"]},
                })
        try:
            path = trace.dump_events(events, role="fleet")
            if path:
                logger.info("fleet trace dumped to %s", path)
        except OSError as e:
            logger.warning("fleet trace dump failed: %s", e)


def _fmt_nodes(nodes: List[int]) -> str:
    if len(nodes) <= 8:
        return str(nodes)
    return f"[{nodes[0]}..{nodes[-1]}]x{len(nodes)}"


def run_scenario(
    scenario: Scenario, out_dir: Optional[str] = None
) -> Dict:
    """Run one scenario; writes ``verdict.json`` (and trace artifacts)
    under ``out_dir`` and returns the verdict dict."""
    runner = FleetRunner(scenario, out_dir=out_dir)
    verdict = runner.run()
    path = os.path.join(runner.out_dir, "verdict.json")
    with open(path, "w") as f:
        json.dump(verdict, f, indent=1)
    verdict["verdict_path"] = path
    verdict["out_dir"] = runner.out_dir
    return verdict
