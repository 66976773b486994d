"""Worker-process bootstrap: the in-training-process face of the framework.

A user script starts with::

    import dlrover_tpu.train as dtrain
    ctx = dtrain.init()          # jax.distributed up, master client connected

which (a) reads the env the elastic agent injected, (b) runs
``jax.distributed.initialize`` against the rendezvous-elected coordinator,
and (c) connects the master client for sharding/steps/checkpoint RPCs.

Parity: the reference reaches this point via torchelastic env + its
trainer-SDK singletons; there is no single ``init`` — this is the
TPU-native consolidation.
"""

from __future__ import annotations

import atexit
import os
import tempfile
import time
from dataclasses import dataclass
from typing import Optional

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger


@dataclass
class WorkerEnv:
    job_name: str = "local"
    master_addr: str = ""
    node_id: int = 0
    node_rank: int = 0
    node_num: int = 1
    coordinator_addr: str = ""
    process_id: int = 0
    num_processes: int = 1
    restart_count: int = 0
    accelerator: str = "tpu"
    local_rank: int = 0
    # distinct TPU slices in the current world (agent-injected; sizes
    # the multislice mesh's DCN axis, changing across slice resizes)
    num_slices: int = 1

    @classmethod
    def from_env(cls) -> "WorkerEnv":
        e = os.environ
        return cls(
            job_name=e.get(NodeEnv.JOB_NAME, "local"),
            master_addr=e.get(NodeEnv.MASTER_ADDR, ""),
            node_id=int(e.get(NodeEnv.NODE_ID, "0")),
            node_rank=int(e.get(NodeEnv.NODE_RANK, "0")),
            node_num=int(e.get(NodeEnv.NODE_NUM, "1")),
            coordinator_addr=e.get(NodeEnv.COORDINATOR_ADDR, ""),
            process_id=int(e.get(NodeEnv.PROCESS_ID, "0")),
            num_processes=int(e.get(NodeEnv.NUM_PROCESSES, "1")),
            restart_count=int(e.get(NodeEnv.RESTART_COUNT, "0")),
            accelerator=e.get("DLROVER_TPU_ACCELERATOR", "tpu"),
            local_rank=int(e.get("DLROVER_TPU_LOCAL_RANK", "0")),
            num_slices=int(e.get("DLROVER_TPU_NUM_SLICES", "1") or 1),
        )


class WorkerContext:
    """What a training process holds after ``init()``."""

    def __init__(self, env: WorkerEnv, client=None):
        self.env = env
        self.client = client
        self._last_reported_step = 0
        self._last_report_ts = 0.0
        self.step_report_interval = 15.0
        # input-wait and GC seconds already shipped with earlier digests
        # (the spine's per-kind totals only grow; reports carry the delta)
        self._kind_marks = {"input_wait": 0.0, "gc_pause": 0.0}
        # whether this worker has ever shipped a comm_links split with
        # a dcn row: after a resize REMOVES the slow link (slice loss →
        # single-slice world) one more report must replace the master's
        # stale dcn row, or the goodput report advertises slow-link
        # load that no longer exists
        self._sent_comm_links = False
        # drained-but-unsent digest window (failed report): merged into
        # the next report so the master's ledger never loses it
        self._unreported_digest = None

    @property
    def process_id(self) -> int:
        return self.env.process_id

    @property
    def num_processes(self) -> int:
        return self.env.num_processes

    @property
    def is_chief(self) -> bool:
        return self.env.process_id == 0

    @property
    def restart_count(self) -> int:
        return self.env.restart_count

    def report_model_info(
        self,
        param_count: int = 0,
        flops_per_step: float = 0.0,
        batch_size: int = 0,
        seq_len: int = 0,
        hidden_dim: int = 0,
        n_layers: int = 0,
        n_heads: int = 0,
        remat: bool = True,
    ):
        """Describe the model to the master (chief only): feeds the
        hyperparam strategy's activation-memory sizing and the MFU
        accounting (reference report_model_info)."""
        if self.client is None or not self.is_chief:
            return
        try:
            self.client.report_model_info(
                param_count=param_count,
                flops_per_step=flops_per_step,
                batch_size=batch_size,
                seq_len=seq_len,
                hidden_dim=hidden_dim,
                n_layers=n_layers,
                n_heads=n_heads,
                remat=remat,
            )
        except Exception as e:
            logger.warning("model info report failed: %s", e)

    def report_resize_breakdown(
        self,
        rendezvous_s: float = 0.0,
        compile_s: float = 0.0,
        state_transfer_s: float = 0.0,
        restore_tier: str = "",
    ):
        """Per-resize downtime breakdown for the master's goodput
        ledger: what this membership change spent on rendezvous vs the
        step rebuild vs moving the train state (live reshard or
        checkpoint restore), and — ``restore_tier`` — which tier the
        state came back through (live | shm | disk | object), so the
        goodput report separates tier-0 fast restarts from real
        node-loss recoveries. Chief-only, like model info — every
        worker sees the same resize."""
        if self.client is None or not self.is_chief:
            return
        try:
            self.client.report_resize_breakdown(
                rendezvous_s=rendezvous_s,
                compile_s=compile_s,
                state_transfer_s=state_transfer_s,
                restore_tier=restore_tier,
            )
        except Exception as e:
            logger.warning("resize breakdown report failed: %s", e)

    def poll_speculation_hint(self, trainer) -> Optional[dict]:
        """Fetch the goodput planner's intended-next-world hint from
        the membership poll and arm the trainer's warm compiler with it
        (brain/planner.py; docs/design/brain_planner.md). The master
        plans in NODES; the hint scales by this process's local device
        count, so the trainer speculates the exact DEVICE world the
        planner-directed resize will seat. A missing/empty hint clears
        nothing armed and returns None — pre-planner masters and
        version skew are harmless (serde drops the unknown field)."""
        if self.client is None:
            return None
        try:
            hint = self.client.speculation_hint()
        except Exception as e:
            logger.debug("speculation-hint poll failed: %s", e)
            return None
        if not hint:
            return None
        world_nodes = int(hint.get("world", 0) or 0)
        if world_nodes <= 0:
            return None
        import jax

        devices_per_node = max(1, jax.local_device_count())
        trainer.set_speculation_hint(
            world_nodes * devices_per_node,
            n_slices=int(hint.get("n_slices", 0) or 0) or None,
        )
        return hint

    def report_step(self, step: int, force: bool = False, digest=None):
        """Throttled global-step report feeding the master's SpeedMonitor.

        ``digest``: a :class:`~dlrover_tpu.observability.digest.
        StepTimeDigest` the caller folds per-step wall times into; the
        report DRAINS one window from it (count/mean/p50/p95/max) and
        attaches the worker's input-wait and GC-pause seconds since the
        last report (the trace spine's per-kind seconds, kept whether or
        not its ring is on) — per-rank step-time
        distributions ride the existing throttled RPC, so the master's
        straggler detector and attribution cost no extra chatter."""
        if self.client is None:
            return
        now = time.time()
        if not force and now - self._last_report_ts < self.step_report_interval:
            return
        payload = None
        if digest is not None:
            try:
                payload = digest.snapshot_and_reset()
            except Exception as e:
                logger.warning("step digest drain failed: %s", e)
                payload = None
        if payload:
            from dlrover_tpu.observability import digest as digest_mod
            from dlrover_tpu.observability import trace

            kinds = trace.trace_ring.kind_seconds()
            for kind, mark in self._kind_marks.items():
                total = kinds.get(kind, 0.0)
                payload[kind + "_s"] = round(max(0.0, total - mark), 6)
                self._kind_marks[kind] = total
            digest_mod.set_last_window(payload)  # worker /metrics gauge
        if self._unreported_digest:
            # a window whose report failed (master relaunch gap) rides
            # the next attempt instead of vanishing from the
            # attribution's productive/input-wait ledgers
            from dlrover_tpu.observability.digest import merge_windows

            payload = merge_windows(self._unreported_digest, payload)
            self._unreported_digest = None
        # per-link comm bytes (profiler/comm.py): the analytic ici/dcn
        # split of this worker's program, riding the same throttled RPC
        # — only attached when a slow link exists (a dcn row), so
        # single-slice jobs add nothing to the wire. One FINAL split is
        # sent after a resize removes the slow link, replacing the
        # master's now-stale dcn row (record_comm_links is
        # last-report-wins per rank).
        comm_links = None
        overlap_ratio = -1.0
        try:
            from dlrover_tpu.profiler.comm import comm_ledger

            links = comm_ledger.link_bytes()
            if links.get("dcn"):
                comm_links = links
                self._sent_comm_links = True
                # the schedule's DCN overlap share rides with the dcn
                # row it qualifies (−1.0 = program reported no split)
                overlap_ratio = comm_ledger.overlap_ratio()
            elif self._sent_comm_links:
                # the {"ici": 0} floor keeps the clearing report
                # truthy through serde (an empty dict would be
                # indistinguishable from "no split attached")
                comm_links = links or {"ici": 0}
                self._sent_comm_links = False
        except Exception:
            comm_links = None
        try:
            try:
                self.client.report_global_step(
                    step, digest=payload, comm_links=comm_links,
                    overlap_ratio=overlap_ratio,
                )
            except TypeError:
                # link/overlap-unaware client (older stubs): retry
                # without the newest field, then plain
                try:
                    self.client.report_global_step(
                        step, digest=payload, comm_links=comm_links
                    )
                except TypeError:
                    self.client.report_global_step(step, digest=payload)
            self._last_reported_step = step
            self._last_report_ts = now
        except Exception as e:
            self._unreported_digest = payload
            logger.warning("step report failed: %s", e)


_context: Optional[WorkerContext] = None


def init(
    connect_master: bool = True,
    init_distributed: bool = True,
    local_device_count: Optional[int] = None,
) -> WorkerContext:
    """Bootstrap this training process; idempotent."""
    global _context
    if _context is not None:
        return _context
    env = WorkerEnv.from_env()

    # hang diagnosis: register the SIGUSR2 all-thread stack dumper the
    # agent's HangDumper triggers (profiler/hang_dump.py)
    stack_dir = os.environ.get("DLROVER_TPU_STACK_DIR", "")
    if stack_dir:
        try:
            from dlrover_tpu.profiler.hang_dump import (
                install_stack_dump_handler,
            )

            install_stack_dump_handler(stack_dir)
        except Exception:
            logger.exception("stack-dump handler install failed; continuing")
    from dlrover_tpu.common import flags as _flags
    from dlrover_tpu.observability import trace as _trace

    # GC pauses as gc_pause spans of the spine (counters always, the
    # profiler's host plane in a session, the ring behind its flag)
    _trace.install_gc_hook()
    if _flags.TRACE.get():
        # dump this process's span ring at exit so the job-timeline CLI
        # (profiler/analysis.py) can merge every rank + the master into
        # one perfetto-loadable trace
        _trace.dump_at_exit(
            role="worker", node_id=env.node_id, process_id=env.process_id
        )
    try:
        sampler_ms = float(
            os.environ.get("DLROVER_TPU_STACK_SAMPLER_MS", "0") or 0
        )
    except ValueError:
        logger.warning("DLROVER_TPU_STACK_SAMPLER_MS not numeric; ignored")
        sampler_ms = 0.0
    if sampler_ms > 0:
        # in-process hotspot sampler (reference stack_util.cc); dumps the
        # weighted stack trie at interpreter exit
        from dlrover_tpu.profiler.stack_sampler import StackSampler

        _sampler = StackSampler(interval=sampler_ms / 1000.0).start()
        out = os.environ.get(
            "DLROVER_TPU_STACK_SAMPLER_OUT",
            os.path.join(
                tempfile.gettempdir(),
                f"dlrover_tpu_hotspots-{os.getpid()}.txt",
            ),
        )

        def _dump_hotspots():
            _sampler.stop()
            try:
                _sampler.dump(out)
            except OSError:
                logger.warning("hotspot dump to %s failed", out)

        atexit.register(_dump_hotspots)

    import jax

    will_init_distributed = bool(
        init_distributed and env.num_processes > 1 and env.coordinator_addr
    )
    if env.accelerator == "cpu":
        # Test mode: virtual CPU devices + gloo cross-process collectives.
        # (The config update wins over a JAX_PLATFORMS the environment
        # carries.)
        if local_device_count:
            import re

            flags = os.environ.get("XLA_FLAGS", "")
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "", flags
            ).strip()
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_device_count}"
            ).strip()
        jax.config.update("jax_platforms", "cpu")
        if will_init_distributed:
            # gloo needs the distributed client: configuring it in a
            # single-process run makes CPU backend init itself fail
            # (make_gloo_tcp_collectives(distributed_client=None))
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )

    # warm-path elasticity: point JAX's persistent compilation cache at
    # the agent-injected dir (train/warm_compile.py) so a restarted
    # worker deserializes the step executable instead of recompiling —
    # the resize-downtime twin of the flash-checkpoint restore
    from dlrover_tpu.train.warm_compile import enable_persistent_cache

    enable_persistent_cache()

    if will_init_distributed:
        logger.info(
            "process %s/%s: jax.distributed.initialize(coordinator=%s)",
            env.process_id,
            env.num_processes,
            env.coordinator_addr,
        )
        init_timeout = int(
            os.environ.get("DLROVER_TPU_DIST_INIT_TIMEOUT", "120")
        )
        jax.distributed.initialize(
            coordinator_address=env.coordinator_addr,
            num_processes=env.num_processes,
            process_id=env.process_id,
            initialization_timeout=init_timeout,
        )

    if env.accelerator == "tpu" and jax.default_backend() != "tpu":
        # no silent CPU training under a TPU job: both kernels would
        # quietly give way to their reference code
        raise RuntimeError(
            f"accelerator=tpu but JAX's backend is "
            f"{jax.default_backend()!r}: no TPU found "
            "(--accelerator=cpu is the test mode)"
        )

    client = None
    if connect_master and env.master_addr:
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(env.master_addr, env.node_id)
        MasterClient.reset_singleton(client)

    _context = WorkerContext(env, client)
    atexit.register(_shutdown)
    return _context


def get_context() -> Optional[WorkerContext]:
    return _context


def _shutdown():
    global _context
    _context = None
