"""Per-host elastic agent: spawn, monitor, and restart JAX worker processes.

Parity: reference ``ElasticTrainingAgent`` (``elastic_agent/torch/training.py:428-1212``):
the ``_invoke_run`` monitor loop, membership-change restarts, failure
reporting and restart-vs-relaunch decision. TPU-natively the agent owns the
``jax.distributed`` bootstrap env (coordinator address, process ids) that it
derives from the master rendezvous, replacing torchelastic's PContext/store.
"""

from __future__ import annotations

import enum
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from dlrover_tpu.agent.config import ElasticLaunchConfig
from dlrover_tpu.agent.diagnosis_agent import (
    DiagnosisAgent,
    WorkerAction,
    WorkerFailure,
)
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.rendezvous import (
    CommWorld,
    MasterRendezvousHandler,
    RendezvousTimeoutError,
)
from dlrover_tpu.common import flags
from dlrover_tpu.common.constants import (
    DefaultValues,
    NodeEnv,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.utils.net import find_free_port, local_ip


class RunResult(enum.Enum):
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    MEMBERSHIP_CHANGED = "membership_changed"
    AGENT_STOPPED = "agent_stopped"


@dataclass
class WorkerProc:
    local_rank: int
    process_id: int
    proc: subprocess.Popen
    log_path: str
    # where this incarnation's output starts: the file is appended to
    # across restarts, and what an earlier worker wrote (the runtime
    # prints "SIGTERM received" when the agent stops it) is not this
    # worker's failure signature
    log_start: int = 0


class ElasticAgent:
    def __init__(
        self,
        config: ElasticLaunchConfig,
        client: Optional[MasterClient] = None,
        log_dir: str = "",
    ):
        self._config = config
        self._client = client or MasterClient.singleton_instance()
        self._log_dir = log_dir or os.path.join(
            tempfile.gettempdir(), "dlrover_tpu_logs", config.job_name,
            f"node-{config.node_id}",
        )
        os.makedirs(self._log_dir, exist_ok=True)
        self._node_ip = local_ip()
        self._workers: List[WorkerProc] = []
        self._restart_count = 0
        self._stop_evt = threading.Event()
        self._restart_requested = threading.Event()
        self._relaunch_requested = False
        self._status_reporter = None
        self._current_world: Optional[CommWorld] = None
        self._ckpt_saver = None  # wired by the flash-checkpoint layer
        # non-numeric values warn once and fall back to the default
        # inside the typed registry (common/flags.py)
        diag_interval = float(flags.DIAG_INTERVAL.get())
        self._diagnosis = DiagnosisAgent(
            client=self._client, node_id=config.node_id,
            interval_secs=max(diag_interval, 1.0),
        )
        self._diagnosis.set_log_source(self._last_worker_log_tail)
        self._tpu_timer_env: Dict[str, str] = {}
        self._hang_dumper = None
        # external accelerator exporters (GKE TPU metrics agent etc.):
        # comma-separated host:port/path endpoints
        self._metric_monitor = None
        endpoints = flags.METRIC_ENDPOINTS.get()
        if endpoints:
            from dlrover_tpu.common.metric import TpuMetricMonitor

            self._metric_monitor = TpuMetricMonitor(
                [e.strip() for e in endpoints.split(",") if e.strip()],
                client=self._client,
            )
        self._paral_tuner = None
        from dlrover_tpu.observability import trace

        if trace.enabled():
            # the agent's spine (rendezvous spans) dumps next to the
            # workers' at exit; JOB_NAME rides the registry so the
            # default dump dir matches theirs
            flags.JOB_NAME.propagate(config.job_name)
            trace.dump_at_exit(role="agent", node_id=config.node_id)
        if config.tpu_timer:
            self._setup_tpu_timer()
        if config.comm_metrics:
            from dlrover_tpu.profiler.comm import CommMetricsSource

            self._diagnosis.set_comm_metrics_source(CommMetricsSource([
                config.comm_metrics_port + i
                for i in range(config.nproc_per_node)
            ]))

    def _setup_tpu_timer(self):
        """Route workers' PJRT plugin loading through the native profiler
        and scrape its metrics into diagnosis (reference: xpu_timer launch
        wrapper + XpuTimerMetricsCollector). Each local rank gets its own
        metrics port (base + local_rank) so servers never collide."""
        import subprocess

        from dlrover_tpu.profiler import TpuTimerMetricsSource, interposer_env

        try:
            self._tpu_timer_env = interposer_env(
                port=self._config.tpu_timer_port
            )
        except subprocess.CalledProcessError as e:
            logger.error(
                "tpu_timer native build failed; disabled:\n%s",
                (e.stderr or b"").decode(errors="replace")[-2000:],
            )
            self._tpu_timer_env = {}
            return
        except Exception:
            logger.exception("tpu_timer setup failed; disabled")
            self._tpu_timer_env = {}
            return
        if self._tpu_timer_env:
            from dlrover_tpu.profiler.hang_dump import HangDumper

            ports = [
                self._config.tpu_timer_port + i
                for i in range(self._config.nproc_per_node)
            ]
            self._diagnosis.set_metrics_source(TpuTimerMetricsSource(ports))
            self._hang_dumper = HangDumper(
                stack_dir=os.path.join(self._log_dir, "hang"),
                metrics_ports=ports,
            )
            self._diagnosis.set_hang_dumper(self._hang_dumper)

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> int:
        self._client.report_node_address(
            self._node_ip,
            slice_name=self._config.slice_name,
            coords=self._config.coords,
        )
        self._start_ckpt_saver()
        self._start_heartbeats()
        if self._metric_monitor is not None:
            self._metric_monitor.start()
        self._install_signal_handlers()
        self._diagnosis.start()
        self._start_paral_config_tuner()
        try:
            return self._invoke_run()
        finally:
            self._stop_evt.set()
            if self._status_reporter is not None:
                self._status_reporter.stop()
            self._diagnosis.stop()
            if self._metric_monitor is not None:
                self._metric_monitor.stop()
            if self._paral_tuner is not None:
                self._paral_tuner.stop()
            self._stop_workers()
            if self._ckpt_saver is not None:
                self._ckpt_saver.stop()

    def _start_paral_config_tuner(self):
        from dlrover_tpu.agent.paral_config_tuner import ParalConfigTuner

        try:
            self._paral_tuner = ParalConfigTuner(
                self._client,
                job_name=self._config.job_name,
                node_id=self._config.node_id,
            )
            self._paral_tuner.start()
        except Exception:
            logger.exception("paral config tuner failed to start")
            self._paral_tuner = None

    def _start_ckpt_saver(self):
        """Host the flash-checkpoint saver so staged state survives worker
        crashes (reference: AsyncCheckpointSaver.start_async_saving_ckpt)."""
        from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver

        try:
            self._ckpt_saver = AsyncCheckpointSaver(
                job_name=self._config.job_name,
                node_id=self._config.node_id,
                replica=self._config.ckpt_replica,
            )
            self._ckpt_saver.start()
            if self._ckpt_saver.replica_port:
                # publish the replica server so peers can reach it
                self._client.report_node_address(
                    self._node_ip,
                    port=self._ckpt_saver.replica_port,
                    slice_name=self._config.slice_name,
                    coords=self._config.coords,
                )
        except Exception:
            logger.exception("checkpoint saver failed to start; continuing")
            self._ckpt_saver = None

    def _invoke_run(self) -> int:
        while not self._stop_evt.is_set():
            try:
                world = self._rendezvous()
            except RendezvousTimeoutError as e:
                logger.error("rendezvous timed out: %s", e)
                self._client.report_failure(str(e), self._restart_count)
                return 1
            self._start_workers(world)
            result, exit_code, err = self._monitor_workers()
            if result == RunResult.SUCCEEDED:
                logger.info("node %s: workers succeeded", self._config.node_id)
                self._client.report_succeeded()
                if self._ckpt_saver is not None:
                    # the job's last checkpoint: copied, but its fanout
                    # and commit may still be running on the saver's
                    # daemon thread, which dies with this process
                    self._ckpt_saver.drain()
                    self._ckpt_saver.cleanup_shm()
                return 0
            if result == RunResult.AGENT_STOPPED:
                # Stopped by a master action (relaunch) or a signal: exit
                # nonzero so the platform replaces this node.
                self._save_checkpoint_at_breakpoint()
                self._stop_workers()
                return 143 if self._relaunch_requested else 0
            if result == RunResult.MEMBERSHIP_CHANGED:
                logger.info(
                    "node %s: membership changed; restarting workers",
                    self._config.node_id,
                )
                self._save_checkpoint_at_breakpoint()
                self._stop_workers()
                continue
            # FAILED: the diagnostician decides restart-in-place vs handing
            # the node back to the platform (reference training.py:1016-1027)
            self._save_checkpoint_at_breakpoint()
            self._stop_workers()
            self._client.report_failure(
                err, self._restart_count, TrainingExceptionLevel.ERROR, exit_code
            )
            action = self._diagnosis.diagnose_training_failure(
                WorkerFailure(
                    node_id=self._config.node_id,
                    restart_count=self._restart_count,
                    max_restarts=self._config.max_restarts,
                    exit_code=exit_code,
                    log_tail=err,
                )
            )
            if action == WorkerAction.RESTART_WORKER:
                self._restart_count += 1
                logger.warning(
                    "node %s: worker failed (exit=%s); restart %s/%s",
                    self._config.node_id,
                    exit_code,
                    self._restart_count,
                    self._config.max_restarts,
                )
                continue
            logger.error(
                "node %s: diagnosis says relaunch (exit=%s); exiting",
                self._config.node_id,
                exit_code,
            )
            return exit_code or 1
        return 0

    # -- rendezvous ---------------------------------------------------------

    def _rendezvous(self) -> CommWorld:
        coord_port = self._config.training_port or find_free_port()
        handler = MasterRendezvousHandler(
            self._client,
            RendezvousName.TRAINING,
            local_world_size=self._config.nproc_per_node,
            node_ip=self._node_ip,
            node_port=coord_port,
            slice_name=self._config.slice_name,
            coords=self._config.coords,
            join_timeout=self._config.rdzv_join_timeout,
        )
        world = handler.next_rendezvous(node_rank_hint=self._config.node_id)
        self._current_world = world
        self._rdzv_handler = handler
        if self._ckpt_saver is not None:
            self._ckpt_saver.update_topology(
                node_rank=world.node_rank,
                num_nodes=world.world_size,
                process_ids=[
                    world.process_id_base + i
                    for i in range(self._config.nproc_per_node)
                ],
            )
            if self._config.ckpt_replica:
                self._sync_replica_peers(world)
        return world

    def _replica_token(self, world: CommWorld) -> str:
        """Shared secret for the cross-host replica servers, minted by the
        round's rank-0 agent and distributed through the master KV store
        (the replica port is reachable cross-host, unlike the node-local
        IPC socket, so requests must be authenticated)."""
        key = "ckpt-replica-token"
        if world.node_rank == 0:
            token = self._client.kv_store_get(key)
            if not token:
                import secrets

                token = secrets.token_hex(16).encode()
                self._client.kv_store_set(key, token)
            return bytes(token).decode()
        deadline = time.time() + 60
        while time.time() < deadline:
            token = self._client.kv_store_get(key)
            if token:
                return bytes(token).decode()
            time.sleep(0.5)
        logger.warning("replica token not available; replica push disabled")
        return ""

    def _sync_replica_peers(self, world: CommWorld):
        """Map rendezvous ranks to peers' replica servers, then pull this
        seat's backup if nothing is staged locally (node replacement)."""
        try:
            token = self._replica_token(world)
            if token:
                self._ckpt_saver.set_replica_token(token)
            by_id = {
                m.node_id: m
                for m in self._client.get_running_nodes()
                if m.port
            }
            peers = {}
            for rank, (node_id, _lws, ip, _port) in world.members.items():
                meta = by_id.get(node_id)
                if meta is not None:
                    peers[rank] = (meta.addr or ip, meta.port)
            self._ckpt_saver.update_replica_peers(
                peers, world.node_rank, world.world_size
            )
            step = self._ckpt_saver.maybe_fetch_replica()
            if step >= 0:
                logger.info(
                    "node %s: staged step %s recovered from peer replica",
                    self._config.node_id,
                    step,
                )
        except Exception:
            logger.exception("replica peer sync failed")

    # -- workers ------------------------------------------------------------

    def _worker_env(self, world: CommWorld, local_rank: int) -> Dict[str, str]:
        env = flags.child_env(self._config.env)
        if self._config.ckpt_replica:
            env["DLROVER_TPU_CKPT_REPLICA"] = "1"
        if self._config.compile_cache_dir:
            # workers point JAX's persistent compile cache here
            # (train/warm_compile.py via bootstrap.init) so a restarted
            # worker's step rebuild is a cache hit, not a cold compile
            env["DLROVER_TPU_COMPILE_CACHE_DIR"] = (
                self._config.compile_cache_dir
            )
        if self._paral_tuner is not None:
            from dlrover_tpu.agent.paral_config_tuner import (
                PARAL_CONFIG_PATH_ENV,
            )

            env[PARAL_CONFIG_PATH_ENV] = self._paral_tuner.path
        if self._tpu_timer_env:
            env.update(self._tpu_timer_env)
            # one metrics server per local rank
            env["DLROVER_TPU_TIMER_PORT"] = str(
                self._config.tpu_timer_port + local_rank
            )
        process_id = world.process_id_base + local_rank
        if self._config.comm_metrics:
            env["DLROVER_TPU_COMM_METRICS_PORT"] = str(
                self._config.comm_metrics_port + local_rank
            )
        env.update(
            {
                NodeEnv.JOB_NAME: self._config.job_name,
                NodeEnv.MASTER_ADDR: self._client.master_addr,
                NodeEnv.NODE_ID: str(self._config.node_id),
                NodeEnv.NODE_RANK: str(world.node_rank),
                NodeEnv.NODE_NUM: str(world.world_size),
                NodeEnv.COORDINATOR_ADDR: world.coordinator_addr,
                NodeEnv.PROCESS_ID: str(process_id),
                NodeEnv.NUM_PROCESSES: str(world.num_processes),
                NodeEnv.RESTART_COUNT: str(self._restart_count),
                "DLROVER_TPU_ACCELERATOR": self._config.accelerator,
                "DLROVER_TPU_LOCAL_RANK": str(local_rank),
                # distinct TPU slices in the seated world: training code
                # sizes the multislice mesh's DCN axis from this, so a
                # slice-count resize flows through re-rendezvous
                "DLROVER_TPU_NUM_SLICES": str(world.n_slices),
                # workers install a SIGUSR2 faulthandler writing here; the
                # agent's HangDumper signals + collects on a detected hang
                "DLROVER_TPU_STACK_DIR": os.path.join(self._log_dir, "hang"),
            }
        )
        return env

    def _start_workers(self, world: CommWorld):
        self._workers = []
        for local_rank in range(self._config.nproc_per_node):
            process_id = world.process_id_base + local_rank
            log_path = os.path.join(
                self._log_dir,
                f"worker-{process_id}-restart{self._restart_count}.log",
            )
            log_file = open(log_path, "ab")
            log_start = os.path.getsize(log_path)
            cmd = [sys.executable, self._config.entrypoint] + list(
                self._config.entrypoint_args
            )
            proc = subprocess.Popen(
                cmd,
                env=self._worker_env(world, local_rank),
                stdout=log_file,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
            log_file.close()
            self._workers.append(
                WorkerProc(local_rank, process_id, proc, log_path, log_start)
            )
            logger.info(
                "node %s: started worker process_id=%s pid=%s log=%s",
                self._config.node_id,
                process_id,
                proc.pid,
                log_path,
            )
        if self._hang_dumper is not None:
            self._hang_dumper.set_workers(
                [w.proc.pid for w in self._workers]
            )

    def _stop_workers(self, grace: float = 10.0):
        for w in self._workers:
            if w.proc.poll() is None:
                try:
                    os.killpg(w.proc.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.time() + grace
        for w in self._workers:
            timeout = max(0.1, deadline - time.time())
            try:
                w.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(w.proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                w.proc.wait()
        self._workers = []

    def _last_worker_log_tail(self, max_bytes: int = 4096) -> str:
        """Concatenated log tails across all local workers (any process on
        this host may carry the failure signature)."""
        workers = list(self._workers)
        if not workers:
            return ""
        per = max(512, max_bytes // len(workers))
        return "\n".join(
            t for t in (self._tail_log(w, per) for w in workers) if t
        )

    def _tail_log(self, worker: WorkerProc, max_bytes: int = 4096) -> str:
        try:
            with open(worker.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(worker.log_start, size - max_bytes))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    # -- monitoring ---------------------------------------------------------

    def _membership_changed(self) -> bool:
        """A node is waiting to (re)join -> the world must re-form."""
        try:
            return self._rdzv_handler.num_nodes_waiting() > 0
        except Exception:
            return False

    def _monitor_workers(self):
        """Returns (RunResult, exit_code, error_text)."""
        while not self._stop_evt.is_set():
            time.sleep(self._config.monitor_interval)
            states = [(w, w.proc.poll()) for w in self._workers]
            failed = next((s for s in states if s[1] not in (None, 0)), None)
            if failed is not None:
                err = self._tail_log(failed[0])
                return RunResult.FAILED, failed[1] or 1, err
            if all(code == 0 for _, code in states):
                return RunResult.SUCCEEDED, 0, ""
            if self._restart_requested.is_set():
                self._restart_requested.clear()
                return RunResult.MEMBERSHIP_CHANGED, 0, ""
            if self._membership_changed():
                return RunResult.MEMBERSHIP_CHANGED, 0, ""
        return RunResult.AGENT_STOPPED, 0, ""

    # -- heartbeats / signals ----------------------------------------------

    def _start_heartbeats(self):
        """Folded status reports replace the old heartbeat-only loop:
        heartbeat + host resource usage ride one periodic RPC
        (agent/reporter.py), and an ``Overloaded`` master widens the
        cadence instead of being hammered. Diagnosis actions still
        arrive on the ack exactly as before."""
        from dlrover_tpu.agent.reporter import StatusReporter

        self._status_reporter = StatusReporter(
            self._client,
            interval_s=DefaultValues.SEC_AGENT_HEARTBEAT_INTERVAL,
            on_actions=lambda actions: [
                self._handle_action(a) for a in actions
            ],
        )
        self._status_reporter.start()

    def _handle_action(self, action):
        cls = getattr(action, "action_cls", "")
        if cls == "RestartWorker":
            self._restart_requested.set()
        elif cls == "RelaunchWorker":
            logger.warning("master requested node relaunch; stopping agent")
            self._relaunch_requested = True
            self._stop_evt.set()
        elif cls == "CollectHangDump":
            # synchronized cross-node dump: off the heartbeat thread (the
            # dump settles ~1.5s waiting for SIGUSR2 stacks to land)
            threading.Thread(
                target=self._diagnosis.collect_and_ship_dump,
                kwargs={"reason": action.action_content or "master_request"},
                name="collect-dump",
                daemon=True,
            ).start()

    def _install_signal_handlers(self):
        if threading.current_thread() is not threading.main_thread():
            return

        def handle(signum, frame):
            # intentional save-on-signal: the preemption grace window is
            # the ONLY time to persist the staged checkpoint, so this
            # handler owns the blocking-I/O risk (the reference agent
            # makes the same trade)  # graftlint: disable=JG005
            logger.warning("agent got signal %s; saving + stopping", signum)
            self._save_checkpoint_at_breakpoint()
            self._stop_evt.set()
            self._stop_workers(grace=5)
            raise SystemExit(143 if signum == signal.SIGTERM else 130)

        signal.signal(signal.SIGTERM, handle)

    # -- checkpoint hook (flash ckpt wires in) ------------------------------

    def set_checkpoint_saver(self, saver):
        self._ckpt_saver = saver

    def _save_checkpoint_at_breakpoint(self):
        if not self._config.save_at_breakpoint:
            return
        if self._ckpt_saver is not None:
            try:
                self._ckpt_saver.save_shm_to_storage()
            except Exception:
                logger.exception("breakpoint checkpoint persist failed")
