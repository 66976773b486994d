"""Agent-resident async checkpoint saver.

Parity: reference ``AsyncCheckpointSaver`` (``ckpt_saver.py:406-1394``):
lives in the agent process so checkpoints survive training-process crashes;
listens for save events on a SharedQueue, copies shm -> storage, commits
via per-node done-files + a tracker file, and persists the latest staged
shm checkpoint when the node is about to die (save-on-failure /
save-on-SIGTERM).

Storage layout (mirrored by BOTH disk tiers)::

    <ckpt_dir>/                        # tier 2: shared "object" storage
      latest_step.txt                  # tracker: last committed step
      step-<N>/
        node-<node_rank>.done          # commit votes (written after fanout)
        proc-<pid>/
          meta.json                    # CheckpointMeta manifest (shard
                                       # index + per-leaf CRC32)
          leaf-<i>.bin                 # raw little-endian bytes per staged
                                       # shard (dtype/shape in meta.json —
                                       # np.save can't round-trip bfloat16)
    <local_root>/node-<id>/            # tier 1: node-local disk
      step-<N>/proc-<pid>/...          # same proc-dir layout

Tiered persist (``DLROVER_TPU_CKPT_DEDUP``, the default): the shm
copy lands on the node-LOCAL disk tier first — a parallel pool of leaf
writers (FastPersist-style, arXiv:2406.13768), per-piece manifests
with CRC32 checksums, manifest written last so a torn proc dir is
never read as valid — and only then fans out to the shared object tier
in the background, off the shm lock. The commit vote moves to the end
of the fanout: a node votes once its pieces are durable on SHARED
storage, so the tracker's committed step is restorable after full node
loss. With the kill-switch off the legacy single-hop shm->object copy
(and its vote placement) is byte-identical to before.

``CheckpointPersister`` is the storage-side logic; ``AsyncCheckpointSaver``
adds the IPC server + event loop the agent hosts.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from dlrover_tpu.common import flags
from dlrover_tpu.common.constants import CheckpointConstant
from dlrover_tpu.common.ipc import IpcServer, SharedQueue, default_socket_path
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.storage import (
    CheckpointDeletionStrategy,
    CheckpointStorage,
    KeepLatestStepStrategy,
    PosixDiskStorage,
)
from dlrover_tpu.checkpoint.shm_handler import (
    CheckpointMeta,
    SharedMemoryHandler,
    shm_name,
)

CKPT_EVENT_QUEUE = "ckpt-events"
SHM_LOCK = "shm-ckpt-lock"
PERSIST_STATE_DICT = "ckpt-persist-state"
TRACKER_FILE = CheckpointConstant.TRACKER_FILE


@dataclass
class CheckpointEvent:
    event_type: str  # "save" | "backup" | "flush" | "exit"
    step: int = -1
    persist: bool = False  # False = memory-only snapshot
    ckpt_dir: str = ""

    def to_wire(self) -> Dict:
        return {
            "event_type": self.event_type,
            "step": self.step,
            "persist": self.persist,
            "ckpt_dir": self.ckpt_dir,
        }

    @classmethod
    def from_wire(cls, d: Dict) -> "CheckpointEvent":
        return cls(
            event_type=d.get("event_type", ""),
            step=d.get("step", -1),
            persist=d.get("persist", False),
            ckpt_dir=d.get("ckpt_dir", ""),
        )


def step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step-{step}")


def local_tier_dir(ckpt_dir: str, node_id: int) -> str:
    """This node's local-disk checkpoint tier (tier 1).

    ``DLROVER_TPU_CKPT_LOCAL_DIR`` points it at a node-local SSD /
    emptyDir volume (deploy/k8s/README.md); unset, it defaults under
    the checkpoint dir — correctness-equivalent (the tier ladder still
    works), just without the locality win. The ``node-<id>`` suffix
    keeps simulated multi-node worlds (the tests) on
    one host from sharing a tier they are supposed to lose
    independently."""
    root = flags.CKPT_LOCAL_DIR.get()
    if not root:
        root = os.path.join(os.path.abspath(ckpt_dir), "_local")
    return os.path.join(root, f"node-{node_id}")


class CheckpointPersister:
    """shm -> storage persistence + the commit/tracker protocol."""

    def __init__(
        self,
        job_name: str,
        node_id: int,
        node_rank: int = 0,
        num_nodes: int = 1,
        local_process_ids: Optional[List[int]] = None,
        storage: Optional[CheckpointStorage] = None,
        deletion_strategy: Optional[CheckpointDeletionStrategy] = None,
        commit_timeout: float = 600.0,
    ):
        self.job_name = job_name
        self.node_id = node_id
        self.node_rank = node_rank
        self.num_nodes = num_nodes
        self.local_process_ids = local_process_ids or [0]
        self._storage = storage or PosixDiskStorage()
        # the local tier is node-local disk BY DEFINITION — always posix,
        # independent of the (configurable) object-tier storage impl
        self._local_storage = PosixDiskStorage()
        self._deletion = deletion_strategy or KeepLatestStepStrategy(3)
        self._commit_timeout = commit_timeout
        self._stop_evt = threading.Event()
        self._persisted_steps: set = set()
        #: steps copied to the local tier whose object fanout (+ vote)
        #: has not run yet — fan_out_step drains it
        self._pending_fanout: set = set()
        self.last_persist_dir = ""

    def stop(self):
        self._stop_evt.set()

    def local_handlers(self) -> List[SharedMemoryHandler]:
        out = []
        for pid in self.local_process_ids:
            h = SharedMemoryHandler(shm_name(self.job_name, self.node_id, pid))
            if h.attach():
                out.append(h)
        return out

    def copy_step_to_storage(self, ckpt_dir: str, step: int = -1) -> List[int]:
        """Copy staged shm checkpoints to storage (NO commit wait).

        Groups local handlers by their staged step; a node votes "done" for
        a step only when EVERY local process has that step staged (a
        partial vote would let a step missing some processes' shards get
        committed). Returns the steps fully persisted by this node.
        """
        t0 = time.time()
        self.last_persist_dir = ckpt_dir
        handlers = self.local_handlers()
        try:
            by_step: Dict[int, List] = {}
            for h in handlers:
                meta = h.read_meta()
                if meta is None:
                    continue
                if meta.step in self._persisted_steps:
                    continue
                if step >= 0 and meta.step != step:
                    # Persist ONLY the requested step: staging (by the
                    # trainer) may already have moved on to a newer step;
                    # persisting whatever is staged would make nodes vote
                    # for different steps and no step would ever collect
                    # num_nodes votes. The newer step's own event follows.
                    logger.warning(
                        "shm %s holds step %s, requested %s; skipping",
                        h.name,
                        meta.step,
                        step,
                    )
                    continue
                by_step.setdefault(meta.step, []).append((meta, h))
            if not by_step:
                return []
            tiered = flags.CKPT_DEDUP.get()
            complete_steps = []
            for s, pairs in sorted(by_step.items()):
                for meta, h in pairs:
                    self._write_process_ckpt(ckpt_dir, meta, h, tiered)
                if len(pairs) == len(self.local_process_ids):
                    if tiered:
                        # pieces are durable on the LOCAL tier; the
                        # commit vote waits for the object fanout
                        # (fan_out_step) so a committed step survives
                        # losing this node outright
                        self._pending_fanout.add(s)
                    else:
                        done_path = os.path.join(
                            step_dir(ckpt_dir, s),
                            f"node-{self.node_rank}.done",
                        )
                        self._storage.write(b"1", done_path)
                    self._persisted_steps.add(s)
                    complete_steps.append(s)
                else:
                    logger.warning(
                        "step %s staged by %s/%s local processes; no vote yet",
                        s,
                        len(pairs),
                        len(self.local_process_ids),
                    )
            if complete_steps:
                logger.info(
                    "persisted steps %s shm->%s in %.2fs",
                    complete_steps,
                    ckpt_dir,
                    time.time() - t0,
                )
            return complete_steps
        finally:
            for h in handlers:
                h.close()

    def persist_step(
        self, ckpt_dir: str, step: int = -1,
        commit_timeout: Optional[float] = None,
    ) -> bool:
        """Copy + fan out + commit (the commit waits for other nodes;
        call off the shm lock — see AsyncCheckpointSaver's event loop)."""
        steps = self.copy_step_to_storage(ckpt_dir, step)
        # drain ALL pending fanouts (retries earlier transient object-
        # store failures), then vote-wait on every step that either was
        # just copied (legacy mode) or just cleared its fanout —
        # including earlier steps whose retry finally landed
        cleared = self.drain_fanouts(ckpt_dir)
        for s in sorted(set(steps) | set(cleared)):
            self._maybe_commit(ckpt_dir, s, timeout=commit_timeout)
        return bool(steps)

    def _persist_pool_size(self, n_files: int) -> int:
        return max(1, min(int(flags.CKPT_PERSIST_WORKERS.get()), n_files))

    def _write_process_ckpt(
        self,
        ckpt_dir: str,
        meta: CheckpointMeta,
        handler: SharedMemoryHandler,
        tiered: bool = False,
    ):
        """One process's staged pieces -> a proc dir: leaf files written
        by the parallel persist pool, then the manifest (meta.json, with
        per-leaf CRC32) LAST — a crash mid-write leaves a manifest-less
        dir that restore skips, never a torn-but-valid checkpoint.
        ``tiered`` writes to the node-local disk tier (the object copy
        is fan_out_step's job); legacy mode writes straight to the
        object storage as before."""
        from dlrover_tpu.observability import trace

        dest = self._local_storage if tiered else self._storage
        root = (
            local_tier_dir(ckpt_dir, self.node_id) if tiered else ckpt_dir
        )
        proc_dir = os.path.join(
            step_dir(root, meta.step), f"proc-{meta.process_id}"
        )
        dest.makedirs(proc_dir)
        persist_m0 = time.monotonic()

        def write_leaf(item):
            i, leaf_meta = item
            arr = handler.read_leaf(leaf_meta, copy=False)
            # raw bytes, not np.save: extended dtypes (bfloat16 etc.) do
            # not survive a .npy round-trip (they come back as void);
            # dtype and shape live in meta.json
            data = np.ascontiguousarray(arr).tobytes()
            dest.write(data, os.path.join(proc_dir, f"leaf-{i}.bin"))
            return zlib.crc32(data)

        items = list(enumerate(meta.leaves))
        workers = self._persist_pool_size(len(items))
        if workers > 1:
            with ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="ckpt-persist"
            ) as pool:
                crcs = list(pool.map(write_leaf, items))
        else:
            crcs = [write_leaf(it) for it in items]
        manifest = dataclasses.replace(
            meta,
            leaves=[
                dataclasses.replace(lm, crc32=crc)
                for lm, crc in zip(meta.leaves, crcs)
            ],
        )
        dest.write(
            manifest.to_json().encode(), os.path.join(proc_dir, "meta.json")
        )
        # trace spine: one per-tier persist span (disk = the node-local
        # tier; storage = the legacy direct-to-object path)
        trace.record(
            "ckpt_save", "persist.proc", persist_m0,
            time.monotonic() - persist_m0,
            tier="disk" if tiered else "storage",
            step=meta.step, leaves=len(meta.leaves),
        )

    def drain_fanouts(self, ckpt_dir: str) -> List[int]:
        """Fan out every pending step (oldest first) — the retry path:
        a step whose object fanout failed transiently stays pending and
        is re-attempted on the next persist cycle. Returns the steps
        that cleared (callers owe them a commit wait)."""
        pending = sorted(self._pending_fanout)
        for s in pending:
            self.fan_out_step(ckpt_dir, s)
        return [s for s in pending if s not in self._pending_fanout]

    def fan_out_step(self, ckpt_dir: str, step: int):
        """Background half of a tiered persist: copy the step's local
        proc dirs to the shared object tier (parallel pool, manifests
        last), then cast this node's commit vote. Runs OFF the shm lock
        — it reads local files, not shm — so a slow object store never
        stalls the trainer's next save. No-op for steps the local copy
        didn't mark pending (legacy mode, or another saver's step). On
        failure the step STAYS pending (drain_fanouts retries it);
        only a successful fanout — or the step's local dir having been
        pruned — unqueues it."""
        if step not in self._pending_fanout:
            return
        local_sdir = step_dir(local_tier_dir(ckpt_dir, self.node_id), step)
        if not self._local_storage.exists(local_sdir):
            # pruned from the local tier before the fanout ever
            # succeeded: nothing left to ship, stop retrying
            self._pending_fanout.discard(step)
            logger.warning(
                "pending fanout of step %s dropped: local dir %s is gone",
                step, local_sdir,
            )
            return
        from dlrover_tpu.observability import trace

        fanout_m0 = time.monotonic()
        obj_sdir = step_dir(ckpt_dir, step)
        copies: List[tuple] = []
        manifests: List[tuple] = []
        for proc in self._local_storage.listdir(local_sdir):
            if not proc.startswith("proc-"):
                continue
            pdir = os.path.join(local_sdir, proc)
            for name in self._local_storage.listdir(pdir):
                pair = (
                    os.path.join(pdir, name),
                    os.path.join(obj_sdir, proc, name),
                )
                (manifests if name == "meta.json" else copies).append(pair)
        try:
            workers = self._persist_pool_size(len(copies))
            if workers > 1:
                with ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="ckpt-fanout"
                ) as pool:
                    list(
                        pool.map(lambda p: self._storage.put_file(*p), copies)
                    )
            else:
                for pair in copies:
                    self._storage.put_file(*pair)
            for pair in manifests:  # manifests last: object commit marker
                self._storage.put_file(*pair)
            self._storage.write(
                b"1",
                os.path.join(obj_sdir, f"node-{self.node_rank}.done"),
            )
        except Exception:
            # the step stays restorable from the local tier AND stays
            # pending — drain_fanouts retries it next cycle; without
            # this node's vote the tracker will not advance to it
            logger.exception(
                "object-tier fanout of step %s failed; no commit vote "
                "cast (will retry)", step,
            )
            return
        self._pending_fanout.discard(step)
        trace.record(
            "ckpt_save", "fanout.object", fanout_m0,
            time.monotonic() - fanout_m0, tier="object", step=step,
            files=len(copies) + len(manifests),
        )
        # every node prunes its OWN local tier (the object tier is
        # pruned by node-rank 0 at commit time; non-rank-0 nodes would
        # otherwise grow their node-local SSD without bound)
        try:
            self._apply_local_deletion(ckpt_dir)
        except Exception:
            logger.exception("local-tier pruning failed")

    def _maybe_commit(
        self, ckpt_dir: str, step: int, timeout: Optional[float] = None
    ):
        """Node-rank-0's saver waits for all nodes' votes then commits."""
        if self.node_rank != 0:
            return
        if step in self._pending_fanout:
            # our own fanout (and so our own vote) has not landed —
            # polling for all votes would block the event loop for the
            # full commit timeout; the drain retry will bring the step
            # back through here once the vote is cast
            logger.warning(
                "step %s: fanout still pending, skipping the commit wait",
                step,
            )
            return
        sdir = step_dir(ckpt_dir, step)
        deadline = time.time() + (
            timeout if timeout is not None else self._commit_timeout
        )
        while time.time() < deadline and not self._stop_evt.is_set():
            done = [
                f
                for f in self._storage.listdir(sdir)
                if f.startswith("node-") and f.endswith(".done")
            ]
            if len(done) >= self.num_nodes:
                self._storage.write(
                    str(step).encode(), os.path.join(ckpt_dir, TRACKER_FILE)
                )
                logger.info("checkpoint step %s committed", step)
                self._apply_deletion(ckpt_dir)
                return
            time.sleep(0.5)
        logger.warning("step %s: only partial commit votes after timeout", step)

    def _prune_tier(self, store, root: str, committed: int, protect=()):
        steps = []
        for name in store.listdir(root):
            if name.startswith("step-"):
                try:
                    steps.append(int(name.split("-", 1)[1]))
                except ValueError:
                    continue
        removable = [
            s
            for s in self._deletion.to_delete(steps)
            if s != committed and s not in protect
        ]
        for s in removable:
            store.delete(step_dir(root, s))
            logger.info("deleted old checkpoint step %s under %s", s, root)

    def _apply_deletion(self, ckpt_dir: str):
        """Object-tier pruning — node-rank 0 only (commit time)."""
        committed = self.committed_step(ckpt_dir)
        self._prune_tier(self._storage, ckpt_dir, committed)

    def _apply_local_deletion(self, ckpt_dir: str):
        """Local-tier pruning — EVERY node, after each successful
        fanout: the node-local SSD holds the same step dirs as the
        object tier with far less room. Steps still awaiting their
        object fanout are protected (their only durable copy is
        local)."""
        committed = self.committed_step(ckpt_dir)
        self._prune_tier(
            self._local_storage,
            local_tier_dir(ckpt_dir, self.node_id),
            committed,
            protect=frozenset(self._pending_fanout),
        )

    def save_shm_to_storage(
        self, ckpt_dir: str = "", commit_timeout: Optional[float] = None
    ) -> bool:
        """Persist whatever is staged in shm right now (failure/SIGTERM).

        The reference's save-at-breakpoint guarantee (``training.py:1098``,
        ``ckpt_saver.py:786``). Runs from failure paths and signal
        handlers, so callers pass a short ``commit_timeout`` — a dying node
        must not spend the preemption grace period polling other nodes'
        votes."""
        ckpt_dir = ckpt_dir or self.last_persist_dir
        handlers = self.local_handlers()
        try:
            metas = [h.read_meta() for h in handlers]
        finally:
            for h in handlers:
                h.close()
        steps = {m.step for m in metas if m is not None}
        if not steps:
            return False
        if not ckpt_dir:
            logger.warning(
                "staged shm checkpoint exists but no ckpt_dir known; "
                "cannot persist"
            )
            return False
        if steps <= self._persisted_steps:
            # the staged steps' local copies exist — but a step whose
            # OBJECT fanout failed transiently is still pending, and
            # this (death-path) save is its last chance to reach
            # storage that outlives the node
            if self._pending_fanout:
                self.drain_fanouts(ckpt_dir)
            return not self._pending_fanout
        return self.persist_step(ckpt_dir, commit_timeout=commit_timeout)

    def committed_step(self, ckpt_dir: str) -> int:
        try:
            return int(self._storage.read(os.path.join(ckpt_dir, TRACKER_FILE)))
        except (FileNotFoundError, ValueError):
            return -1


class AsyncCheckpointSaver:
    """One per agent/node: IPC server + async persist event loop."""

    def __init__(
        self,
        job_name: str,
        node_id: int,
        node_rank: int = 0,
        num_nodes: int = 1,
        local_process_ids: Optional[List[int]] = None,
        storage: Optional[CheckpointStorage] = None,
        deletion_strategy: Optional[CheckpointDeletionStrategy] = None,
        socket_path: str = "",
        replica: bool = False,
    ):
        self.replica_enabled = replica
        self.replica_manager = None
        self.persister = CheckpointPersister(
            job_name=job_name,
            node_id=node_id,
            node_rank=node_rank,
            num_nodes=num_nodes,
            local_process_ids=local_process_ids,
            storage=storage,
            deletion_strategy=deletion_strategy,
        )
        self.socket_path = socket_path or default_socket_path(job_name, node_id)
        self._ipc = IpcServer(self.socket_path)
        self._event_queue: Optional[SharedQueue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        # drain(): flush token -> set when the event loop reaches it
        self._flushes: Dict[int, threading.Event] = {}

    def start(self):
        self._ipc.start()
        if self.replica_enabled:
            from dlrover_tpu.checkpoint.replica import ReplicaManager

            self.replica_manager = ReplicaManager()
        self._event_queue = SharedQueue(CKPT_EVENT_QUEUE, self.socket_path)
        self._thread = threading.Thread(
            target=self._event_loop, name="ckpt-saver", daemon=True
        )
        self._thread.start()
        logger.info(
            "checkpoint saver started (node %s, ipc %s)",
            self.persister.node_id,
            self.socket_path,
        )

    def stop(self):
        self._stop_evt.set()
        self.persister.stop()
        if self.replica_manager is not None:
            self.replica_manager.server.stop()
        self._ipc.stop()

    # -- replica (cross-host backup) ---------------------------------------

    @property
    def replica_port(self) -> int:
        return self.replica_manager.port if self.replica_manager else 0

    def update_replica_peers(self, peers, self_rank: int, world: int):
        if self.replica_manager is not None:
            self.replica_manager.update_peers(peers, self_rank, world)

    def set_replica_token(self, token: str):
        if self.replica_manager is not None:
            self.replica_manager.set_token(token)

    def maybe_fetch_replica(self) -> int:
        """After a relaunch: if nothing is staged locally, pull this seat's
        backup from the peer so workers restore from memory, not storage."""
        if self.replica_manager is None:
            return -1
        for h in self.persister.local_handlers():
            try:
                if h.attach() and h.read_meta() is not None:
                    return -1  # local staged state exists
            finally:
                h.close()
        targets = [
            shm_name(self.persister.job_name, self.persister.node_id, pid)
            for pid in self.persister.local_process_ids
        ]
        return self.replica_manager.fetch_backup_into_shm(targets)

    def _release_persist_waiters(self, step: int):
        """Release the trainer's persist back-pressure — but only for
        processes whose staged step has reached ``step`` (copied, or the
        trainer already moved past so waiting longer cannot help). A
        process still holding an OLDER step keeps waiting for its own
        event; releasing it here would let it overwrite un-copied shards."""
        try:
            staged: Dict[int, int] = {}
            for h in self.persister.local_handlers():
                meta = h.read_meta()
                if meta is not None:
                    staged[meta.process_id] = meta.step
                h.close()
            state = self._ipc.state.get_dict(PERSIST_STATE_DICT)
            for pid in self.persister.local_process_ids:
                if staged.get(pid, -1) >= step:
                    key = f"copied-{pid}"
                    state[key] = max(int(state.get(key, -1)), step)
        except Exception:
            logger.exception("persist-state release failed")

    def _push_replica(self, step_hint: int = -1):
        """Copy segments out of shm under the lock, stream lock-free.
        Coalesced: a step already pushed (e.g. the persist path after a
        backup event) is not streamed twice."""
        if self.replica_manager is None:
            return
        if 0 <= step_hint <= self.replica_manager.last_pushed_step:
            return
        lock = self._ipc.state.get_lock(SHM_LOCK)
        if not lock.acquire(timeout=30):
            logger.warning("replica push skipped: shm lock busy")
            return
        handlers = self.persister.local_handlers()
        try:
            snapshot = self.replica_manager.collect_segments(handlers)
        finally:
            lock.release()
            for h in handlers:
                h.close()
        if snapshot is None:
            return
        step, segments, payload = snapshot
        if step <= self.replica_manager.last_pushed_step:
            return
        self.replica_manager.send_backup(step, segments, payload)

    def update_topology(self, node_rank: int, num_nodes: int, process_ids: List[int]):
        """Called by the agent after each rendezvous round."""
        self.persister.node_rank = node_rank
        self.persister.num_nodes = num_nodes
        self.persister.local_process_ids = list(process_ids)
        # a round boundary is a restart boundary: stale copied-{pid} marks
        # from a pre-restart (possibly higher) step would disarm the new
        # incarnation's persist back-pressure after a rollback restore
        try:
            self._ipc.state.get_dict(PERSIST_STATE_DICT).clear()
        except Exception:
            pass

    # Bounded commit wait for failure-path persists: a dying node writes its
    # shards + vote and gives peers only this long to show up before it gets
    # on with shutdown (GKE preemption grace is short).
    BREAKPOINT_COMMIT_TIMEOUT = 30.0

    def save_shm_to_storage(self, ckpt_dir: str = "") -> bool:
        """Breakpoint persist, guarded by the same shm lock the trainer
        takes (bounded wait: a dying trainer's connection drop auto-releases
        its lock, so this cannot wedge)."""
        lock = self._ipc.state.get_lock(SHM_LOCK)
        acquired = lock.acquire(timeout=30)
        if not acquired:
            # A trainer is (still) mid-stage after 30s: the shm region may
            # be torn mid-overwrite. Persisting it could commit garbage —
            # the previously committed step stays the restore point.
            logger.error(
                "breakpoint persist: shm lock not acquired in 30s; "
                "refusing to persist a possibly-torn checkpoint"
            )
            return False
        try:
            return self.persister.save_shm_to_storage(
                ckpt_dir, commit_timeout=self.BREAKPOINT_COMMIT_TIMEOUT
            )
        finally:
            lock.release()

    def drain(self, timeout: float = 600.0) -> bool:
        """Durability flush for a finished job: block until every persist
        queued so far has been copied, fanned out AND committed. The
        trainer is released as soon as the shm copy is done; the fanout
        and the commit run on after it, on a daemon thread that dies
        with the agent — without this wait a job's last checkpoint is
        copied but never committed, and a restart resumes from the one
        before. A ``flush`` event goes onto the same FIFO queue; the
        single-threaded event loop reaches it only after it has handled
        everything queued ahead of it."""
        if self._thread is None or not self._thread.is_alive():
            return True  # no event loop, nothing in flight
        flushed = threading.Event()
        token = id(flushed)
        self._flushes[token] = flushed
        # the server-side queue, put in-process: the queue client is the
        # event loop's (one socket, one request at a time)
        self._ipc.state.get_queue(CKPT_EVENT_QUEUE).put(
            CheckpointEvent("flush", step=token).to_wire()
        )
        if flushed.wait(timeout):
            return True
        self._flushes.pop(token, None)
        logger.warning("checkpoint saver still busy after %.0fs", timeout)
        return False

    def cleanup_shm(self):
        """Unlink staged segments (only after a successful job end)."""
        for h in self.persister.local_handlers():
            h.close(unlink=True)

    def _event_loop(self):
        while not self._stop_evt.is_set():
            try:
                raw = self._event_queue.get(timeout=1.0)
            except queue.Empty:
                continue
            except Exception:
                if self._stop_evt.is_set():
                    return
                logger.exception("ckpt event queue read failed")
                time.sleep(1)
                continue
            event = CheckpointEvent.from_wire(raw)
            if event.event_type == "exit":
                return
            if event.event_type == "flush":
                flushed = self._flushes.pop(event.step, None)
                if flushed is not None:
                    flushed.set()
                continue
            if event.event_type == "backup":
                try:
                    self._push_replica(step_hint=event.step)
                except Exception:
                    logger.exception("replica push failed")
                continue
            if event.event_type == "save" and event.persist:
                # Hold the shm lock only for the shm->storage copy (the
                # trainer takes the same lock for staging); the commit wait
                # on other nodes happens OUTSIDE the lock so it can never
                # stall the trainer's next save.
                lock = self._ipc.state.get_lock(SHM_LOCK)
                try:
                    with lock:
                        steps = self.persister.copy_step_to_storage(
                            event.ckpt_dir, event.step
                        )
                    # release back-pressure NOW: the copy the trainer is
                    # waiting on is done; the object fanout reads LOCAL
                    # files (not shm), and commit waits and replica pushes
                    # can take minutes — none of it may stall training
                    self._release_persist_waiters(event.step)
                    # drain retries earlier failed fanouts too; commit-
                    # wait everything that copied or newly cleared
                    cleared = self.persister.drain_fanouts(event.ckpt_dir)
                    for s in sorted(set(steps) | set(cleared)):
                        self.persister._maybe_commit(event.ckpt_dir, s)
                    if self.replica_manager is not None:
                        self._push_replica(step_hint=event.step)
                except Exception:
                    logger.exception("persist of step %s failed", event.step)
                finally:
                    # idempotent: also covers a copy that raised
                    self._release_persist_waiters(event.step)
