"""Training-process side of flash checkpoint.

Parity: reference ``CheckpointEngine`` (``flash_checkpoint/engine.py:155-502``)
+ the sharded FSDP/Megatron engines, unified for JAX: every process stages
its *addressable unique shards* (with global index metadata) into its own
shm segment — the blocking cost of a save is one ``jax.device_get`` of local
shards plus a host memcpy. Persist/commit happens asynchronously in the
agent's saver.

Replica-deduplicated staging (``DLROVER_TPU_CKPT_DEDUP``, default on):
on a dp-replicated mesh every process used to stage its full
addressable view — dp identical copies of the replicated leaves per
save. With dedup each process stages (and the saver persists) only the
pieces it OWNS under the disjoint partition ``checkpoint/ownership.py``
derives from the leaves' shardings — per-process staged+persisted
bytes drop to ~1/dp on pure-dp meshes (Orbax's replica-aware
persistence, arXiv:2605.23066).

Restore is a tier ladder whose rungs UNION (each adds the pieces the
previous rungs were missing, per step):
- tier 0, shm      — this process's staged segment (fast restart);
- tier 1, disk     — the node-local tier (union across this node's
  process manifests);
- tier 2, object   — the shared storage tier (union across ALL nodes'
  manifests) — so a restore survives losing any single node's shm AND
  local disk. ``last_restore_stats`` records the tier, piece count and
  bytes. Disk/object pieces are CRC-verified; a corrupt piece demotes
  to the next tier instead of restoring garbage. Incomplete coverage
  after the last rung fails loudly (None + error log), never a
  silently zero-filled state.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.checkpoint import ownership
from dlrover_tpu.checkpoint.saver import (
    CKPT_EVENT_QUEUE,
    PERSIST_STATE_DICT,
    SHM_LOCK,
    CheckpointEvent,
    TRACKER_FILE,
    local_tier_dir,
    step_dir,
)
from dlrover_tpu.checkpoint.shm_handler import (
    CheckpointMeta,
    SharedMemoryHandler,
    flatten_state,
    resolve_dtype,
    shm_name,
    unflatten_state,
)
from dlrover_tpu.common import flags
from dlrover_tpu.observability import trace
from dlrover_tpu.common.ipc import (
    SharedDict,
    SharedLock,
    SharedQueue,
    default_socket_path,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage


# save-side region keys and the ownership plan's must stay
# byte-identical (staging matches one against the other) — single impl
_index_to_ranges = ownership.index_to_ranges


def _slice_pieces(
    plist, idx, shape: Tuple[int, ...], dtype, stats: Dict[str, int]
) -> np.ndarray:
    """Materialize exactly the requested region of a leaf from its
    staged pieces — the shard-wise restore callback. Never assembles
    the full array: either one piece CONTAINS the region (a contiguous
    slice of it comes back — the common case, since restore targets
    re-slice the same or a coarser grid than the save staged), or the
    region is assembled from the overlapping pieces at the region's
    extent (world-resize storage restores, where old-world shards tile
    differently). Uncovered gaps zero-fill, matching the historical
    full-array assembly (``np.zeros`` + piece copies)."""
    ranges = _index_to_ranges(idx, shape)
    extent = tuple(e - s for s, e in ranges)
    for p_index, arr, _ in plist:
        if all(
            ps <= ns and ne <= pe
            for (ns, ne), (ps, pe) in zip(ranges, p_index)
        ):
            rel = tuple(
                slice(ns - ps, ne - ps)
                for (ns, ne), (ps, pe) in zip(ranges, p_index)
            )
            stats["sliced"] = stats.get("sliced", 0) + 1
            # copy=True even when the slice is already contiguous: the
            # piece may be a VIEW into the shm segment, and the CPU
            # backend zero-copy-aliases host buffers into jax arrays —
            # an aliased restore would be silently overwritten by the
            # next staged save (and pins the segment against close())
            return np.array(arr[rel], dtype=dtype, copy=True)
    out = np.zeros(extent, dtype=dtype)
    for p_index, arr, _ in plist:
        inter = [
            (max(ns, ps), min(ne, pe))
            for (ns, ne), (ps, pe) in zip(ranges, p_index)
        ]
        if any(s >= e for s, e in inter):
            continue
        dst = tuple(
            slice(s - ns, e - ns) for (s, e), (ns, _) in zip(inter, ranges)
        )
        src = tuple(
            slice(s - ps, e - ps) for (s, e), (ps, _) in zip(inter, p_index)
        )
        out[dst] = arr[src]
    stats["region_assembled"] = stats.get("region_assembled", 0) + 1
    return out


#: live engines whose in-flight background stage must be drained at
#: teardown. Module-level (one atexit hook + one SIGTERM chain link per
#: PROCESS, not per engine) so repeatedly built engines — benches,
#: elastic rebuilds — neither grow the handler chain nor stay pinned
#: after close(). Weak refs: an engine abandoned without close() is
#: GC-collectable, not pinned (and not serially drained) forever.
_DRAIN_REGISTRY = weakref.WeakSet()
_drain_hooks_installed = False


def _drain_all_engines():
    for eng in list(_DRAIN_REGISTRY):
        try:
            eng._drain_at_exit()
        except BaseException as e:  # never let one engine's failure (or
            # a SystemExit smuggled out of a staging thread) skip the
            # remaining drains or the SIGTERM re-kill chain
            logger.warning("drain of %r at teardown failed: %s", eng, e)


def _install_drain_hooks():
    global _drain_hooks_installed
    if _drain_hooks_installed:
        return
    _drain_hooks_installed = True
    import atexit
    import signal

    atexit.register(_drain_all_engines)
    try:
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            _drain_all_engines()
            if callable(prev):
                prev(signum, frame)
            else:
                # prev is SIG_DFL/SIG_IGN — or None for a handler some C
                # extension installed, which Python cannot re-invoke; the
                # best available behavior is default-action re-kill
                signal.signal(signum, prev or signal.SIG_DFL)
                os.kill(os.getpid(), signum)
                # Reached only when the re-raise did not terminate us —
                # prev was SIG_IGN (the kill was ignored). Reinstall this
                # handler so LATER SIGTERMs still drain: leaving SIG_IGN
                # installed would let one survived SIGTERM permanently
                # disable crash-drain for the rest of the process.
                signal.signal(signum, _on_term)

        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):
        pass  # not the main thread: atexit alone still covers exits


#: a device-side snapshot is taken only where every local device has
#: this many times the bytes of its shards free
_SNAPSHOT_SLACK = 1.15


class CheckpointEngine:
    def __init__(
        self,
        ckpt_dir: str,
        job_name: str = "",
        node_id: Optional[int] = None,
        process_id: Optional[int] = None,
        storage: Optional[CheckpointStorage] = None,
        socket_path: str = "",
        master_client=None,
        async_staging: Optional[bool] = None,
        dedup: Optional[bool] = None,
        ownership_world: Optional[Tuple[int, int]] = None,
    ):
        from dlrover_tpu.common.constants import NodeEnv

        self.ckpt_dir = ckpt_dir
        # warm-path elasticity: the checkpoint dir is the one path the
        # deployment already persists across pod restarts, so when no
        # explicit compile-cache dir was configured, default JAX's
        # persistent compilation cache under it — a restarted worker
        # then rebuilds its train step from cache (never overrides a
        # dir jax already has; no-op under DLROVER_TPU_WARM_COMPILE=0)
        try:
            from dlrover_tpu.train.warm_compile import default_cache_under

            default_cache_under(ckpt_dir)
        except Exception:
            pass  # cache is an optimization, never a ckpt failure
        self.job_name = job_name or flags.JOB_NAME.get()
        self.node_id = (
            node_id
            if node_id is not None
            else int(flags.NODE_ID.get())
        )
        self.process_id = (
            process_id
            if process_id is not None
            else int(flags.PROCESS_ID.get())
        )
        self._storage = storage or PosixDiskStorage()
        self._shm = SharedMemoryHandler(
            shm_name(self.job_name, self.node_id, self.process_id), create=True
        )
        self._socket_path = socket_path or default_socket_path(
            self.job_name, self.node_id
        )
        self._event_queue: Optional[SharedQueue] = None
        self._shm_lock: Optional[SharedLock] = None
        self._persist_state: Optional[SharedDict] = None
        self._awaiting_persist = -1
        self._master_client = master_client
        self.latest_saved_step = -1
        # Async staging (default ON): the training pause is one jitted
        # device-side copy of the state into fresh (non-donated) HBM
        # buffers — milliseconds, independent of the d2h link — after
        # which the d2h transfer and the host->shm memcpy both run in a
        # background thread against the snapshot. Donation safety: the
        # trainer's jitted step donates state buffers via donate_argnums,
        # which invalidates the source arrays the moment the next step
        # runs; the snapshot's buffers are XLA outputs with no
        # input-output aliasing, so they survive any later donation.
        # When HBM headroom cannot fit a second copy of the state the
        # stage degrades to blocking for the d2h transfer (the round-3
        # behavior); torch engines block for the whole shm stage
        # (reference blocks ~0.5 s, flash_checkpoint.md:362-415).
        if async_staging is None:
            async_staging = flags.ASYNC_STAGING.get()
        self._async_staging = bool(async_staging)
        self._device_snapshot_enabled = flags.DEVICE_SNAPSHOT.get()
        self._snap_fn = None
        self._staging_thread: Optional[threading.Thread] = None
        self._staging_error: Optional[BaseException] = None
        self._crash_drain_installed = False
        #: how the last save staged: "device_snapshot" (pause = HBM copy),
        #: "host_gather" (pause = d2h transfer), or "sync"
        self.last_stage_mode = ""
        #: how the last targeted restore placed its leaves: counts of
        #: "sliced" (single containing piece — zero assembly),
        #: "region_assembled" (requested extent built from overlapping
        #: pieces) and "full_assembled" (host-target fallback); tiered
        #: restores add "tier" (shm|disk|object — the deepest rung that
        #: had to contribute pieces), "tiers_read", "pieces" and "bytes"
        self.last_restore_stats: Dict[str, Any] = {}
        #: what the last stage kept vs skipped (replica-deduplicated
        #: staging): staged_bytes / skipped_replica_bytes / dedup
        self.last_stage_stats: Dict[str, Any] = {}
        # Replica-deduplicated tiered checkpointing (ownership.py):
        # `dedup` overrides the DLROVER_TPU_CKPT_DEDUP kill-switch;
        # `ownership_world` = (rank, world) simulates an N-process world
        # from one process (the tests) — the device
        # list is split into `world` contiguous virtual nodes.
        self._dedup = dedup
        self._ownership_world = ownership_world
        # the local tier is node-local disk by definition — plain posix,
        # independent of the configurable object-tier storage
        self._local_tier_storage = PosixDiskStorage()
        # lazy; lives as long as the engine so its pending-fanout retry
        # state survives across bare-run saves (_persist_inline)
        self._inline_persister = None

    # -- IPC (lazy: standalone use without an agent works too) --------------

    def _ipc_available(self) -> bool:
        return os.path.exists(self._socket_path)

    def _queue(self) -> Optional[SharedQueue]:
        if self._event_queue is None and self._ipc_available():
            self._event_queue = SharedQueue(CKPT_EVENT_QUEUE, self._socket_path)
        return self._event_queue

    def _lock(self) -> Optional[SharedLock]:
        if self._shm_lock is None and self._ipc_available():
            self._shm_lock = SharedLock(SHM_LOCK, self._socket_path)
        return self._shm_lock

    def _persist_dict(self) -> Optional[SharedDict]:
        if self._persist_state is None and self._ipc_available():
            self._persist_state = SharedDict(
                PERSIST_STATE_DICT, self._socket_path
            )
        return self._persist_state

    def _wait_pending_persist(self, timeout: float = 120.0):
        """Back-pressure: a queued disk persist reads the CURRENT shm, so
        staging the next step before the saver's copy would silently drop
        the persisted step (the saver refuses mismatched steps). Block
        until the saver reports the copy done (reference analogue: the
        trainer's next save contends on the saver-held shm lock)."""
        if self._awaiting_persist < 0:
            return
        state = self._persist_dict()
        if state is None:
            self._awaiting_persist = -1
            return
        deadline = time.time() + timeout
        key = f"copied-{self.process_id}"
        while time.time() < deadline:
            try:
                copied = state.get(key)
            except Exception:
                break
            if copied is not None and int(copied) >= self._awaiting_persist:
                self._awaiting_persist = -1
                return
            time.sleep(0.02)
        logger.warning(
            "persist of step %s still pending after %.0fs; staging anyway "
            "(that step may not reach storage)",
            self._awaiting_persist,
            timeout,
        )
        self._awaiting_persist = -1

    # -- save ---------------------------------------------------------------

    def _ownership_info(self):
        """(rank, world, device->rank) when replica-deduplicated staging
        applies, else None. Real worlds partition by process; the
        ``ownership_world`` ctor override simulates N virtual nodes from
        one process (the tests)."""
        enabled = (
            bool(self._dedup) if self._dedup is not None
            else flags.CKPT_DEDUP.get()
        )
        if not enabled:
            return None
        if self._ownership_world is not None:
            rank, world = self._ownership_world
            if world <= 1:
                return None
            return int(rank), int(world), ownership.virtual_proc_of(world)
        import jax

        world = jax.process_count()
        if world <= 1:
            return None
        return jax.process_index(), world, ownership.real_proc_of()

    def _tiering_enabled(self) -> bool:
        """Tiered restore rides the same kill-switch as dedup staging —
        but unlike staging it applies at ANY world size (a 1-process
        world still restores through shm -> local disk -> object)."""
        if self._dedup is not None:
            return bool(self._dedup)
        return flags.CKPT_DEDUP.get()

    def _gather_local_shards(self, state):
        """device_get each leaf's unique addressable shards — under
        replica-deduplicated staging, only the shards this process OWNS
        (ownership.py's disjoint partition; replicated leaves round-robin
        across the dp replicas so each stages ~1/dp of them).

        Returns (named_leaves, shard_info, treedef_bytes, leaf_paths)
        where named_leaves are (path#k, np array) entries for the shm
        segment and leaf_paths is the FULL flattened leaf set (restore
        uses it to tell "never saved" from "piece missing").
        """
        import jax

        flat, treedef_bytes = flatten_state_lazy(state)
        leaf_paths = [p for p, _ in flat]
        own = self._ownership_info()
        # one round-robin stream per stage, advanced in flatten order —
        # identical on every process (ownership.py's determinism rule)
        rr = ownership.RoundRobin() if own is not None else None
        skipped_bytes = 0
        # Pass 1: select each leaf's unique addressable shards (replicated
        # duplicates are skipped, never transferred) and issue all their
        # device->host transfers together, so the copies overlap on the
        # transfer engine instead of serializing behind np.asarray.
        # Plan entries: (name, data, extent, shard ranges, global shape,
        # owned sub-pieces). ``subs`` None stages the whole shard; a
        # list means the shard region was dp-round-robin SPLIT and only
        # the listed owned chunks are staged (sliced on host in pass 2).
        plan: List[Tuple[str, Any, Tuple[int, ...], Tuple,
                         Tuple[int, ...], Optional[list]]] = []

        def _owned_vol(pieces) -> int:
            total = 0
            for a in pieces:
                v = 1
                for s, e in a.ranges:
                    v *= max(0, e - s)
                total += v
            return total

        with trace.span("ckpt_save", "d2h.issue") as issued:
            for path, leaf in flat:
                if isinstance(leaf, jax.Array) and hasattr(
                        leaf, "addressable_shards"):
                    owned_by_parent = None
                    if own is not None:
                        try:
                            assigns = ownership.assign_leaf(
                                tuple(leaf.shape), leaf.sharding, own[2], rr
                            )
                            owned_by_parent = {}
                            for a in assigns:
                                if a.owner == own[0]:
                                    owned_by_parent.setdefault(
                                        a.parent_ranges, []
                                    ).append(a)
                        except Exception as e:
                            # degrade to staging every unique shard: a leaf we
                            # cannot partition must never be silently dropped
                            logger.warning(
                                "ownership derivation failed for %s (%s); "
                                "staging all unique shards", path, e,
                            )
                    seen = set()
                    k = 0
                    for shard in leaf.addressable_shards:
                        ranges = _index_to_ranges(shard.index, leaf.shape)
                        if ranges in seen:
                            continue
                        seen.add(ranges)
                        shard_bytes = int(
                            np.prod(shard.data.shape, dtype=np.int64)
                            * shard.data.dtype.itemsize
                        )
                        subs = None
                        if owned_by_parent is not None:
                            mine = owned_by_parent.get(ranges, [])
                            if not mine:
                                skipped_bytes += shard_bytes
                                continue
                            if len(mine) > 1 or mine[0].ranges != ranges:
                                subs = mine
                                skipped_bytes += shard_bytes - (
                                    _owned_vol(mine)
                                    * shard.data.dtype.itemsize
                                )
                        try:
                            shard.data.copy_to_host_async()
                        except Exception:
                            pass
                        extent = tuple(e - s for s, e in ranges)
                        plan.append(
                            (f"{path}#s{k}", shard.data, extent, ranges,
                             tuple(leaf.shape), subs)
                        )
                        k += 1
                else:
                    arr = np.asarray(leaf)
                    full = tuple((0, d) for d in arr.shape)
                    subs = None
                    if own is not None:
                        mine = [
                            a
                            for a in ownership.assign_host_leaf(
                                tuple(arr.shape), own[1], rr
                            )
                            if a.owner == own[0]
                        ]
                        if not mine:
                            skipped_bytes += int(arr.nbytes)
                            continue
                        if len(mine) > 1 or mine[0].ranges != full:
                            subs = mine
                            skipped_bytes += int(arr.nbytes) - (
                                _owned_vol(mine) * arr.dtype.itemsize
                            )
                    plan.append(
                        (f"{path}#s0", arr, tuple(arr.shape), full,
                         tuple(arr.shape), subs)
                    )
            issued.set(shards=len(plan))
        # Pass 2: consume (np.asarray reuses the host literal the async
        # copy produced, so this is a wait + memcpy, not a transfer).
        # Split shards stage only their owned chunks — sliced views of
        # the host shard, materialized by the shm memcpy.
        named_leaves: List[Tuple[str, np.ndarray]] = []
        shard_info: Dict[str, Tuple[Tuple[int, ...], Tuple]] = {}
        staged_bytes = 0
        with trace.span("ckpt_save", "d2h.wait") as waited:
            for name, data, extent, ranges, gshape, subs in plan:
                host = np.asarray(data).reshape(extent)
                if subs is None:
                    staged_bytes += int(host.nbytes)
                    named_leaves.append((name, host))
                    shard_info[name] = (gshape, ranges)
                    continue
                for j, a in enumerate(subs):
                    rel = tuple(
                        slice(s - ps, e - ps)
                        for (s, e), (ps, _) in zip(a.ranges, ranges)
                    )
                    piece = np.ascontiguousarray(host[rel])
                    staged_bytes += int(piece.nbytes)
                    sub_name = f"{name}.{j}"
                    named_leaves.append((sub_name, piece))
                    shard_info[sub_name] = (gshape, a.ranges)
            waited.set(bytes=staged_bytes)
        # single-writer (the staging thread); readers only sample it
        self.last_stage_stats = {
            "staged_bytes": staged_bytes,
            "skipped_replica_bytes": skipped_bytes,
            "dedup": own is not None,
        }
        return named_leaves, shard_info, treedef_bytes, leaf_paths

    def save_to_memory(self, step: int, state: Any) -> float:
        """Stage into shm; returns the blocking seconds (the training pause).

        With ``async_staging`` the stage runs in a background thread and
        this returns in microseconds; a subsequent save (or load/close)
        joins the in-flight stage first.
        """
        t0 = time.time()
        # trace spine: the training PAUSE this save cost (the background
        # stage has its own span, on the staging thread, caused by this)
        with trace.span("ckpt_save", "save.blocking", step=step,
                        tier="shm") as pause:
            if self._async_staging:
                blocking = self._start_async_stage(
                    t0, step, state, persist=False, cause=pause.id)
            else:
                try:
                    self._stage_sync(step, state)
                except TimeoutError as e:
                    logger.warning("%s; skipping memory save", e)
                    return time.time() - t0
                blocking = time.time() - t0
                self._report_save(step, blocking)
            pause.set(mode=self.last_stage_mode)
        return blocking

    def _install_crash_drain(self):
        """Join in-flight staging on every teardown the interpreter can
        see: atexit (covers normal exit AND uncaught exceptions) plus a
        chained SIGTERM handler (covers agent-driven restarts and k8s
        preemption grace windows). The device snapshot dies with the
        process, so draining at teardown is what turns "save() returned"
        into "that step is recoverable" for every crash short of SIGKILL.
        A hard kill falls back to the last drained step — or, if the kill
        lands inside the shm write itself (the header is invalidated
        before the payload memcpy and republished after, so a torn write
        can never be READ as valid), to the last disk persist; the
        master's shard queues replay the lost steps exactly
        (tests/test_ckpt_e2e.py covers both crash modes). Reference
        blocks through the shm write instead (engine.py:155-502) — zero
        window, but the pause scales with the d2h link."""
        if self._crash_drain_installed:
            return
        self._crash_drain_installed = True
        _DRAIN_REGISTRY.add(self)
        _install_drain_hooks()

    def _drain_at_exit(self):
        # Default 20 s: comfortably under Kubernetes' default 30 s
        # termination grace, leaving the previous SIGTERM handler's
        # cleanup time to run before the kubelet's SIGKILL. Raise it in
        # lockstep with terminationGracePeriodSeconds on slow d2h links
        # (deploy/k8s/README.md documents the pairing).
        timeout = float(flags.DRAIN_TIMEOUT.get())
        try:
            self.wait_staging(timeout=timeout)
        except BaseException as e:  # staging errors are stored broadly
            logger.warning("checkpoint drain at exit failed: %s", e)

    def _start_async_stage(
        self, t0: float, step: int, state: Any, persist: bool,
        cause: Optional[int] = None,
    ) -> float:
        self._install_crash_drain()
        # Degrade, don't crash training: a failure of the PREVIOUS cycle's
        # staging (incl. its shm-lock timeout) means that step was lost —
        # log it and carry on with this one. The unbounded join means the
        # previous thread is always finished here, so the shm is free.
        with trace.span("ckpt_save", "save.join_previous",
                        joined=int(self._staging_thread is not None
                                   and self._staging_thread.is_alive())):
            try:
                self.wait_staging()
            except Exception as e:
                logger.warning(
                    "previous background staging failed (%s); continuing", e
                )
        self._staging_error = None
        # Preferred: device-side snapshot — blocking cost is one HBM->HBM
        # copy; the d2h transfer moves to the background thread, so the
        # training pause is independent of the host link speed.
        payload = self._snapshot_on_device(state)
        on_device = payload is not None
        self.last_stage_mode = "device_snapshot" if on_device else "host_gather"
        if not on_device:
            # Fallback (no headroom / no device arrays / snapshot off):
            # d2h transfers happen HERE, synchronously, before the
            # caller's next (buffer-donating) train step can run. Only
            # host memory is touched after this point.
            try:
                payload = self._gather_local_shards(state)
            except Exception as e:
                logger.warning("device->host snapshot of step %s failed: %s",
                               step, e)
                # surface on the next wait_staging/load/close — a silently
                # dead snapshot path would let a job train for hours while
                # believing it is checkpointing
                self._staging_error = e
                return time.time() - t0
        pause = time.time() - t0
        self._staging_thread = threading.Thread(
            target=self._stage_in_background,
            args=(step, payload, on_device, persist, pause, cause),
            name="ckpt-staging",
            daemon=True,
        )
        self._staging_thread.start()
        return time.time() - t0

    # -- device-side snapshot ----------------------------------------------

    def _snapshot_on_device(self, state):
        """Copy every device-array leaf into fresh HBM buffers via one
        jitted copy (milliseconds). Returns the snapshot pytree, or None
        when the engine should fall back to the blocking d2h stage
        (snapshot disabled, nothing on device, insufficient HBM headroom,
        or the copy itself failed, e.g. a racing allocation OOMed it).
        The ``save.snapshot`` span says which (``why``)."""
        with trace.span("ckpt_save", "save.snapshot") as sp:
            snapshot, why = self._try_snapshot(state, sp)
            sp.set(taken=int(snapshot is not None), why=why)
        return snapshot

    def _try_snapshot(self, state, sp):
        """(snapshot, "") or (None, why not)."""
        if not self._device_snapshot_enabled:
            return None, "off"
        import jax

        flat, treedef = jax.tree_util.tree_flatten(state)
        idx = [
            i
            for i, leaf in enumerate(flat)
            if isinstance(leaf, jax.Array)
            and hasattr(leaf, "addressable_shards")
        ]
        if not idx:
            return None, "no_arrays"
        need, free = self._hbm_headroom([flat[i] for i in idx])
        sp.set(need_bytes=need, free_bytes=free)
        if free is not None and free < need * _SNAPSHOT_SLACK:
            logger.warning(
                "insufficient HBM headroom for a device-side checkpoint "
                "snapshot; blocking for the d2h transfer instead"
            )
            return None, "headroom"
        if self._snap_fn is None:
            import jax.numpy as jnp

            # jnp.copy under jit lowers to a real copy op: without
            # donation XLA never aliases an entry parameter into an
            # output buffer, so the results are independent of the
            # (soon-to-be-donated) source arrays.
            self._snap_fn = jax.jit(
                lambda xs: [jnp.copy(x) for x in xs]
            )
        try:
            copies = self._snap_fn([flat[i] for i in idx])
            jax.block_until_ready(copies)
        except Exception as e:
            logger.warning(
                "device-side snapshot failed (%s); blocking for the d2h "
                "transfer instead", e
            )
            return None, "failed"
        for i, c in zip(idx, copies):
            flat[i] = c
        return jax.tree_util.tree_unflatten(treedef, flat), ""

    @staticmethod
    def _hbm_headroom(arrays) -> Tuple[int, Optional[int]]:
        """(bytes a second copy of its shards takes, bytes free) on the
        local device with the least room to spare. Free is None when the
        backend exposes no memory stats (CPU): the caller is optimistic
        then."""
        need: Dict[Any, int] = {}
        for leaf in arrays:
            seen = set()
            for shard in leaf.addressable_shards:
                ranges = _index_to_ranges(shard.index, leaf.shape)
                if ranges in seen:
                    continue
                seen.add(ranges)
                nbytes = int(
                    np.prod(shard.data.shape, dtype=np.int64)
                    * shard.data.dtype.itemsize
                )
                need[shard.device] = need.get(shard.device, 0) + nbytes
        tightest = (max(need.values(), default=0), None)
        for dev, nbytes in need.items():
            try:
                stats = dev.memory_stats()
            except Exception:
                continue
            if not stats:
                continue
            limit = stats.get("bytes_limit")
            used = stats.get("bytes_in_use")
            if not limit or used is None:
                continue
            free = int(limit - used)
            if tightest[1] is None or (
                free - nbytes < tightest[1] - tightest[0]
            ):
                tightest = (nbytes, free)
        return tightest

    def wait_staging(self, timeout: Optional[float] = None):
        """Join any in-flight background stage; re-raise its failure.
        Raises TimeoutError (keeping the thread tracked) if it is still
        running after ``timeout`` — callers must not touch the shm then."""
        thread = self._staging_thread
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                raise TimeoutError(
                    f"checkpoint staging still running after {timeout}s"
                )
            self._staging_thread = None
        if self._staging_error is not None:
            err, self._staging_error = self._staging_error, None
            raise err

    def _stage_in_background(
        self, step: int, payload, on_device: bool, persist: bool,
        pause: float, cause: Optional[int] = None,
    ):
        try:
            with trace.span("ckpt_save", "stage.background", cause=cause,
                            step=step, tier="shm"):
                if on_device:
                    # d2h off the training critical path: the source is
                    # the private device snapshot, untouchable by
                    # donation.
                    payload = self._gather_local_shards(payload)
                with trace.span("ckpt_save", "stage.wait_persist"):
                    self._wait_pending_persist()
                self._write_shm(step, payload)
            if persist:
                self._queue_persist(step)
            self._report_save(step, pause)
        except BaseException as e:  # surfaced on the next wait_staging
            logger.exception("background staging of step %s failed", step)
            # single pointer write; the only reader (wait_staging) joins
            # this thread first, so the join IS the happens-before edge
            # a lock would add  # graftlint: disable=JG006
            self._staging_error = e
        finally:
            payload = None  # free the snapshot's HBM buffers promptly

    def _report_save(self, step: int, blocking: float):
        if self._master_client is not None:
            try:
                self._master_client.report_ckpt_step(step, blocking)
            except Exception:
                pass

    def _stage_sync(self, step: int, state: Any):
        self.last_stage_mode = "sync"
        with trace.span("ckpt_save", "stage.wait_persist"):
            self._wait_pending_persist()
        self._write_shm(step, self._gather_local_shards(state))

    def _write_shm(self, step: int, snapshot):
        import jax

        named_leaves, shard_info, treedef_bytes, leaf_paths = snapshot
        lock = self._lock()
        with trace.span("ckpt_save", "stage.shm_lock"):
            if lock is not None and not lock.acquire(timeout=120):
                raise TimeoutError(
                    f"shm lock not acquired in 120s; step {step} not staged"
                )
        staged_bytes = sum(int(a.nbytes) for _, a in named_leaves)
        try:
            with trace.span("ckpt_save", "stage.shm_write",
                            bytes=staged_bytes):
                self._shm.save_state(
                    step,
                    named_leaves,
                    treedef_bytes,
                    shard_info=shard_info,
                    world_size=jax.process_count(),
                    process_id=self.process_id,
                    ckpt_dir=os.path.abspath(self.ckpt_dir),
                    leaf_paths=leaf_paths,
                )
        finally:
            if lock is not None:
                lock.release()
        self.latest_saved_step = step
        trace.gauge("ckpt.staged_bytes", staged_bytes)
        # replica mode (agent-set env): tell the saver to stream this staged
        # state to the backup peer, off the training critical path
        if flags.CKPT_REPLICA.get() == "1":
            q = self._queue()
            if q is not None:
                q.put(CheckpointEvent("backup", step=step).to_wire())

    def _queue_persist(self, step: int):
        q = self._queue()
        if q is not None:
            q.put(
                CheckpointEvent(
                    "save", step=step, persist=True, ckpt_dir=self.ckpt_dir
                ).to_wire()
            )
            self._awaiting_persist = step
        else:
            # no agent (bare run): persist synchronously in-process
            self._persist_inline(step)

    def save_to_storage(self, step: int, state: Any) -> float:
        """Stage + hand persistence to the agent saver (async)."""
        t0 = time.time()
        with trace.span("ckpt_save", "save.blocking", step=step,
                        tier="shm", persist=True) as pause:
            if self._async_staging:
                blocking = self._start_async_stage(
                    t0, step, state, persist=True, cause=pause.id)
            else:
                try:
                    self._stage_sync(step, state)
                except TimeoutError as e:
                    # staging was skipped (shm lock timeout): queuing a
                    # persist event would make the saver persist a stale
                    # step as if it were this one — surface the failure
                    # instead
                    logger.error("%s; skipping persist", e)
                    return time.time() - t0
                self._queue_persist(step)
                blocking = time.time() - t0
                self._report_save(step, blocking)
            pause.set(mode=self.last_stage_mode)
        return blocking

    def _persist_inline(self, step: int):
        import jax

        from dlrover_tpu.checkpoint.saver import CheckpointPersister

        # one long-lived persister per engine, NOT per save: its
        # _pending_fanout set is what lets a transiently-failed object
        # fanout retry on the next cycle (and protects those steps from
        # local-tier pruning) — a throwaway instance would discard both
        if self._inline_persister is None:
            self._inline_persister = CheckpointPersister(
                job_name=self.job_name,
                node_id=self.node_id,
                node_rank=jax.process_index(),
                num_nodes=jax.process_count(),
                local_process_ids=[self.process_id],
                storage=self._storage,
            )
        self._inline_persister.persist_step(self.ckpt_dir, step)

    # -- load ---------------------------------------------------------------

    def load(self, target: Any = None) -> Optional[Tuple[int, Any]]:
        """Restore (step, state) through the tier ladder: shm, then the
        node-local disk tier, then the shared object tier — each rung
        ADDING the pieces the previous rungs were missing (replica-
        deduplicated saves spread the pieces across processes, so no
        single rung need be complete). Kill-switch off: the legacy
        two-rung shm -> storage restore."""
        try:
            self.wait_staging()
        except Exception as e:
            logger.warning("in-flight staging failed before load: %s", e)
        m0 = time.monotonic()
        if not self._tiering_enabled():
            result = self._load_from_memory(target)
            if result is not None:
                logger.info("restored step %s from shared memory", result[0])
            else:
                result = self._load_from_storage(target)
        else:
            result = self._load_tiered(target)
        # trace spine: one restore span, stamped with the tier that
        # actually supplied the state (shm | disk | object | storage)
        trace.record(
            "ckpt_restore", "restore", m0, time.monotonic() - m0,
            tier=str((self.last_restore_stats or {}).get("tier", "")),
            step=result[0] if result is not None else -1,
            ok=result is not None,
        )
        return result

    # -- tiered load (shm -> local disk -> object) --------------------------

    def _staged_shm_meta(self):
        """This process's staged shm meta, after the ownership gate
        (a different job's Checkpointer staging under the same shm name
        is not ours to restore)."""
        meta = self._shm.read_meta()
        if (
            meta is not None and meta.ckpt_dir
            and meta.ckpt_dir != os.path.abspath(self.ckpt_dir)
        ):
            logger.info(
                "staged shm belongs to %s (this engine: %s); ignoring",
                meta.ckpt_dir, os.path.abspath(self.ckpt_dir),
            )
            return None
        return meta

    def _load_tiered(self, target: Any = None):
        import jax

        shm_meta = self._staged_shm_meta()
        shm_step = shm_meta.step if shm_meta is not None else -1
        # Restore-time consistency gate: every process must attempt the
        # SAME staged step, else one host restores step N and another
        # N-1 and the job trains from a torn state (the legacy memory
        # path's gate, kept verbatim for the shm rung).
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            steps = np.asarray(
                multihost_utils.process_allgather(np.array([shm_step]))
            ).reshape(-1)
            if not (steps == steps[0]).all():
                logger.warning(
                    "staged steps disagree across processes (%s); "
                    "ignoring the shm tier",
                    steps.tolist(),
                )
                shm_meta, shm_step = None, -1
        committed = self.committed_step()
        # Agree on the committed candidate too: the tracker is a shared
        # file a concurrent commit may be rewriting, so per-process
        # reads can return N and N-1 — candidate lists of different
        # content (torn adoption) or length (mismatched collective
        # counts below = hang). The MIN is the value every process has
        # definitely observed as committed.
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            committed = int(
                np.asarray(
                    multihost_utils.process_allgather(
                        np.array([committed])
                    )
                ).min()
            )
        candidates = []
        if shm_step >= 0:
            candidates.append(shm_step)
        if committed >= 0 and committed != shm_step:
            candidates.append(committed)
        # The candidate list is now identical on every process (both
        # entries were just agreed), so the loop ITSELF needs agreement
        # only on each attempt's OUTCOME: one node may cover an
        # uncommitted staged step from its tiers while another cannot
        # (its peer's fanout died mid-write) — returning per-process
        # would resume one host at step N and another at M < N, a torn
        # state. Every process therefore votes after each attempt and a
        # candidate is adopted only unanimously.
        for step in candidates:
            result = self._restore_step_tiered(
                step, target, shm_meta if step == shm_step else None
            )
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                oks = np.asarray(
                    multihost_utils.process_allgather(
                        np.array([1 if result is not None else 0])
                    )
                ).reshape(-1)
                if not oks.all():
                    if result is not None:
                        logger.warning(
                            "step %s restorable here but not on %d peer "
                            "process(es); discarding for the next "
                            "candidate", step, int((oks == 0).sum()),
                        )
                    result = None
            if result is not None:
                return result
        if candidates:
            # fail LOUDLY: coverage gaps after the last rung mean lost
            # pieces, and a silently partial (zero-filled) state is the
            # one outcome worse than no restore at all
            logger.error(
                "tiered restore failed: no tier union covers the target "
                "for candidate steps %s (shm/local-disk/object read)",
                candidates,
            )
        return None

    def _merge_tier_pieces(
        self, storage, sdir: str, step: int, pieces, seen, expected
    ) -> Tuple[str, int, int]:
        """Merge one disk-layout tier's pieces for ``step`` into
        ``pieces``, skipping (leaf, region)s already supplied by an
        earlier rung and CRC-verifying each leaf file (a corrupt piece
        is dropped with a warning — the next rung supplies it).
        Returns (treedef_hex, pieces_added, bytes_added)."""
        added_p = added_b = 0
        tdef = ""
        for name in storage.listdir(sdir):
            if not name.startswith("proc-"):
                continue
            pdir = os.path.join(sdir, name)
            try:
                meta = CheckpointMeta.from_json(
                    storage.read(os.path.join(pdir, "meta.json")).decode()
                )
            except (FileNotFoundError, ValueError, KeyError):
                continue  # manifest-less dir = torn write; skip it
            if meta.step != step:
                continue
            tdef = tdef or meta.treedef_hex
            if not expected and meta.leaf_paths:
                # every manifest records the same complete list, in
                # flatten order — first one wins
                expected.extend(meta.leaf_paths)
            for i, lm in enumerate(meta.leaves):
                base = lm.path.rsplit("#", 1)[0]
                key = (base, lm.index)
                if key in seen:
                    continue
                try:
                    data = storage.read(os.path.join(pdir, f"leaf-{i}.bin"))
                except (FileNotFoundError, OSError):
                    continue
                if lm.crc32 and zlib.crc32(data) != (lm.crc32 & 0xFFFFFFFF):
                    logger.warning(
                        "CRC mismatch for %s piece %s under %s; dropping "
                        "the corrupt piece (a later tier supplies it)",
                        base, lm.index, pdir,
                    )
                    continue
                try:
                    arr = np.frombuffer(
                        data, dtype=resolve_dtype(lm.dtype)
                    ).reshape(lm.shape)
                except (ValueError, TypeError) as e:
                    logger.warning(
                        "unreadable piece %s under %s (%s); dropping",
                        base, pdir, e,
                    )
                    continue
                pieces.setdefault(base, []).append(
                    (lm.index, arr, lm.global_shape)
                )
                seen.add(key)
                added_p += 1
                added_b += len(data)
        return tdef, added_p, added_b

    def _restore_step_tiered(self, step: int, target, shm_meta):
        """Accumulate pieces for ``step`` rung by rung, attempting the
        assemble after every rung that contributed — the deepest rung
        actually read is the restore's tier attribution."""
        pieces: Dict[str, List[Tuple[Tuple, np.ndarray, Tuple[int, ...]]]] = {}
        seen: set = set()
        expected: List[str] = []  # full leaf list, manifest flatten order
        treedef_hex = ""
        contributed: List[str] = []
        total_p = total_b = 0

        def attempt():
            if not pieces:
                return None
            return self._assemble(
                step, (treedef_hex, pieces), target, full_data=False,
                expected_paths=expected or None,
            )

        def success(result):
            stats = (
                self.last_restore_stats if target is not None else {}
            )
            stats["tier"] = contributed[-1] if contributed else "shm"
            stats["tiers_read"] = list(contributed)
            stats["pieces"] = total_p
            stats["bytes"] = total_b
            self.last_restore_stats = stats
            logger.info(
                "restored step %s via tier %s (%d pieces, %d bytes, "
                "rungs read: %s)",
                step, stats["tier"], total_p, total_b, contributed,
            )
            return result

        # tier 0: this process's shm segment
        if shm_meta is not None and shm_meta.step == step:
            treedef_hex = shm_meta.treedef_hex
            if not expected and shm_meta.leaf_paths:
                expected.extend(shm_meta.leaf_paths)
            _, shm_pieces = self._read_pieces_from_shm(
                shm_meta, copy=target is None
            )
            added = 0
            for base, plist in shm_pieces.items():
                for idx, arr, gshape in plist:
                    key = (base, tuple(idx))
                    if key in seen:
                        continue
                    seen.add(key)
                    pieces.setdefault(base, []).append((idx, arr, gshape))
                    added += 1
                    total_b += int(arr.nbytes)
            if added:
                total_p += added
                contributed.append("shm")
                result = attempt()
                if result is not None:
                    return success(result)
        # tier 1: the node-local disk tier (union across this node's
        # process manifests)
        local_sdir = step_dir(
            local_tier_dir(self.ckpt_dir, self.node_id), step
        )
        tdef, p, b = self._merge_tier_pieces(
            self._local_tier_storage, local_sdir, step, pieces, seen,
            expected,
        )
        treedef_hex = treedef_hex or tdef
        if p:
            total_p += p
            total_b += b
            contributed.append("disk")
            result = attempt()
            if result is not None:
                return success(result)
        # tier 2: the shared object tier (union across ALL nodes)
        obj_sdir = step_dir(self.ckpt_dir, step)
        tdef, p, b = self._merge_tier_pieces(
            self._storage, obj_sdir, step, pieces, seen, expected
        )
        treedef_hex = treedef_hex or tdef
        if p:
            total_p += p
            total_b += b
            contributed.append("object")
            result = attempt()
            if result is not None:
                return success(result)
        return None

    def _load_from_memory(self, target: Any = None):
        import jax

        meta = self._shm.read_meta()
        if (
            meta is not None and meta.ckpt_dir
            and meta.ckpt_dir != os.path.abspath(self.ckpt_dir)
        ):
            # a different job's Checkpointer (same shm key: default job
            # name) staged this segment — it is not ours to restore
            logger.info(
                "staged shm belongs to %s (this engine: %s); ignoring",
                meta.ckpt_dir, os.path.abspath(self.ckpt_dir),
            )
            meta = None
        step = -1
        if meta is not None and meta.world_size == jax.process_count():
            step = meta.step
        elif meta is not None:
            # The world resized: this process's staged shards no longer
            # cover what the new mesh assigns it. Storage has all shards.
            logger.info(
                "staged shm is from a %s-process world (now %s); "
                "falling back to storage restore",
                meta.world_size,
                jax.process_count(),
            )
        # Restore-time consistency gate: every process must hold the SAME
        # staged step, else one host restores step N and another N-1 and
        # the job trains from a torn state. The reference guards this at
        # save time with a gloo allgather (engine.py:76-95); gating at
        # restore keeps the save hot path collective-free.
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            steps = np.asarray(
                multihost_utils.process_allgather(np.array([step]))
            ).reshape(-1)
            if not (steps == steps[0]).all():
                logger.warning(
                    "staged steps disagree across processes (%s); "
                    "falling back to storage restore",
                    steps.tolist(),
                )
                return None
            step = int(steps[0])
        if step < 0 or meta is None:
            return None
        # With a target the placement callback copies just the slices it
        # is asked for, so the leaves can stay VIEWS into the shm buffer
        # (no up-front whole-leaf memcpy). Without a target the restored
        # pytree itself would alias shm — copy as before.
        pieces = self._read_pieces_from_shm(meta, copy=target is None)
        result = self._assemble(meta.step, pieces, target, full_data=False)
        if result is not None and target is not None:
            stats = self.last_restore_stats
            stats["tier"] = "shm"
            stats["tiers_read"] = ["shm"]
            stats["pieces"] = len(meta.leaves)
            stats["bytes"] = int(sum(lm.nbytes for lm in meta.leaves))
        return result

    def _load_from_storage(self, target: Any = None):
        """Legacy (kill-switch-off) storage restore — one object rung
        through the same manifest reader as the ladder, so CRC
        verification and torn-dir skipping apply here too."""
        step = self.committed_step()
        if step < 0:
            return None
        sdir = step_dir(self.ckpt_dir, step)
        pieces: Dict[str, List[Tuple[Tuple, np.ndarray, Tuple[int, ...]]]] = {}
        expected: List[str] = []
        treedef_hex, _, _ = self._merge_tier_pieces(
            self._storage, sdir, step, pieces, set(), expected
        )
        if not pieces:
            return None
        result = self._assemble(
            step, (treedef_hex, pieces), target, full_data=True,
            expected_paths=expected or None,
        )
        if result is not None:
            if target is not None:
                stats = self.last_restore_stats
                stats["tier"] = "object"
                stats["tiers_read"] = ["object"]
                stats["pieces"] = sum(len(p) for p in pieces.values())
                stats["bytes"] = int(sum(
                    a.nbytes
                    for plist in pieces.values()
                    for _, a, _ in plist
                ))
            logger.info("restored step %s from storage %s", step, sdir)
        return result

    def _read_pieces_from_shm(self, meta: CheckpointMeta, copy: bool = True):
        pieces: Dict[str, List[Tuple[Tuple, np.ndarray, Tuple[int, ...]]]] = {}
        for leaf_meta in meta.leaves:
            arr = self._shm.read_leaf(leaf_meta, copy=copy)
            base = leaf_meta.path.rsplit("#", 1)[0]
            pieces.setdefault(base, []).append(
                (leaf_meta.index, arr, leaf_meta.global_shape)
            )
        return meta.treedef_hex, pieces

    def _assemble(
        self, step, treedef_and_pieces, target, full_data: bool,
        expected_paths: Optional[List[str]] = None,
    ):
        """Rebuild the pytree. With a ``target`` (pytree of jax.Arrays or
        ShapeDtypeStructs with shardings) arrays are placed per the target's
        sharding; otherwise plain numpy arrays are returned.

        ``expected_paths`` (tiered restores): the checkpoint's FULL leaf
        list from its manifests, in flatten order. A target leaf in that
        set with no pieces is MISSING DATA — return None so the caller
        reads the next tier (or fails loudly) — while a target leaf
        outside it was never saved (a new state field) and legitimately
        keeps its target value. Targetless restores also rebuild in the
        manifest's leaf order (merged multi-process pieces arrive
        grouped by process, not in flatten order)."""
        import jax

        treedef_hex, pieces = treedef_and_pieces
        expected_set = set(expected_paths) if expected_paths else None

        def build_full(path: str) -> Optional[np.ndarray]:
            plist = pieces.get(path)
            if not plist:
                return None
            _, first_arr, gshape = plist[0]
            # global_shape is always recorded at stage time; () is a
            # legitimate 0-d shape, not "absent".
            gshape = tuple(gshape)
            if len(plist) == 1 and tuple(first_arr.shape) == gshape:
                return plist[0][1]
            out = np.zeros(gshape, dtype=first_arr.dtype)
            for index, arr, _ in plist:
                sl = tuple(slice(s, e) for s, e in index)
                out[sl] = arr.reshape(tuple(e - s for s, e in index))
            return out

        def region_covered(needed, plist) -> bool:
            """The staged pieces' UNION covers the region — not just a
            single containing piece. A resize that re-tiles a leaf
            (zero-1 moments: dp4 staged quarters, dp2 target halves)
            makes each target shard span several staged pieces, which
            ``_slice_pieces`` assembles fine; requiring single-piece
            containment here would reject exactly those restores. The
            check partitions the region on the pieces' boundary grid
            and demands every cell lie inside some piece (pieces are
            per-device shards — the grid stays tiny)."""
            import itertools

            cuts = []
            for d, (ns, ne) in enumerate(needed):
                c = {ns, ne}
                for p_index, _, _ in plist:
                    ps, pe = p_index[d]
                    if ns < ps < ne:
                        c.add(ps)
                    if ns < pe < ne:
                        c.add(pe)
                edges = sorted(c)
                cuts.append(list(zip(edges, edges[1:])))
            for cell in itertools.product(*cuts):
                if not any(
                    all(
                        ps <= cs and ce <= pe
                        for (cs, ce), (ps, pe) in zip(cell, p_index)
                    )
                    for p_index, _, _ in plist
                ):
                    return False
            return True

        def covers_target(t_leaf, path: str) -> bool:
            """Partial (shm) data must cover every region the target's
            sharding assigns locally — else zero-fill would corrupt state."""
            if full_data:
                return True
            plist = pieces.get(path)
            if not plist:
                return False
            if not (isinstance(t_leaf, jax.Array) or hasattr(t_leaf, "sharding")):
                # host (unsharded) target: build_full materializes the
                # WHOLE array, so the pieces' union must tile all of it
                # — under dedup staging this process's shm holds only
                # its chunk of a split host leaf, and waving it through
                # would zero-fill the non-owned ranges
                gshape = tuple(plist[0][2])
                return region_covered(
                    tuple((0, d) for d in gshape), plist
                )
            shape = tuple(t_leaf.shape)
            # dedup via the normalized (start, stop) form: raw shard
            # indices are tuples of slice objects, which are unhashable
            # before Python 3.12 — set() over them is a TypeError here
            for needed in {
                _index_to_ranges(idx, shape)
                for idx in t_leaf.sharding.addressable_devices_indices_map(
                    shape
                ).values()
            }:
                if not region_covered(needed, plist):
                    return False
            return True

        if target is not None:
            stats: Dict[str, int] = {}
            flat_t, treedef = jax.tree_util.tree_flatten_with_path(target)
            out_leaves = []
            for path, t_leaf in flat_t:
                key = jax.tree_util.keystr(path)
                plist = pieces.get(key)
                if not plist:
                    if expected_set is not None and key in expected_set:
                        logger.warning(
                            "leaf %s is in the checkpoint manifest but no "
                            "pieces are available from the tiers read so "
                            "far", key,
                        )
                        return None
                    logger.warning("checkpoint missing leaf %s; keeping target", key)
                    out_leaves.append(t_leaf)
                    continue
                # global shape recorded at stage time; shape-gate without
                # assembling anything
                gshape = tuple(plist[0][2])
                if (
                    hasattr(t_leaf, "shape")
                    and gshape != tuple(t_leaf.shape)
                ):
                    # same leaf path but a different tensor shape: this is
                    # NOT our checkpoint (e.g. a stale shm segment from an
                    # unrelated job reusing the name) — refuse the whole
                    # restore so the caller falls through to storage/orbax
                    logger.warning(
                        "checkpoint leaf %s shape %s != target %s; "
                        "rejecting this source",
                        key, gshape, tuple(t_leaf.shape),
                    )
                    return None
                if not covers_target(t_leaf, key):
                    logger.info(
                        "staged shards do not cover leaf %s for the current "
                        "sharding; falling back to storage",
                        key,
                    )
                    return None
                if isinstance(t_leaf, jax.Array) or hasattr(
                    t_leaf, "sharding"
                ):
                    # SHARD-WISE placement: the callback materializes
                    # exactly the index each device asks for, straight
                    # from the staged pieces — the full host array is
                    # never assembled (peak restore memory = largest
                    # local shard, not largest tensor)
                    out_leaves.append(
                        _place_sharded(t_leaf, plist, stats)
                    )
                else:
                    full = build_full(key)
                    if not full_data:
                        # shm pieces are views; build_full's single-piece
                        # shortcut returns the view itself, and a host
                        # target leaf would keep it — aliasing the
                        # restored value to the segment the next save
                        # overwrites
                        full = np.array(full, copy=True)
                    stats["full_assembled"] = (
                        stats.get("full_assembled", 0) + 1
                    )
                    out_leaves.append(_place_like(t_leaf, full))
            self.last_restore_stats = stats
            return step, jax.tree_util.tree_unflatten(treedef, out_leaves)

        # no target: numpy pytree via stored treedef
        full_leaves = []
        if expected_set is not None:
            missing = [p for p in expected_paths if p not in pieces]
            if missing:
                logger.warning(
                    "checkpoint leaves %s have no pieces in the tiers "
                    "read so far", missing[:3],
                )
                return None
            paths = list(expected_paths)  # manifest flatten order
        else:
            # legacy: stored leaf order == flatten order (single-process
            # metas record paths in order)
            paths = list(pieces.keys())
        for path in paths:
            plist = pieces[path]
            if not full_data:
                # partial (shm) data: pieces must tile the whole array
                _, first_arr, gshape = plist[0]
                gvol = int(np.prod(tuple(gshape))) if gshape else first_arr.size
                vol = sum(int(a.size) for _, a, _ in plist)
                if vol < gvol:
                    logger.info(
                        "staged shards cover %s/%s of %s; need storage restore",
                        vol,
                        gvol,
                        path,
                    )
                    return None
            full = build_full(path)
            if full is None:
                return None
            full_leaves.append(full)
        try:
            state = unflatten_state(bytes.fromhex(treedef_hex), full_leaves)
        except Exception as e:
            logger.warning("treedef restore failed (%s); returning dict", e)
            state = dict(zip(paths, full_leaves))
        return step, state

    # -- misc ---------------------------------------------------------------

    def committed_step(self) -> int:
        try:
            return int(
                self._storage.read(os.path.join(self.ckpt_dir, TRACKER_FILE))
            )
        except (FileNotFoundError, ValueError):
            return -1

    def close(self, unlink_shm: bool = False):
        """``unlink_shm=True`` also removes the shm segment — for
        short-lived tools (benches, dryruns) whose staged state must not
        outlive them; training processes keep the segment so the agent's
        saver can ship it after a crash."""
        _DRAIN_REGISTRY.discard(self)
        self._crash_drain_installed = False
        try:
            self.wait_staging(timeout=300)
        except Exception as e:
            logger.warning("in-flight staging failed at close: %s", e)
        # durability flush: the last queued persist reads this process's
        # shm, and a finished job's agent unlinks it — leave only once
        # the saver has copied the step, or the job's final checkpoint
        # may never reach storage
        self._wait_pending_persist(timeout=300.0)
        if self._event_queue is not None:
            self._event_queue.close()
        if self._shm_lock is not None:
            self._shm_lock.close()
        self._shm.close(unlink=unlink_shm)


def _place_sharded(t_leaf, plist, stats: Dict[str, int]):
    """Place a leaf per the target's sharding, shard-wise: each device's
    buffer is fed exactly its requested region sliced from the staged
    pieces (no per-host full-array assembly — Orbax-style distributed
    restore, arXiv:2605.23066). 0-d leaves short-circuit to a plain
    ``device_put`` (no index to slice)."""
    import jax

    sharding = t_leaf.sharding
    dtype = t_leaf.dtype
    shape = tuple(t_leaf.shape)
    if len(shape) == 0:
        stats["sliced"] = stats.get("sliced", 0) + 1
        # copy: the piece may be a view into shm (see _slice_pieces)
        return jax.device_put(
            np.array(plist[0][1], dtype=dtype, copy=True).reshape(()),
            sharding,
        )
    return jax.make_array_from_callback(
        shape,
        sharding,
        lambda idx: _slice_pieces(plist, idx, shape, dtype, stats),
    )


def _place_like(t_leaf, full: np.ndarray):
    """Place a host array according to the target leaf's sharding/dtype."""
    import jax

    if isinstance(t_leaf, jax.Array) or hasattr(t_leaf, "sharding"):
        sharding = t_leaf.sharding
        dtype = t_leaf.dtype
        full = full.astype(dtype) if full.dtype != dtype else full
        if full.ndim == 0:
            return jax.device_put(full, sharding)
        return jax.make_array_from_callback(
            tuple(t_leaf.shape), sharding, lambda idx: np.ascontiguousarray(full[idx])
        )
    if hasattr(t_leaf, "shape") and hasattr(t_leaf, "dtype"):
        return full.astype(t_leaf.dtype)
    return full


def flatten_state_lazy(state):
    """flatten_state but without forcing device transfer (arrays stay jax)."""
    import jax
    import pickle
    import pickletools

    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(state)
    flat = [(jax.tree_util.keystr(p), leaf) for p, leaf in leaves_with_path]
    treedef_bytes = pickletools.optimize(pickle.dumps(treedef))
    return flat, treedef_bytes
