"""Warm-path elasticity: make the post-resize recompile a cache hit.

The flash-checkpoint port minimizes the *save* side of a membership
change; this module attacks the *rebuild* side. After a resize,
``ElasticTrainer.remesh()`` drops the jitted step and the next
``step()`` call recompiles the full fwd+bwd+adamw program from scratch
— tens of seconds of dead chip time for a billion-param model. Three
layers turn that cold compile into a warm one:

1. **Persistent compilation cache** (:func:`enable_persistent_cache`):
   JAX's on-disk executable cache, pointed at
   ``DLROVER_TPU_COMPILE_CACHE_DIR`` (the elastic agent injects it; the
   checkpoint engine defaults it under the checkpoint dir so it lives
   on the same volume that already survives pod restarts). A restarted
   worker deserializes the step executable instead of recompiling.

2. **AOT compilation** (:meth:`ElasticTrainer.lower_step`): the step
   can be lowered and compiled against ``jax.ShapeDtypeStruct``
   avatars, so a world size that is *not live* can be compiled for —
   no state arrays, no training pause. Compiled executables are kept
   in an in-process cache keyed by the step *signature* (mesh shape +
   device assignment + accum + state/batch avatars), so a same-process
   remesh picks the executable up with zero compile.

3. **Speculative neighbor compilation** (:func:`neighbor_worlds` +
   :class:`WarmCompiler`): after each successful live build, a single
   bounded daemon thread compiles the step for the neighbor world
   sizes the ``MeshConfig`` admits (world ± one node, world/2 — the
   memberships an elastic resize actually lands on), populating both
   caches before the resize happens. Worlds larger than the attached
   device set cannot be speculated from here; they are covered by the
   persistent cache instead (a grow event returns to a world that
   compiled before the shrink).

Everything is behind the ``DLROVER_TPU_WARM_COMPILE=0`` kill-switch,
which restores the plain ``jax.jit`` rebuild path exactly. Compile
times land in a small JSON ledger (``compile_ledger.json`` next to the
cache) keyed by ``(world, config-hash)`` with a cold/warm/speculative
source tag, and are exported as Prometheus gauges on the worker
``/metrics`` endpoint (profiler/comm.py).
"""

from __future__ import annotations

import atexit
import hashlib
import json
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger

PyTree = Any

# flag names kept importable for tests/docs; reads go through the
# typed registry (common/flags.py, graftlint JG003)
ENV_KILL_SWITCH = flags.WARM_COMPILE.name
ENV_CACHE_DIR = flags.COMPILE_CACHE_DIR.name
ENV_MIN_COMPILE_S = flags.COMPILE_CACHE_MIN_S.name
ENV_MAX_TARGETS = flags.WARM_COMPILE_MAX_TARGETS.name

LEDGER_FILENAME = "compile_ledger.json"

__all__ = [
    "warm_compile_enabled",
    "enable_persistent_cache",
    "default_cache_under",
    "configured_cache_dir",
    "neighbor_worlds",
    "CompileLedger",
    "compile_ledger",
    "WarmCompiler",
    "prometheus_lines",
]


def warm_compile_enabled() -> bool:
    """Kill-switch, read at call time so tests/benches can flip it."""
    return flags.WARM_COMPILE.get()


_enable_lock = threading.Lock()
_enabled_dir: Optional[str] = None


def configured_cache_dir() -> Optional[str]:
    """The persistent-cache dir this process actually runs with: what
    this module configured, else whatever jax was already given
    (``JAX_COMPILATION_CACHE_DIR``)."""
    if _enabled_dir:
        return _enabled_dir
    try:
        import jax

        return getattr(jax.config, "jax_compilation_cache_dir", None) or None
    except Exception:
        return None


def enable_persistent_cache(path: Optional[str] = None) -> Optional[str]:
    """Point JAX's persistent compilation cache at ``path`` (default:
    ``DLROVER_TPU_COMPILE_CACHE_DIR``). Idempotent, and never overrides
    a cache dir jax already has — the jax config is process-global and
    the first owner (a ``JAX_COMPILATION_CACHE_DIR`` placed from
    outside) wins. Returns the effective
    dir, or None when disabled/unconfigured. Purely an optimization:
    any failure logs and returns None rather than failing the caller."""
    global _enabled_dir
    if not warm_compile_enabled():
        return None
    with _enable_lock:
        existing = configured_cache_dir()
        if existing:
            return existing
        path = path or flags.COMPILE_CACHE_DIR.get()
        if not path:
            return None
        try:
            os.makedirs(path, exist_ok=True)
            import jax

            jax.config.update("jax_compilation_cache_dir", path)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(flags.COMPILE_CACHE_MIN_S.get()),
            )
        except Exception as e:
            logger.warning("persistent compile cache unavailable: %s", e)
            return None
        # children (speculative compile helpers, restarted workers
        # forked from this env) inherit the same dir
        flags.COMPILE_CACHE_DIR.propagate(path)
        _enabled_dir = path
        logger.info("persistent compile cache at %s", path)
        return path


def default_cache_under(base_dir: str) -> Optional[str]:
    """Checkpoint-engine hook: when nothing configured a cache dir,
    default it to ``<ckpt_dir>/compile_cache`` — the checkpoint dir is
    the one path the deployment already persists across pod restarts,
    so the compile cache survives exactly as far as the checkpoints
    do. An explicit ``DLROVER_TPU_COMPILE_CACHE_DIR`` wins."""
    if not warm_compile_enabled():
        return None
    if flags.COMPILE_CACHE_DIR.present():
        return enable_persistent_cache()
    if not base_dir:
        return None
    return enable_persistent_cache(os.path.join(base_dir, "compile_cache"))


# ---------------------------------------------------------------------------
# Compile-seconds ledger
# ---------------------------------------------------------------------------


class CompileLedger:
    """Compile seconds per ``(world, config-hash)``, with provenance.

    In-memory always (the tests read it); when
    a persistent cache dir is configured the ledger is also mirrored to
    ``compile_ledger.json`` inside it, atomically, so post-mortems can
    see what each membership's step cost to build and whether resizes
    were landing warm."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, dict] = {}
        self._disk_merged = False

    def _merge_disk_locked(self):
        """Fold the previous lifetime's ledger in before the first
        persist — a restarted worker must extend the file, not clobber
        it (the whole point is seeing cold→warm across restarts)."""
        if self._disk_merged:
            return
        cache_dir = configured_cache_dir()
        if not cache_dir:
            return  # retry on a later record; a dir may appear
        self._disk_merged = True
        path = os.path.join(cache_dir, LEDGER_FILENAME)
        try:
            with open(path) as f:
                disk = json.load(f)
        except (OSError, ValueError):
            return
        if not isinstance(disk, dict):
            return
        for key, entry in disk.items():
            if not isinstance(entry, dict) or "compiles" not in entry:
                continue
            ours = self._entries.get(key)
            if ours is None:
                self._entries[key] = dict(entry)
            else:
                ours["compiles"] = (
                    list(entry["compiles"]) + ours["compiles"]
                )

    def record(
        self,
        world: int,
        config_hash: str,
        seconds: float,
        source: str,
    ) -> dict:
        """``source``: ``cold`` (live blocking compile), ``warm``
        (in-process AOT cache hit), ``speculative`` (background
        neighbor compile), ``jit`` (kill-switch path, first-call time
        not separable from the first step)."""
        key = f"world{world}:{config_hash}"
        with self._lock:
            self._merge_disk_locked()
            entry = self._entries.setdefault(
                key,
                {
                    "world": world,
                    "config_hash": config_hash,
                    "compiles": [],
                },
            )
            entry["compiles"].append(
                {
                    "seconds": round(seconds, 4),
                    "source": source,
                    "ts": time.time(),
                }
            )
            snapshot = {k: dict(v) for k, v in self._entries.items()}
        self._persist(snapshot)
        return entry

    def get(self, world: int, config_hash: str) -> Optional[dict]:
        with self._lock:
            return self._entries.get(f"world{world}:{config_hash}")

    def entries(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def clear(self):
        with self._lock:
            self._entries.clear()

    def _persist(self, snapshot: Dict[str, dict]):
        cache_dir = configured_cache_dir()
        if not cache_dir or not os.path.isdir(cache_dir):
            return
        path = os.path.join(cache_dir, LEDGER_FILENAME)
        try:
            # multiple workers share one cache dir (the intended k8s
            # layout): fold in keys other writers added since our merge
            # so the file converges instead of ping-pong clobbering.
            # Same-key concurrent updates are still last-writer-wins
            # within a write window — acceptable for telemetry.
            try:
                with open(path) as f:
                    disk = json.load(f)
                if isinstance(disk, dict):
                    for key, entry in disk.items():
                        if key not in snapshot and isinstance(entry, dict):
                            snapshot[key] = entry
            except (OSError, ValueError):
                pass
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(snapshot, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass  # telemetry only, never worth failing a compile over

    def prometheus_lines(self) -> List[str]:
        """Gauges for the worker /metrics endpoint: last compile
        seconds per (world, source) plus warm-hit counts."""
        lines = [
            "# TYPE dlrover_tpu_compile_seconds gauge",
            "# TYPE dlrover_tpu_compile_count gauge",
        ]
        with self._lock:
            entries = {k: dict(v) for k, v in self._entries.items()}
        for key in sorted(entries):
            e = entries[key]
            by_source: Dict[str, List[dict]] = {}
            for c in e["compiles"]:
                by_source.setdefault(c["source"], []).append(c)
            for source in sorted(by_source):
                rows = by_source[source]
                label = (
                    f'world="{e["world"]}",config="{e["config_hash"]}",'
                    f'source="{source}"'
                )
                lines.append(
                    f"dlrover_tpu_compile_seconds{{{label}}} "
                    f"{rows[-1]['seconds']:.4f}"
                )
                lines.append(
                    f"dlrover_tpu_compile_count{{{label}}} {len(rows)}"
                )
        return lines


#: process-wide ledger (one trainer per process is the normal shape;
#: entries are keyed by config)
compile_ledger = CompileLedger()


def prometheus_lines() -> List[str]:
    """Module-level convenience for the metrics server."""
    return compile_ledger.prometheus_lines()


# ---------------------------------------------------------------------------
# Neighbor-world heuristic
# ---------------------------------------------------------------------------


def neighbor_worlds(
    world: int,
    mesh_config,
    *,
    n_devices_available: int,
    devices_per_node: int = 1,
    global_batch_size: int,
    micro_batch_size: int,
    max_targets: Optional[int] = None,
    n_slices: int = 1,
) -> List["WorldDescriptor"]:
    """Candidate :class:`~dlrover_tpu.common.world.WorldDescriptor`\\ s
    a resize is likely to land on, filtered to the ones we can actually
    compile for from here. Each descriptor carries the refit mesh axes
    and the surviving slice count — the same checked type the goodput
    planner scores and the contract specs key on, so the speculated
    executable and everything downstream describe one world.

    Candidates, in priority order: world minus one node (the single
    most common elastic event — a preemption/eviction), world/2 (an
    autoscaler halving), world plus one node (node recovered). A
    candidate survives only if

    - it differs from ``world`` and is > 0;
    - a mesh for it exists within the attached device set (speculation
      compiles against a *subset* mesh of live devices; a world larger
      than what is attached has no devices to lower against — the
      persistent cache covers grow events instead);
    - the refit ``MeshConfig`` (``parallel.mesh.remesh``) admits it —
      model axes are preserved, so the world must still hold them;
    - the elastic global-batch invariant holds: ``global_batch %
      (micro_batch * dp') == 0`` for the refit config.

    ``n_slices > 1`` (multislice): the resize unit is a whole SLICE,
    not a node — a preemption takes the slice with it and the survivor
    worlds are whole-slice multiples. Candidates become world minus one
    slice (the most common multislice loss), half the slices, world
    plus one slice; every candidate must tile into whole slices AND the
    refit dp (or, for stage-pinned pp worlds, pp) must still decompose
    over the surviving slice count (dp and pp are the only axes allowed
    to span DCN). A slice loss then resizes warm: the speculated
    executable was compiled on the slice-major neighbor mesh the
    re-seated world actually forms.

    Stage-aware enumeration (``pp > 1``): each candidate world size is
    tried both pp-preserving (shrink/grow the data axes WITHIN every
    stage — `parallel.mesh.remesh` keeps model axes) and with the stage
    count rebalanced (pp halved / doubled, layers re-slabbed), so a
    node loss that starves a stage of its dp width still has a
    speculated executable waiting."""
    import dataclasses as _dc

    from dlrover_tpu.common.world import WorldDescriptor
    from dlrover_tpu.parallel.mesh import remesh as remesh_config

    if max_targets is None:
        max_targets = int(flags.WARM_COMPILE_MAX_TARGETS.get())
    node = max(1, devices_per_node)
    per_slice = world // n_slices if n_slices > 1 else 0
    if n_slices > 1 and (world % n_slices or per_slice == 0):
        per_slice = 0
    if per_slice:
        raw = [world - per_slice, (n_slices // 2) * per_slice,
               world + per_slice]
    else:
        raw = [world - node, world // 2, world + node]
    pp0 = getattr(mesh_config, "pp", 1)
    base_cfgs = [mesh_config]
    if pp0 > 1:
        if pp0 % 2 == 0:
            base_cfgs.append(_dc.replace(mesh_config, pp=pp0 // 2))
        base_cfgs.append(_dc.replace(mesh_config, pp=pp0 * 2))
    out: List[WorldDescriptor] = []
    seen: set = set()
    for w in raw:
        if w <= 0 or w == world:
            continue
        if w > n_devices_available:
            continue
        for base in base_cfgs:
            try:
                refit = remesh_config(base, w)
                resolved = refit.resolve(w)
                dp = resolved.data_parallel_size
            except ValueError:
                continue
            if global_batch_size % (micro_batch_size * dp):
                continue
            slices = 1
            if per_slice:
                slices = w // per_slice
                if w % per_slice:
                    continue
                # the surviving world must still host a legal
                # multislice mesh: dp spans DCN when it can, else
                # whole pp stages pin to slices; nothing else may
                if slices > 1 and resolved.dp % slices \
                        and resolved.pp % slices:
                    continue
            try:
                cand = WorldDescriptor.from_axis_sizes(
                    resolved.shape(),
                    n_slices=max(1, slices),
                    hier=slices > 1,
                )
            except ValueError:
                continue
            if cand.spec in seen:
                continue
            seen.add(cand.spec)
            out.append(cand)
            if len(out) >= max_targets:
                return out
    return out


# ---------------------------------------------------------------------------
# In-process AOT executable cache + speculative compile thread
# ---------------------------------------------------------------------------


def signature_hash(parts: Sequence[str]) -> str:
    return hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]


class WarmCompiler:
    """Holds compiled step executables and runs the speculative thread.

    The cache is in-process: a same-process remesh (a slice-count
    change absorbed without a restart) reuses the compiled
    executable directly. Across restarts the persistent XLA cache does
    the same job one layer down. One ``WarmCompiler`` per trainer.

    The speculative thread is deliberately modest: a single daemon
    thread, targets compiled serially, bounded count
    (``DLROVER_TPU_WARM_COMPILE_MAX_TARGETS``, default 2), and it skips
    entirely when no persistent cache dir is configured — without one,
    a speculative compile only helps a same-process resize, and a
    billion-param lowering costs real host RAM that the live step's
    input pipeline may want. It never raises into the training loop."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cache: Dict[str, Any] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        _live_compilers.add(self)

    # -- executable cache ---------------------------------------------------

    def get(self, sig: str) -> Optional[Any]:
        with self._lock:
            return self._cache.get(sig)

    def put(self, sig: str, compiled: Any):
        with self._lock:
            self._cache[sig] = compiled

    def evict(self, sig: str):
        """Drop a signature whose executable proved unusable (e.g. the
        live state rejected its input shardings) so later remeshes
        don't keep warm-hitting a poisoned entry."""
        with self._lock:
            self._cache.pop(sig, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def clear(self):
        self.cancel()
        with self._lock:
            self._cache.clear()

    # -- speculation --------------------------------------------------------

    @property
    def speculating(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def speculate(
        self,
        targets: Sequence[Any],
        compile_for_world: Callable[[Any], Any],
        require_cache_dir: bool = True,
    ) -> bool:
        """Kick the background thread compiling ``compile_for_world(w)``
        for each target (``WorldDescriptor``\\ s from
        ``neighbor_worlds``, or whatever the caller's compile fn
        accepts). Returns True if a thread was started.
        At most one speculation generation runs at a time; a new call
        while one is in flight is dropped (the next build re-triggers)."""
        if not warm_compile_enabled() or not targets:
            return False
        if require_cache_dir and not (
            configured_cache_dir() or enable_persistent_cache()
        ):
            return False
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return False
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run,
                args=(list(targets), compile_for_world),
                name="warm-compile",
                daemon=True,
            )
            self._thread.start()
        return True

    def _run(self, targets: List[Any], compile_for_world):
        for w in targets:
            if self._stop.is_set():
                return
            try:
                compile_for_world(w)
            except Exception as e:
                # a neighbor that cannot lower (odd divisibility the
                # heuristic missed, OOM in the compiler) is just an
                # uncached future resize, not an error worth a restart
                logger.warning(
                    "speculative compile for world=%s skipped: %s",
                    getattr(w, "spec", w), e,
                )

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Join the speculative thread (tests). True if idle."""
        t = self._thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def cancel(self):
        self._stop.set()
        self.wait_idle(timeout=5.0)


#: every live WarmCompiler, so interpreter exit can join their threads:
#: a daemon thread abandoned inside an XLA compile segfaults CPython's
#: teardown (pthread_exit mid-C++-frame). The stop flag bounds the wait
#: to at most the one in-flight target.
_live_compilers: "weakref.WeakSet[WarmCompiler]" = weakref.WeakSet()


def _shutdown_speculation():
    # bounded join: holding exit for a full billion-param compile could
    # outlive the pod's termination grace (SIGKILL mid-teardown); past
    # the bound we accept the daemon-thread teardown risk instead. The
    # stop flag bounds the common case to "finish the current target".
    timeout = float(flags.WARM_COMPILE_EXIT_JOIN_S.get())
    for wcm in list(_live_compilers):
        wcm._stop.set()
    deadline = time.monotonic() + timeout
    for wcm in list(_live_compilers):
        try:
            wcm.wait_idle(max(0.0, deadline - time.monotonic()))
        except Exception:
            pass


atexit.register(_shutdown_speculation)
