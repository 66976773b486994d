"""Live state resharding: old-mesh → new-mesh without the checkpoint
round-trip.

PR 2 (train/warm_compile.py) made the *compile* half of an elastic
resize warm; this module attacks the *state* half. When a membership
change is absorbed in-process (``ElasticTrainer.remesh()`` with the old
state still resident in HBM), the post-resize restore used to pay a
full checkpoint round-trip anyway: stage to shm / read from storage,
reassemble every leaf as a full host array, re-place it with
``jax.make_array_from_callback`` — downtime scaling with model bytes
even though every byte already sits on surviving devices. ElasWave
(arXiv:2510.00606) and Orbax's distributed restore (arXiv:2605.23066)
both show elastic-native systems hiding membership changes with live
migration instead; this is the TPU-native version of that move.

The transfer plan:

1. **Target shardings from the step-signature machinery.** The trainer
   already derives mesh-independent avatars (shape/dtype/PartitionSpec
   per leaf) for warm compilation; binding each avatar's spec to the
   *new* mesh yields the exact ``NamedSharding`` pytree the post-resize
   step will demand — no reference state, no checkpoint metadata.
2. **Batched ``jax.device_put``.** One call over the whole state pytree
   with the sharding pytree as target: XLA/the runtime schedules all
   leaf transfers together and handles the cross-device (ICI — and on
   jax versions that support it, cross-host) moves device-to-device.
3. **Fallback ladder.** Where the running jax rejects a direct
   cross-mesh transfer, fall back leaf-wise (salvaging the leaves that
   do transfer directly), and per-leaf to a host-gather bridge
   (device_get the full leaf — only possible when it is fully
   addressable — then re-place against the new sharding). If even the
   bridge cannot move a leaf, :class:`LiveReshardError` propagates and
   the caller falls back to the checkpoint restore path, which remains
   the restart-based resize path anyway.

Everything is behind the ``DLROVER_TPU_LIVE_RESHARD=0`` kill-switch
(common/flags.py): off, ``remesh()`` ignores the passed state and the
caller restores through the checkpoint engine exactly as before.

Per-resize downtime lands in :data:`resize_ledger` broken into
rendezvous / compile / state-transfer seconds, exported as Prometheus
gauges on the worker ``/metrics`` endpoint (profiler/comm.py) and
reported to the master's SpeedMonitor for goodput attribution.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger

PyTree = Any

__all__ = [
    "live_reshard_enabled",
    "LiveReshardError",
    "state_shardings",
    "state_targets",
    "stage_transfer_plan",
    "transfer_state",
    "ResizeLedger",
    "resize_ledger",
    "prometheus_lines",
]


def live_reshard_enabled() -> bool:
    """Kill-switch, read at call time so tests/benches can flip it."""
    return flags.LIVE_RESHARD.get()


class LiveReshardError(RuntimeError):
    """No rung of the transfer ladder could move some leaf; the caller
    must fall back to the checkpoint restore path."""


def state_shardings(avatar_tree: PyTree, mesh, world=None) -> PyTree:
    """Bind each avatar's PartitionSpec to ``mesh``: the NamedSharding
    pytree the post-resize step expects its state in. ``avatar_tree``
    is the trainer's ``_state_avatar`` (or any tree whose leaves carry
    a ``.spec``) — the same machinery ``lower_step`` compiles against,
    so transfer targets and executable signature can never disagree.

    ``world`` (a :class:`~dlrover_tpu.common.world.WorldDescriptor`):
    when given, the mesh is CHECKED against it before any sharding is
    derived — the transfer target and the AOT executable then describe
    the same world through one checked type instead of trusting that
    two call sites re-derived the same shape."""
    import jax
    from jax.sharding import NamedSharding

    if world is not None:
        world.check_mesh(mesh)
    return jax.tree.map(
        lambda av: NamedSharding(mesh, av.spec), avatar_tree
    )


def state_targets(avatar_tree: PyTree, mesh, world=None) -> PyTree:
    """``ShapeDtypeStruct`` (with sharding) pytree for ``mesh`` — the
    restore-target form of :func:`state_shardings`, for callers driving
    the checkpoint engine's placed restore against the same avatars
    (the parity tests). ``world``: optional
    WorldDescriptor checked against ``mesh`` exactly as in
    :func:`state_shardings`."""
    import jax
    from jax.sharding import NamedSharding

    if world is not None:
        world.check_mesh(mesh)
    return jax.tree.map(
        lambda av: jax.ShapeDtypeStruct(
            av.shape, av.dtype, sharding=NamedSharding(mesh, av.spec)
        ),
        avatar_tree,
    )


def _bridge_leaf(leaf, sharding):
    """Host-gather bridge for one leaf: d2h the full array, re-place it
    under the new sharding. Only possible when every shard of the leaf
    is addressable from this process — a multi-host leaf that the
    direct transfer rejected cannot be gathered here and must take the
    checkpoint path."""
    import jax
    import numpy as np

    if not getattr(leaf, "is_fully_addressable", True):
        raise LiveReshardError(
            "leaf is not fully addressable from this process; the host "
            "bridge cannot gather it (checkpoint restore required)"
        )
    host = np.asarray(jax.device_get(leaf))
    # the alias is safe — and the point: `host` is a private snapshot
    # whose only consumer is the array placed right here (the caller
    # drops the source leaf after transfer), and copying would double
    # peak host RAM for the leaf. Nothing rewrites the buffer.
    if host.ndim == 0:
        return jax.device_put(host, sharding)  # graftlint: disable=JG007
    return jax.make_array_from_callback(  # graftlint: disable=JG007
        host.shape, sharding, lambda idx: np.ascontiguousarray(host[idx])
    )


def stage_transfer_plan(old_world, new_world) -> Optional[Dict[str, Any]]:
    """Per-stage movement plan for a pp-aware resize, derived from the
    same :class:`~dlrover_tpu.common.world.WorldDescriptor` pair that
    keys the AOT executable — so what moves and what signs can never
    disagree. Returns ``None`` when neither world pipelines (the plain
    dp/fsdp transfer needs no stage bookkeeping). Kinds:

    - ``dp_within_stage``: stage count unchanged — each stage's data
      axes shrink/grow in place, layer slabs never cross stages;
    - ``stage_rebalance``: stage count changed — layer slabs re-slab
      (new stage ``s'`` takes the old-stage fraction
      ``[s'*old_pp/new_pp, (s'+1)*old_pp/new_pp)``);

    plus, per new stage, its slice placement before/after (from the
    canonical ``stage_map``) — ``cross_slice`` marks a stage whose
    bytes must ride DCN."""
    if old_world is None or new_world is None:
        return None
    old_pp, new_pp = old_world.pp, new_world.pp
    if old_pp <= 1 and new_pp <= 1:
        return None
    kind = "dp_within_stage" if old_pp == new_pp else "stage_rebalance"
    old_map, new_map = old_world.stage_map(), new_world.stage_map()
    stages = []
    for s in range(new_pp):
        # old stages whose layer slab lands (fully or partly) on s:
        # the old-stage fraction [s/new_pp, (s+1)/new_pp) of the stack
        lo = s * old_pp // new_pp
        hi = -(-(s + 1) * old_pp // new_pp)  # ceil
        src = tuple(range(lo, max(lo + 1, hi)))
        src_slices = sorted({sl for o in src if o < old_pp
                             for sl in old_map[o]})
        dst_slices = list(new_map[s])
        stages.append({
            "stage": s,
            "src_stages": list(src),
            "src_slices": src_slices,
            "dst_slices": dst_slices,
            "cross_slice": bool(src_slices) and src_slices != dst_slices,
        })
    return {
        "kind": kind,
        "old_pp": old_pp,
        "new_pp": new_pp,
        "from": old_world.spec,
        "to": new_world.spec,
        "stages": stages,
    }


def transfer_state(
    state: PyTree,
    shardings: PyTree,
    *,
    block: bool = True,
    old_world=None,
    new_world=None,
) -> tuple:
    """Move ``state`` onto the shardings' mesh device-to-device.

    Returns ``(new_state, info)``; ``info`` records the path taken
    (``direct`` | ``leafwise`` | ``bridge``), per-rung leaf counts and
    the transfer seconds. ``block=True`` waits for the transfers so the
    recorded seconds are the real cost (callers on a hot path can defer
    the sync to their first step instead).

    Raises :class:`LiveReshardError` when some leaf could not be moved
    by any rung — state is untouched and the caller should restore
    through the checkpoint engine.
    """
    import jax

    from dlrover_tpu.observability import trace

    t0 = time.perf_counter()
    m0 = time.monotonic()
    info: Dict[str, Any] = {"path": "direct", "leaves_bridged": 0}
    plan = stage_transfer_plan(old_world, new_world)
    if plan is not None:
        info["stage_plan"] = plan
    try:
        new_state = jax.device_put(state, shardings)
    except Exception as e:
        logger.info(
            "batched cross-mesh device_put unsupported here (%s); "
            "falling back leaf-wise", str(e)[:200],
        )
        new_state, bridged = _transfer_leafwise(state, shardings)
        info["path"] = "bridge" if bridged else "leafwise"
        info["leaves_bridged"] = bridged
    if block:
        jax.block_until_ready(new_state)
    info["transfer_s"] = time.perf_counter() - t0
    # trace spine: the state half of a live resize is a state_transfer
    # span (the resize ledger keeps the per-event breakdown; the spine
    # is what merges into the job timeline)
    trace.record(
        "state_transfer", "live_reshard.transfer", m0,
        info["transfer_s"], path=info["path"],
        leaves_bridged=info["leaves_bridged"],
    )
    return new_state, info


def _transfer_leafwise(state: PyTree, shardings: PyTree):
    """Rung 2+3: per-leaf direct transfer, host bridge for the leaves
    the runtime rejects. Returns (new_state, n_bridged)."""
    import jax

    flat_s, treedef = jax.tree_util.tree_flatten(state)
    flat_sh = treedef.flatten_up_to(shardings)
    out: List[Any] = []
    bridged = 0
    for leaf, sh in zip(flat_s, flat_sh):
        try:
            out.append(jax.device_put(leaf, sh))
        except Exception:
            out.append(_bridge_leaf(leaf, sh))
            bridged += 1
    return jax.tree_util.tree_unflatten(treedef, out), bridged


# ---------------------------------------------------------------------------
# Per-resize downtime breakdown ledger
# ---------------------------------------------------------------------------


class ResizeLedger:
    """Downtime breakdown per resize event: rendezvous / compile /
    state-transfer seconds, with the transfer path taken.

    In-memory, process-wide (one trainer per process is the normal
    shape). ``prometheus_lines()`` exports the last event's phases as
    gauges plus cumulative per-phase totals — the fleet-level signal
    for whether resizes are landing warm on BOTH halves (executable
    AND state)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: List[dict] = []

    def record(
        self,
        world_from: int,
        world_to: int,
        *,
        rendezvous_s: float = 0.0,
        compile_s: float = 0.0,
        state_transfer_s: float = 0.0,
        path: str = "",
        restore_tier: str = "",
    ) -> dict:
        """``path``: ``direct`` | ``leafwise`` | ``bridge`` (live
        transfer rung) or ``checkpoint`` (the round-trip fallback).
        ``restore_tier``: where the state that ended this downtime came
        from — ``live`` (device-to-device, no restore) or the checkpoint
        engine's tier (``shm`` | ``disk`` | ``object``) — so the goodput
        ledger can separate tier-0 fast restarts from the slower
        disk/object recoveries."""
        event = {
            "world_from": int(world_from),
            "world_to": int(world_to),
            "rendezvous_s": round(float(rendezvous_s), 6),
            "compile_s": round(float(compile_s), 6),
            "state_transfer_s": round(float(state_transfer_s), 6),
            "path": path,
            "restore_tier": restore_tier,
            "ts": time.time(),
        }
        with self._lock:
            self._events.append(event)
        return event

    def events(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def last(self) -> Optional[dict]:
        with self._lock:
            return dict(self._events[-1]) if self._events else None

    def clear(self):
        with self._lock:
            self._events.clear()

    def prometheus_lines(self) -> List[str]:
        lines = [
            "# TYPE dlrover_tpu_resize_seconds gauge",
            "# TYPE dlrover_tpu_resize_seconds_total gauge",
            "# TYPE dlrover_tpu_resize_events gauge",
        ]
        with self._lock:
            events = [dict(e) for e in self._events]
        if not events:
            return lines
        last = events[-1]
        label_base = (
            f'world_from="{last["world_from"]}",'
            f'world_to="{last["world_to"]}",path="{last["path"]}"'
        )
        totals = {"rendezvous": 0.0, "compile": 0.0, "state_transfer": 0.0}
        for e in events:
            for phase in totals:
                totals[phase] += e[f"{phase}_s"]
        for phase in ("rendezvous", "compile", "state_transfer"):
            lines.append(
                f'dlrover_tpu_resize_seconds{{phase="{phase}",'
                f"{label_base}}} {last[f'{phase}_s']:.6f}"
            )
            lines.append(
                f'dlrover_tpu_resize_seconds_total{{phase="{phase}"}} '
                f"{totals[phase]:.6f}"
            )
        lines.append(f"dlrover_tpu_resize_events {len(events)}")
        return lines


#: process-wide ledger (trainer records; /metrics reads)
resize_ledger = ResizeLedger()


def prometheus_lines() -> List[str]:
    """Module-level convenience for the metrics server."""
    return resize_ledger.prometheus_lines()
