"""Replica-deduplicated checkpoint ownership.

On a dp-replicated mesh every process used to stage (and persist) its
full addressable view of the train state — ``dp`` identical copies of
the params and any replicated optimizer moments hit shm and storage on
every save. Orbax's replica-aware persistence (arXiv:2605.23066) and
FastPersist's parallel-IO save path (arXiv:2406.13768) both partition
the state into *disjoint* per-writer shards instead: each replica
persists only the pieces it owns, and restore reassembles from the
union. This module derives that partition.

The derivation has to satisfy one invariant above all: **the save
layout and the restore target must come from the same machinery**, so
they can never disagree across resizes or zero-1 on/off flips. Both
sides therefore key on a leaf's ``(shape, NamedSharding)`` — the live
arrays at stage time, and the trainer's ``_state_avatar_for(mesh)``
avatars (the same trees AOT lowering and live-reshard transfer targets
are built from) on the planning/verification side.
:func:`plan_for_avatars` and :func:`plan_for_state` produce identical
assignments for a state placed by those avatars
(tests/test_ckpt_tiers.py pins it).

Assignment rules, deterministic across processes (no communication):

- every distinct shard *region* of a leaf (from
  ``sharding.devices_indices_map`` over the full mesh — identical on
  every process) is assigned exactly one owner among the processes
  holding a replica of it;
- a region with a single holder (a genuinely sharded piece — fsdp/sp
  shards, zero-1 moments) is owned by that holder;
- a region replicated across ``k`` processes (pure-dp params, the
  pre-zero-1 moments) is SPLIT into ``k`` contiguous chunks along its
  largest dimension, one chunk per replica — the dp-round-robin split
  — so per-node bytes land at ~1/dp regardless of how unevenly leaf
  sizes are distributed (a whole-leaf round-robin would hand whoever
  draws the embedding table several times its fair share). The
  chunk→replica pairing is rotated by a per-replica-set counter
  advanced in flatten order, so the first-chunk remainder element
  doesn't always land on the same rank. Regions too small to split
  (every dim < k, scalars) fall back to whole-region round-robin over
  the same counter.

Determinism argument: the pytree flatten order, each leaf's global
``devices_indices_map`` and the sorted region order are identical on
every process, so every process computes the same full assignment and
simply keeps its own slice of it.

Virtual worlds: single-process test runs (the 8-device CPU mesh)
have ``jax.process_count() == 1``, which makes the real partition
trivial. :func:`virtual_proc_of` splits the device list into ``world``
contiguous groups so a single process can *simulate* an N-node world —
the node-loss recovery tests stage one virtual node at a time through
it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

PyTree = Any
Ranges = Tuple[Tuple[int, int], ...]


@dataclasses.dataclass(frozen=True)
class PieceAssignment:
    """One owned piece of one leaf. ``ranges`` is the piece itself;
    ``parent`` is the staged shard region it was cut from (equal to
    ``ranges`` for unsplit pieces) — staging matches a device shard's
    region against ``parent`` and slices ``ranges`` out of it."""

    ranges: Ranges          # (start, stop) per dim, () for 0-d
    owner: int              # owning process rank
    replicas: Tuple[int, ...]  # every rank holding parent
    parent: Optional[Ranges] = None

    @property
    def parent_ranges(self) -> Ranges:
        return self.ranges if self.parent is None else self.parent


class RoundRobin:
    """Per-replica-set round-robin counters. One instance per staging
    pass / plan; advancing it in flatten order on every process yields
    the same assignment everywhere (the module docstring's determinism
    argument)."""

    def __init__(self):
        self._counters: Dict[Tuple[int, ...], int] = {}

    def advance(self, replicas: Tuple[int, ...]) -> int:
        i = self._counters.get(replicas, 0)
        self._counters[replicas] = i + 1
        return i

    def next(self, replicas: Tuple[int, ...]) -> int:
        return replicas[self.advance(replicas) % len(replicas)]


def index_to_ranges(index, shape) -> Ranges:
    """Normalize a jax shard index (tuple of slices) to (start, stop)
    pairs — the hashable, sortable region form everything here keys on."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def virtual_proc_of(world: int) -> Callable[[Any], int]:
    """device -> virtual rank: the device list split into ``world``
    contiguous groups. Matches the dp-major device order ``build_mesh``
    lays out, so on a pure-dp mesh each virtual rank is one dp slice.
    Test-only — real multi-process worlds use the device's
    ``process_index``."""
    import jax

    devs = jax.devices()
    per = max(1, (len(devs) + world - 1) // world)
    rank_of = {d.id: min(i // per, world - 1) for i, d in enumerate(devs)}
    return lambda d: rank_of.get(d.id, 0)


def real_proc_of() -> Callable[[Any], int]:
    return lambda d: d.process_index


def split_region(ranges: Ranges, k: int) -> Optional[List[Ranges]]:
    """Split a region into ``k`` contiguous chunks along its largest
    dimension (ties: the first). None when no dimension has extent
    >= k — callers fall back to whole-region round-robin."""
    if k <= 1 or not ranges:
        return None
    extents = [e - s for s, e in ranges]
    axis = max(range(len(extents)), key=lambda d: extents[d])
    n = extents[axis]
    if n < k:
        return None
    base, rem = divmod(n, k)
    out: List[Ranges] = []
    start = ranges[axis][0]
    for i in range(k):
        size = base + (1 if i < rem else 0)
        sub = list(ranges)
        sub[axis] = (start, start + size)
        out.append(tuple(sub))
        start += size
    return out


def _assign_replicated(
    region: Ranges, reps: Tuple[int, ...], rr: RoundRobin
) -> List[PieceAssignment]:
    """The dp-round-robin split of one replicated region: one chunk per
    replica, chunk→replica pairing rotated by the replica set's counter;
    unsplittable regions round-robin whole."""
    subs = split_region(region, len(reps))
    if subs is None:
        return [
            PieceAssignment(
                ranges=region, owner=rr.next(reps), replicas=reps,
                parent=region,
            )
        ]
    off = rr.advance(reps)
    return [
        PieceAssignment(
            ranges=sub, owner=reps[(i + off) % len(reps)], replicas=reps,
            parent=region,
        )
        for i, sub in enumerate(subs)
    ]


def assign_leaf(
    shape: Tuple[int, ...],
    sharding,
    proc_of: Callable[[Any], int],
    rr: RoundRobin,
) -> List[PieceAssignment]:
    """Ownership assignment for every distinct shard region of one
    leaf. ``sharding`` must expose ``devices_indices_map`` (any
    jax.sharding.Sharding). Raises whatever the sharding raises —
    callers degrade to staging everything."""
    imap = sharding.devices_indices_map(tuple(shape))
    regions: Dict[Ranges, set] = {}
    for dev, idx in imap.items():
        r = index_to_ranges(idx, shape)
        regions.setdefault(r, set()).add(proc_of(dev))
    out: List[PieceAssignment] = []
    for r in sorted(regions):
        reps = tuple(sorted(regions[r]))
        if len(reps) == 1:
            out.append(
                PieceAssignment(
                    ranges=r, owner=reps[0], replicas=reps, parent=r
                )
            )
        else:
            out.extend(_assign_replicated(r, reps, rr))
    return out


def assign_host_leaf(
    shape: Tuple[int, ...], world: int, rr: RoundRobin
) -> List[PieceAssignment]:
    """A host (non-device) leaf — python scalars, numpy arrays — is
    replicated on every process by construction; dp-round-robin-split
    it like any fully-replicated region."""
    reps = tuple(range(world))
    ranges = tuple((0, int(d)) for d in shape)
    if world == 1:
        return [
            PieceAssignment(
                ranges=ranges, owner=0, replicas=reps, parent=ranges
            )
        ]
    return _assign_replicated(ranges, reps, rr)


def plan_for_state(
    state: PyTree,
    proc_of: Optional[Callable[[Any], int]] = None,
    world: Optional[int] = None,
) -> Dict[str, List[PieceAssignment]]:
    """Full assignment keyed by leaf path, derived from the LIVE state's
    shardings — what the engine's staging pass computes. Defaults to the
    real process topology."""
    import jax

    if proc_of is None:
        proc_of = real_proc_of()
    if world is None:
        world = jax.process_count()
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    rr = RoundRobin()
    plan: Dict[str, List[PieceAssignment]] = {}
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "devices_indices_map"):
            plan[key] = assign_leaf(tuple(leaf.shape), sharding, proc_of, rr)
        else:
            shape = tuple(getattr(leaf, "shape", ()) or ())
            plan[key] = assign_host_leaf(shape, world, rr)
    return plan


def plan_for_avatars(
    avatar_tree: PyTree,
    mesh,
    proc_of: Optional[Callable[[Any], int]] = None,
    world: Optional[int] = None,
) -> Dict[str, List[PieceAssignment]]:
    """The same assignment derived from the trainer's mesh-independent
    avatars (``_state_avatar_for(mesh)``) bound to ``mesh`` — the
    restore-target side of the invariant. Identical to
    :func:`plan_for_state` of a state placed by those avatars."""
    import jax
    from jax.sharding import NamedSharding

    if proc_of is None:
        proc_of = real_proc_of()
    if world is None:
        world = jax.process_count()
    flat, _ = jax.tree_util.tree_flatten_with_path(avatar_tree)
    rr = RoundRobin()
    plan: Dict[str, List[PieceAssignment]] = {}
    for path, av in flat:
        key = jax.tree_util.keystr(path)
        spec = getattr(av, "spec", None)
        if spec is None:
            plan[key] = assign_host_leaf(
                tuple(getattr(av, "shape", ())), world, rr
            )
            continue
        sharding = NamedSharding(mesh, spec)
        plan[key] = assign_leaf(tuple(av.shape), sharding, proc_of, rr)
    return plan


def owned_bytes(
    plan: Dict[str, List[PieceAssignment]],
    sizes: Dict[str, Tuple[Tuple[int, ...], int]],
    rank: int,
) -> int:
    """Bytes of ``rank``'s owned pieces; ``sizes`` maps leaf path ->
    (global shape, itemsize). Diagnostic helper for benches/tests."""
    total = 0
    for path, assigns in plan.items():
        _, itemsize = sizes.get(path, ((), 0))
        for a in assigns:
            if a.owner != rank:
                continue
            vol = 1
            for s, e in a.ranges:
                vol *= max(0, e - s)
            total += vol * itemsize
    return total


def validate_plan(plan: Dict[str, List[PieceAssignment]]) -> None:
    """Sanity gate used by tests: every piece has exactly one owner,
    that owner is among its replicas, no piece is assigned twice, each
    piece lies inside its parent region, and the pieces cut from one
    parent tile it exactly (volumes sum to the parent's)."""
    for path, assigns in plan.items():
        by_parent: Dict[Ranges, List[PieceAssignment]] = {}
        for a in assigns:
            if a.owner not in a.replicas:
                raise AssertionError(
                    f"{path}: owner {a.owner} not a replica of {a.ranges} "
                    f"({a.replicas})"
                )
            for (s, e), (ps, pe) in zip(a.ranges, a.parent_ranges):
                if s < ps or e > pe:
                    raise AssertionError(
                        f"{path}: piece {a.ranges} outside parent "
                        f"{a.parent_ranges}"
                    )
            by_parent.setdefault(a.parent_ranges, []).append(a)
        seen = [a.ranges for a in assigns]
        if len(seen) != len(set(seen)):
            raise AssertionError(f"{path}: duplicate region assignment")
        def _vol(r: Ranges) -> int:
            v = 1
            for s, e in r:
                v *= max(0, e - s)
            return v

        for parent, group in by_parent.items():
            if parent == ():  # 0-d: one piece == the whole parent
                if len(group) != 1:
                    raise AssertionError(f"{path}: 0-d region split")
                continue
            vol = sum(_vol(a.ranges) for a in group)
            if vol != _vol(parent):
                raise AssertionError(
                    f"{path}: pieces of parent {parent} cover {vol} of "
                    f"{_vol(parent)} elements"
                )


__all__ = [
    "PieceAssignment",
    "RoundRobin",
    "index_to_ranges",
    "split_region",
    "virtual_proc_of",
    "real_proc_of",
    "assign_leaf",
    "assign_host_leaf",
    "plan_for_state",
    "plan_for_avatars",
    "owned_bytes",
    "validate_plan",
]
