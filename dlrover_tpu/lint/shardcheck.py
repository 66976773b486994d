"""shardcheck: static analysis of the *lowered* step program (SC rules).

graftlint (rules.py) machine-checks elasticity invariants at the Python
AST level; every truly expensive bug this repo shipped lived **below**
the AST — in the program XLA actually runs:

- the GSPMD ``jnp.concatenate`` miscompile that doubled every target id
  (an unreduced replica sum the source code could never show);
- adam moments coming back from the step re-sharded, silently changing
  step N+1's input signature (recompile under jit, hard reject under
  AOT);
- the dense ``[B, T, V]`` f32 logits materialization chunked-CE exists
  to kill.

So this module reads the IR itself. Two texts, both obtained for free
from the warm-compile machinery (``ElasticTrainer.lower_step`` lowers
the step for *any* admissible world from shape avatars — live or not —
so the whole analysis runs on CPU, in CI, with no TPU attached):

- **StableHLO** (``lowered.as_text()``): global shapes, the entry
  signature's per-arg/per-result ``mhlo.sharding`` strings and the
  ``tf.aliasing_output`` donation links, explicit ``@Sharding``
  constraint sites. Feeds SC002/SC003/SC004.
- **optimized HLO** (``compiled.as_text()``): the post-GSPMD per-device
  program where the collectives are real ops with replica groups and
  shapes. Feeds SC001/SC005.

Rules (each encodes a shipped bug — see docs/design/shardcheck.md):

SC001  collective census: count + size every all-gather / all-reduce /
       reduce-scatter / collective-permute / all-to-all per mesh axis
       and diff against a checked-in per-(mesh, config-hash) contract.
SC002  replicated-large-tensor: an explicitly sharding-constrained
       intermediate above a byte threshold left fully replicated while
       the mesh has data axes to shard it over.
SC003  dense-vocab materialization: a float dot_general result carrying
       BOTH the sequence and the full vocab dim (the chunked-CE
       regression gate).
SC004  output-sharding drift: a donated state input whose paired output
       sharding is missing (left to XLA — free to drift) or different.
SC005  host transfer inside the jitted step: host callbacks, infeed /
       outfeed, host send/recv.
SC006  exposed-DCN-bytes: the exposed/overlapped split of slice-boundary
       transfers diffed against the contract (the overlap schedule's
       regression gate).
SC007  custom-call census: every non-benign custom-call (the Pallas /
       Mosaic kernels) recorded per contract — a contracted kernel
       vanishing from the lowered step is a silent fallback to the
       reference path, a new un-contracted one is an unreviewed kernel.

Everything here is text analysis over the two IR strings plus a small
``StepProgram`` context object — no jax import, no device use — so the
rules themselves are unit-testable from canned IR and the module stays
importable in the dep-free lint environment. Lowering the program to
GET the text (CLI ``--hlo``, trainer hook) is the caller's job.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from dlrover_tpu.lint.engine import Severity, Violation

#: contracts shipped with the package (``--fix-contracts`` rewrites)
DEFAULT_CONTRACTS_DIR = os.path.join(os.path.dirname(__file__), "contracts")

#: the world-shape vocabulary lives in common/world.py now (the
#: WorldDescriptor refactor): the contract-spec grammar, the canonical
#: axis order and the parse/format pair are defined ONCE there and
#: re-exported here for the existing call sites — shardcheck, the
#: trainer hook, the CLI and the planner all describe a program's world
#: through the same checked type instead of four re-derivations
from dlrover_tpu.common.world import (  # noqa: F401  (re-exports)
    CANONICAL_AXES,
    ZERO1_SUFFIX,
    WorldDescriptor,
    contract_spec_of,
    mesh_spec_of,
    parse_contract_spec,
    parse_mesh_spec,
)


class ShardcheckError(RuntimeError):
    """Raised by the strict lower-time hook when the compiled step
    program violates an SC rule."""

    def __init__(self, violations: Sequence[Violation]):
        self.violations = list(violations)
        super().__init__(
            f"{len(self.violations)} shardcheck violation(s):\n"
            + "\n".join(v.format() for v in self.violations)
        )


#: collective HLO opcodes the census tracks (``-start`` variants fold
#: into their base op: async pairs describe one transfer)
COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

#: dtype byte widths for HLO/StableHLO shape strings
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "i8": 1,
    "s16": 2, "u16": 2, "i16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "i32": 4, "f32": 4,
    "s64": 8, "u64": 8, "i64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: SC001 default: byte growth beyond this fraction of the contract
#: fails even when no new collective appeared
DEFAULT_BYTE_TOLERANCE = 0.10

#: SC002 default: "large" means a global tensor above this many bytes
#: (CPU-mesh tests pass explicit tiny thresholds)
DEFAULT_REPLICATED_BYTES = 256 << 20

#: StableHLO custom_call targets that are partitioner plumbing, not
#: host transfers
_BENIGN_CUSTOM_CALLS = {
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
    "MoveToHost",  # explicit host offload is its own, opted-in feature
    "MoveToDevice",
    "AllocateBuffer",
    "LayoutConstraint",
}

_HOST_CALLBACK_HINTS = ("cpu_callback", "host_callback", "py_callback")

#: custom_call targets that ARE the device kernels this repo ships
#: (Pallas lowers through Mosaic to ``tpu_custom_call``). Never host
#: transfers — SC005 must not flag them — and exactly what the SC007
#: census exists to track.
_DEVICE_KERNEL_HINTS = ("tpu_custom_call", "mosaic", "triton_kernel_call")


# ---------------------------------------------------------------------------
# shape / sharding string parsing
# ---------------------------------------------------------------------------


def shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string like ``f32[2,16,64]`` (layout
    ``{...}`` already stripped by the caller's regex). Tuples and
    opaque/token shapes return 0 — they never matter for a census."""
    m = re.match(r"([a-z]+[0-9]*)\[([0-9,]*)\]$", shape_str.strip())
    if not m:
        return 0
    width = _DTYPE_BYTES.get(m.group(1))
    if width is None:
        return 0
    n = 1
    dims = m.group(2)
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * width


def tensor_type_dims(type_str: str) -> Tuple[Tuple[int, ...], str]:
    """``'8x16x256xf32'`` → ((8, 16, 256), 'f32'); scalars → ((), dtype).
    Unparsable (dynamic dims, complex element syntax) → ((), '')."""
    parts = type_str.strip().split("x")
    if not parts:
        return (), ""
    dtype = parts[-1]
    dims: List[int] = []
    for p in parts[:-1]:
        if not p.isdigit():
            return (), ""
        dims.append(int(p))
    if not re.match(r"^[a-z]+[0-9]*$", dtype):
        return (), ""
    return tuple(dims), dtype


def tensor_type_bytes(type_str: str) -> int:
    dims, dtype = tensor_type_dims(type_str)
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        return 0
    n = 1
    for d in dims:
        n *= d
    return n * width


@dataclasses.dataclass(frozen=True)
class ParsedSharding:
    """One ``mhlo.sharding`` / HLO sharding string, reduced to what the
    rules need: how many ways the tensor is tiled (model shards) and
    how many ways each tile is replicated."""

    raw: str
    kind: str  # "replicated" | "maximal" | "tiled" | "unknown"
    tile_dims: Tuple[int, ...] = ()
    num_devices: int = 0
    replicate_ways: int = 1

    @property
    def tile_count(self) -> int:
        n = 1
        for d in self.tile_dims:
            n *= d
        return n


def parse_sharding(raw: str) -> ParsedSharding:
    """Parse the V1 sharding syntax jax prints into ``mhlo.sharding``:
    ``{replicated}``, ``{maximal device=0}``,
    ``{devices=[2,2,2]<=[8] last_tile_dim_replicate}`` (the trailing
    tile dim is the replication factor), iota/transpose device lists."""
    s = raw.strip().strip("{}").strip()
    if s == "replicated" or s == "":
        return ParsedSharding(raw, "replicated")
    if s.startswith("maximal"):
        return ParsedSharding(raw, "maximal")
    m = re.match(r"devices=\[([0-9,]+)\]", s)
    if not m:
        return ParsedSharding(raw, "unknown")
    dims = tuple(int(d) for d in m.group(1).split(","))
    n = 1
    for d in dims:
        n *= d
    if "last_tile_dim_replicate" in s:
        return ParsedSharding(
            raw, "tiled", tile_dims=dims[:-1], num_devices=n,
            replicate_ways=dims[-1],
        )
    return ParsedSharding(raw, "tiled", tile_dims=dims, num_devices=n)


# ---------------------------------------------------------------------------
# replica-group parsing + mesh-axis attribution
# ---------------------------------------------------------------------------


def parse_replica_groups(attr: str) -> List[Tuple[int, ...]]:
    """Both HLO forms: explicit ``{{0,2},{1,3}}`` and iota
    ``[4,2]<=[8]`` / ``[4,2]<=[2,2,2]T(2,1,0)`` (arange over the
    reshape dims, transposed by the permutation, regrouped row-major)."""
    attr = attr.strip()
    if attr.startswith("{"):
        groups = []
        for grp in re.findall(r"\{([0-9,\s]*)\}", attr):
            ids = tuple(int(x) for x in grp.replace(" ", "").split(",") if x)
            if ids:
                groups.append(ids)
        return groups
    m = re.match(
        r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", attr
    )
    if not m:
        return []
    out_dims = [int(x) for x in m.group(1).split(",")]
    src_dims = [int(x) for x in m.group(2).split(",")]
    total = 1
    for d in src_dims:
        total *= d
    ids = list(range(total))
    if m.group(3):
        perm = [int(x) for x in m.group(3).split(",")]
        # arange reshaped to src_dims, transposed by perm, flattened —
        # index arithmetic without numpy (this module stays dep-free)
        strides = [1] * len(src_dims)
        for i in range(len(src_dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * src_dims[i + 1]
        t_dims = [src_dims[p] for p in perm]
        t_strides = [strides[p] for p in perm]
        flat: List[int] = []

        def _emit(prefix_idx: List[int], depth: int):
            if depth == len(t_dims):
                flat.append(
                    sum(i * s for i, s in zip(prefix_idx, t_strides))
                )
                return
            for i in range(t_dims[depth]):
                _emit(prefix_idx + [i], depth + 1)

        _emit([], 0)
        ids = flat
    if len(out_dims) == 1:
        return [tuple(ids)]
    group_size = out_dims[-1]
    n_groups = 1
    for d in out_dims[:-1]:
        n_groups *= d
    return [
        tuple(ids[g * group_size:(g + 1) * group_size])
        for g in range(n_groups)
    ]


def parse_source_target_pairs(attr: str) -> List[Tuple[int, int]]:
    return [
        (int(a), int(b))
        for a, b in re.findall(r"\{(\d+),(\d+)\}", attr)
    ]


class MeshCoords:
    """Maps a replica-group member to its coordinate along each mesh
    axis, so a group of participants can be attributed to the axes its
    members vary over.

    ``axis_sizes`` follows the mesh's axis order. Group members in
    post-GSPMD HLO are **logical device-assignment positions** (the
    partition index), NOT hardware device ids — and jax builds the
    assignment in ``mesh.devices.flat`` order, so a member decodes
    directly as a flat index into the mesh shape. (Mapping through
    hardware ids would invert the attribution on any mesh whose device
    order is permuted — every real TPU torus mesh.)

    ``n_slices > 1`` adds LINK-CLASS attribution: the multislice
    layout is slice-major over the outermost (dp) axis
    (``parallel/mesh.py _build_multislice_mesh``), so a device-
    assignment position's slice is simply ``position // per_slice`` —
    and a replica group whose members span more than one slice is a
    collective that crosses DCN."""

    def __init__(self, axis_sizes: Dict[str, int], n_slices: int = 1):
        self.axis_sizes = dict(axis_sizes)
        self.axes = list(axis_sizes)
        n = 1
        for s in axis_sizes.values():
            n *= s
        self.num_devices = n
        self.n_slices = max(1, int(n_slices))
        if self.n_slices > 1 and n % self.n_slices:
            # a world that doesn't tile into slices cannot be slice-
            # attributed; fail soft to single-slice (everything "ici")
            # rather than mis-labeling — the mesh builder would have
            # rejected this topology anyway
            self.n_slices = 1
        self._per_slice = (
            n // self.n_slices if self.n_slices > 1 else n
        )

    def slice_of(self, position: int) -> int:
        """Slice of a device-assignment position (slice-major layout)."""
        if self._per_slice <= 0:
            return 0
        return position // self._per_slice

    def slices_spanned(self, members: Sequence[int]) -> int:
        """Distinct slices a replica group's members live on."""
        if self.n_slices <= 1:
            return 1
        return len({self.slice_of(m) for m in members}) or 1

    def link_of_groups(self, groups: Sequence[Sequence[int]]) -> Tuple[
        str, int
    ]:
        """``("ici"|"dcn", max slices spanned by any group)``. Empty
        groups (= every device participates) span all slices."""
        if self.n_slices <= 1:
            return "ici", 1
        if not groups:
            return "dcn", self.n_slices
        spanned = max(self.slices_spanned(g) for g in groups)
        return ("dcn" if spanned > 1 else "ici"), spanned

    def link_of_pairs(self, pairs: Sequence[Tuple[int, int]]) -> Tuple[
        str, int
    ]:
        """collective-permute link class: any pair crossing a slice
        boundary makes the op ride DCN."""
        if self.n_slices <= 1:
            return "ici", 1
        spanned = 1
        for s, t in pairs:
            if s != t and self.slice_of(s) != self.slice_of(t):
                spanned = 2
                break
        return ("dcn" if spanned > 1 else "ici"), spanned

    def coords(self, position: int) -> Optional[Tuple[int, ...]]:
        if not 0 <= position < self.num_devices:
            return None
        out = []
        for axis in reversed(self.axes):
            size = self.axis_sizes[axis]
            out.append(position % size)
            position //= size
        return tuple(reversed(out))

    def _varying_axes(self, members: Sequence[int]) -> Optional[List[str]]:
        coord_list = [self.coords(m) for m in members]
        if any(c is None for c in coord_list):
            return None
        varying = []
        for i, axis in enumerate(self.axes):
            if len({c[i] for c in coord_list}) > 1:
                varying.append(axis)
        return varying

    def attribute_groups(self, groups: Sequence[Sequence[int]]) -> str:
        """Axis label for a replica-group list: the axes whose
        coordinates vary inside the groups — ``"dp"``, ``"fsdp"``,
        ``"dp+fsdp"`` for a fused data reduce, ``"unattributed"`` when
        ids fall outside the mesh. Always named by the actual axes
        (never collapsed to a "world" label): the same logical
        collective must key the same census cell on every mesh shape,
        or contracts stop being comparable across meshes."""
        if not groups:
            # num_replicas-style empty groups = every device participates
            varying = {a for a, s in self.axis_sizes.items() if s > 1}
        else:
            varying = set()
            for g in groups:
                v = self._varying_axes(g)
                if v is None:
                    return "unattributed"
                varying.update(v)
        if not varying:
            return "self"
        return "+".join(a for a in self.axes if a in varying)

    def attribute_pairs(self, pairs: Sequence[Tuple[int, int]]) -> str:
        """collective-permute: attribute by the axes source and target
        coordinates differ over (self-pairs ignored)."""
        varying: set = set()
        for s, t in pairs:
            if s == t:
                continue
            v = self._varying_axes([s, t])
            if v is None:
                return "unattributed"
            varying.update(v)
        if not varying:
            return "self"
        return "+".join(a for a in self.axes if a in varying)


# ---------------------------------------------------------------------------
# compiled-HLO collective census (SC001 substrate)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str
    shape: str  # result shape, e.g. "f32[2,16,64]"
    bytes: int  # per-device contribution (see parse_collectives)
    axes: str  # mesh-axis label ("fsdp", "dp+fsdp", "tp", ...)
    line: int  # 1-indexed line in the HLO text
    #: link class: "dcn" when any replica group spans >1 slice of a
    #: multislice device assignment, else "ici" (single-slice meshes
    #: are all-ici by construction)
    link: str = "ici"
    #: modeled per-device bytes this op moves ACROSS the slice
    #: boundary (0 for ici ops) — see parse_collectives
    dcn_bytes: int = 0


_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(?)(?:[a-z0-9]+\[[0-9,]*\])"
    r"[^=]*?\b(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\("
)
_SHAPE_RE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def _result_shape(line: str, op_start: int, is_async: bool) -> str:
    """The RESULT payload shape of a collective op line. Sync ops:
    the (possibly tuple of) shapes before the op name are all results
    — variadic collectives sum below. Async ``-start`` ops: the tuple
    is (operand…, result…); the LAST element is the result, so the
    census records the same bytes whether XLA lowered the transfer
    sync or async."""
    eq = line.find("= ")
    seg = line[eq + 2:op_start] if eq >= 0 else line[:op_start]
    shapes = _SHAPE_RE.findall(seg)
    if not shapes:
        return ""
    if is_async or len(shapes) == 1:
        return shapes[-1]
    return "+".join(shapes)  # sync variadic: every element is a result


def parse_collectives(
    hlo_text: str, coords: MeshCoords
) -> List[CollectiveOp]:
    """Every collective op in an optimized HLO module, with its payload
    and mesh-axis attribution. ``-done`` halves of async pairs are
    skipped (the ``-start`` carries the transfer).

    Byte accounting is the PER-DEVICE CONTRIBUTION of one op — the same
    unit the analytic comm ledger uses (profiler/comm.py, "what one
    rank sends"): the full reduced tensor for all-reduce, the scattered
    shard for reduce-scatter, and for all-gather the operand shard each
    rank contributes (result bytes / participants), NOT the gathered
    result. Counting the gathered result would overstate an all-gather
    by the axis size against every other op — and make the
    allreduce→reduce-scatter+all-gather rewrite (zero-1) read as MORE
    communication when it moves strictly less per link.

    On a multislice assignment (``coords.n_slices > 1``) each op also
    carries its LINK class and modeled per-device DCN bytes — what the
    op moves across the slice boundary. The contribution unit cannot
    express this (a flat reduce-scatter over dp and the hierarchical
    DCN leg scatter the same result shape while moving very different
    bytes over the slow link), so the DCN model follows the op's
    *operand*, the analytic-formula approach the comm ledger already
    takes for bandwidth: with ``s`` = slices the group spans and
    ``frac = 1 - 1/s`` (the share of a uniformly-partitioned payload
    that is remote),

    - all-reduce / all-to-all: operand == result → ``result × frac``;
    - reduce-scatter: operand = result × participants → that × frac
      (the un-scattered input is what rides the ring past the cut);
    - all-gather: every remote shard crosses once → gathered result ×
      frac;
    - collective-permute: the full payload crosses iff the pair does.

    A model, not a packet count — its value is that flat and
    hierarchical variants of the same reduction are scored by the same
    rule, so the 2slice contracts can assert the hierarchy's DCN bytes
    are ~1/dp_in of the flat path's and veto a regression that moves
    bytes back onto the slow link."""
    out: List[CollectiveOp] = []
    for lineno, line in enumerate(hlo_text.splitlines(), start=1):
        if "-done" in line:
            continue
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        shape = _result_shape(line, m.start(1), m.group(2) is not None)
        raw_bytes = sum(shape_bytes(s) for s in shape.split("+"))
        nbytes = raw_bytes
        if kind == "collective-permute":
            pairs = parse_source_target_pairs(
                _attr(line, "source_target_pairs")
            )
            axes = coords.attribute_pairs(pairs)
            link, spanned = coords.link_of_pairs(pairs)
            participants = 1
        else:
            groups = parse_replica_groups(_attr(line, "replica_groups"))
            axes = coords.attribute_groups(groups)
            link, spanned = coords.link_of_groups(groups)
            participants = (
                len(groups[0]) if groups and groups[0]
                else max(coords.num_devices, 1)
            )
            if kind == "all-gather":
                nbytes //= max(participants, 1)
        dcn_bytes = 0
        if link == "dcn":
            frac = 1.0 - 1.0 / max(spanned, 2)
            if kind == "collective-permute":
                dcn_bytes = raw_bytes
            elif kind == "reduce-scatter":
                dcn_bytes = int(raw_bytes * participants * frac)
            else:
                dcn_bytes = int(raw_bytes * frac)
        out.append(
            CollectiveOp(
                kind=kind,
                shape=shape,
                bytes=nbytes,
                axes=axes,
                line=lineno,
                link=link,
                dcn_bytes=dcn_bytes,
            )
        )
    return out


def _attr(line: str, name: str) -> str:
    """Value of ``name=...`` in an HLO op line, balanced over {}/[]/()
    — handles the iota forms ``[4,2]<=[8]`` and
    ``[4,2]<=[2,2,2]T(2,1,0)``, which continue past their first ``]``."""
    idx = line.find(name + "=")
    if idx < 0:
        return ""
    i = idx + len(name) + 1
    depth = 0
    start = i
    while i < len(line):
        c = line[i]
        if c in "{[(":
            depth += 1
        elif c in "}])":
            depth -= 1
            if depth == 0 and line[i + 1:i + 2] not in ("<", "T"):
                return line[start:i + 1]
        elif c == "," and depth == 0:
            return line[start:i]
        i += 1
    return line[start:]


def collective_census(
    hlo_text: str, coords: MeshCoords
) -> Dict[str, Dict[str, int]]:
    """``{"all-gather|fsdp": {"count": N, "bytes": B}, ...}`` — the
    SC001 fingerprint. Bytes are per-device contributions (see
    ``parse_collectives``) summed over static ops (a scan body counts
    once: the fingerprint tracks the *program*, not the per-step issue
    count — accum lives in the comm ledger, not here).

    On a multislice assignment every cell additionally carries
    ``dcn_bytes`` — the modeled bytes its ops move across the slice
    boundary (0 for cells whose ops all stay on ICI). Cell KEYS are
    link-free on purpose: the flat and hierarchical programs label the
    same logical reduction ``…|dp`` on every topology, so their
    censuses stay comparable and only the link split differs."""
    multislice = coords.n_slices > 1
    census: Dict[str, Dict[str, int]] = {}
    for op in parse_collectives(hlo_text, coords):
        key = f"{op.kind}|{op.axes}"
        cell = census.setdefault(key, {"count": 0, "bytes": 0})
        if multislice:
            cell.setdefault("dcn_bytes", 0)
            cell["dcn_bytes"] += op.dcn_bytes
        cell["count"] += 1
        cell["bytes"] += op.bytes
    return census


def census_dcn_bytes(census: Dict[str, Dict[str, int]]) -> int:
    """Total modeled DCN bytes of a (multislice) census."""
    return sum(c.get("dcn_bytes", 0) for c in census.values())


# ---------------------------------------------------------------------------
# SC006 — exposed vs. overlapped DCN bytes (schedule analysis)
# ---------------------------------------------------------------------------
#
# The census counts WHAT crosses the slice boundary; this section asks
# WHEN — can the transfer hide behind compute, or does the step stall
# on it?  It reads the post-GSPMD HLO as a graph of computations and
# classifies every DCN collective as OVERLAPPED or EXPOSED:
#
# - **async pairs** (``-start``/``-done``, how a latency-hiding TPU
#   schedule spells overlap): overlapped iff some compute-class op in
#   the same computation is neither an ancestor of the start nor a
#   descendant of the done — i.e. the scheduler has real work to run
#   while the transfer is in flight.
# - **sync collectives** (CPU contract programs — the CPU backend never
#   emits async pairs, so structure must stand in for the schedule): a
#   DCN collective is overlapped iff it executes inside a ``while``
#   body AND its transitive operand closure *within that body* contains
#   no compute-class op — it consumes only loop-carried state (gtes
#   through passive reshapes/concats), so it is issueable at iteration
#   entry, concurrent with the whole iteration's compute.  This is the
#   shape ``overlap_value_and_grad`` lowers to: the exchange of micro
#   k-1's gradients rides the loop carry while micro k's backward runs.
#   Deliberately conservative: a collective fed by ANY in-iteration
#   compute (the fused hierarchical engine's per-micro DCN leg, the
#   loss psum) counts exposed even though XLA may find partial overlap
#   — partial credit would let a re-serializing change hide behind
#   scheduler luck.
#
# Bytes are weighted by the product of enclosing loop trip counts
# (``backend_config known_trip_count``) so "exposed bytes per step"
# compares schedules honestly: a DCN leg issued once per microbatch
# inside a trip-N accumulation scan costs N transfers; the overlap
# schedule's single post-scan flush costs one.

#: opcodes that ARE the work a transfer could hide behind (plus any
#: collective: a DCN op gated on another transfer is not issueable at
#: iteration entry)
_COMPUTE_OPS = frozenset({
    "dot", "convolution", "cholesky", "triangular-solve", "fft",
    "custom-call", "scatter", "sort",
})

_COMPUTATION_HEAD_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_REF_RE = re.compile(r"%([\w.\-]+)")
#: optional shape prefix (absent after a tuple-shaped result has been
#: skipped — ``(s32[], f32[2]{0}) while(...)``), then the opcode; a
#: shape can never false-match the opcode group (``[`` follows it, not
#: ``(``)
_SHAPE_OPCODE_RE = re.compile(
    r"(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?\s*)?([a-z][a-z0-9\-]*)\("
)
_TRIP_RE = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}")


@dataclasses.dataclass
class _HloInstr:
    name: str
    opcode: str
    line: int  # 1-indexed line in the module text
    operands: Tuple[str, ...]  # same-computation value refs
    called: Tuple[str, ...]  # computations fusion/call/cond branches run
    body: str = ""  # while only: the body computation
    trip: int = 1  # while only: known_trip_count (1 when unknown)


@dataclasses.dataclass
class _HloComputation:
    name: str
    entry: bool
    instrs: Dict[str, _HloInstr] = dataclasses.field(default_factory=dict)


def _split_instr_rhs(rhs: str) -> Tuple[str, str, str]:
    """``(opcode, operand_segment, attr_tail)`` of an HLO instruction's
    right-hand side. Tuple-shaped results (``(s32[], f32[2]{0}) while``)
    are skipped by balanced-paren counting — layout tiles like
    ``{1,0:T(8,128)}`` keep parens balanced, so this survives them."""
    s = rhs.lstrip()
    if s.startswith("("):
        depth = 0
        for i, ch in enumerate(s):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    s = s[i + 1:].lstrip()
                    break
    m = _SHAPE_OPCODE_RE.match(s)
    if not m:
        return "", "", ""
    opcode = m.group(1)
    depth, i = 1, m.end()
    start = i
    while i < len(s) and depth:
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
        i += 1
    return opcode, s[start:i - 1], s[i:]


def _called_computations(attr_tail: str) -> Tuple[List[str], str]:
    """``(called, body)``: computation refs in the attributes that mean
    "this op RUNS that computation" (fusion/call/conditional/while —
    NOT ``to_apply`` reducers, which are scalar add/max lambdas), and
    the while body specifically."""
    called: List[str] = []
    body = ""
    for key in ("calls", "body", "condition", "branch_computations"):
        val = _attr(attr_tail, key)
        if not val:
            continue
        refs = _REF_RE.findall(val)
        called.extend(refs)
        if key == "body" and refs:
            body = refs[0]
    return called, body


def _parse_hlo_module(hlo_text: str) -> Dict[str, _HloComputation]:
    """The module as named computations of def-use-linked instructions.
    Line-oriented, like the rest of this file: optimized HLO prints one
    instruction per line and closes every computation with ``}``."""
    comps: Dict[str, _HloComputation] = {}
    current: Optional[_HloComputation] = None
    for lineno, line in enumerate(hlo_text.splitlines(), start=1):
        if current is None:
            m = _COMPUTATION_HEAD_RE.match(line)
            if m and line.rstrip().endswith("{"):
                current = _HloComputation(
                    name=m.group(2), entry=m.group(1) is not None
                )
                comps[current.name] = current
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        opcode, operand_seg, attr_tail = _split_instr_rhs(rhs)
        if not opcode:
            continue
        called, body = _called_computations(attr_tail)
        trip_m = _TRIP_RE.search(attr_tail)
        current.instrs[name] = _HloInstr(
            name=name,
            opcode=opcode,
            line=lineno,
            operands=tuple(_REF_RE.findall(operand_seg)),
            called=tuple(called),
            body=body,
            trip=int(trip_m.group(1)) if trip_m else 1,
        )
    return comps


def _while_body_context(
    comps: Dict[str, _HloComputation]
) -> Dict[str, Tuple[str, int]]:
    """``{body_computation: (computation holding the while, trip)}``."""
    ctx: Dict[str, Tuple[str, int]] = {}
    for comp in comps.values():
        for ins in comp.instrs.values():
            if ins.opcode == "while" and ins.body:
                ctx[ins.body] = (comp.name, ins.trip)
    return ctx


def _trip_product(
    comp_name: str, while_ctx: Dict[str, Tuple[str, int]]
) -> int:
    """Product of trip counts of every loop enclosing ``comp_name``
    (1 for entry-level code)."""
    product, seen = 1, set()
    while comp_name in while_ctx and comp_name not in seen:
        seen.add(comp_name)
        comp_name, trip = while_ctx[comp_name]
        product *= max(trip, 1)
    return product


def _is_collective_opcode(opcode: str) -> bool:
    return any(
        opcode == c or opcode.startswith(c + "-") for c in COLLECTIVE_OPS
    )


def _computation_has_compute(
    name: str, comps: Dict[str, _HloComputation], memo: Dict[str, bool]
) -> bool:
    if name not in comps:
        return False
    if name in memo:
        return memo[name]
    memo[name] = False  # cycle guard (HLO call graphs are acyclic)
    memo[name] = any(
        _is_compute_instr(ins, comps, memo)
        for ins in comps[name].instrs.values()
    )
    return memo[name]


def _is_compute_instr(
    ins: _HloInstr, comps: Dict[str, _HloComputation], memo: Dict[str, bool]
) -> bool:
    if ins.opcode in _COMPUTE_OPS or _is_collective_opcode(ins.opcode):
        return True
    if ins.called:  # fusion / call / while / conditional
        return any(
            _computation_has_compute(c, comps, memo) for c in ins.called
        )
    return False


def _closure_has_compute(
    start: _HloInstr,
    comp: _HloComputation,
    comps: Dict[str, _HloComputation],
    memo: Dict[str, bool],
) -> bool:
    """Does the transitive operand closure of ``start`` WITHIN ``comp``
    contain a compute-class instruction?  (Refs that are not local
    instruction names — parameters, computation names — terminate.)"""
    stack, seen = list(start.operands), set()
    while stack:
        ref = stack.pop()
        if ref in seen:
            continue
        seen.add(ref)
        ins = comp.instrs.get(ref)
        if ins is None:
            continue
        if _is_compute_instr(ins, comps, memo):
            return True
        stack.extend(ins.operands)
    return False


def _async_pair_overlapped(
    start: _HloInstr,
    comp: _HloComputation,
    comps: Dict[str, _HloComputation],
    memo: Dict[str, bool],
) -> bool:
    """``-start``/``-done`` rule: overlapped iff some compute-class op
    in the same computation is ordered with NEITHER half — not an
    ancestor of the start, not a descendant of the done — so the
    scheduler can run it while the transfer is in flight."""
    done = next(
        (
            i for i in comp.instrs.values()
            if i.opcode.endswith("-done") and start.name in i.operands
        ),
        None,
    )
    users: Dict[str, List[str]] = {}
    for ins in comp.instrs.values():
        for ref in ins.operands:
            users.setdefault(ref, []).append(ins.name)

    def _reach(roots: Iterable[str], edges) -> set:
        out, stack = set(), list(roots)
        while stack:
            ref = stack.pop()
            if ref in out:
                continue
            out.add(ref)
            stack.extend(edges(ref))
        return out

    ancestors = _reach(
        start.operands,
        lambda r: comp.instrs[r].operands if r in comp.instrs else (),
    )
    descendants = _reach(
        users.get(done.name, []) if done is not None else [],
        lambda r: users.get(r, []),
    )
    ordered = ancestors | descendants | {start.name}
    if done is not None:
        ordered.add(done.name)
    return any(
        ins.name not in ordered and _is_compute_instr(ins, comps, memo)
        for ins in comp.instrs.values()
    )


def overlap_report(
    hlo_text: str,
    coords: MeshCoords,
    collectives: Optional[List[CollectiveOp]] = None,
) -> Dict:
    """Classify every DCN collective of an optimized multislice program
    as overlapped or exposed (module docstring above) and total the
    trip-weighted bytes:

    ``{"dcn_exposed_bytes", "dcn_overlapped_bytes", "overlap_ratio",
    "ops": [...]}``

    ``overlap_ratio`` = overlapped / (overlapped + exposed), 0.0 when
    the program moves no DCN bytes at all.  ``ops`` carries the
    per-collective verdicts for the CLI surface; the contract
    stores only the three totals."""
    if collectives is None:
        collectives = parse_collectives(hlo_text, coords)
    dcn = [op for op in collectives if op.link == "dcn" and op.dcn_bytes]
    exposed = overlapped = 0
    rows: List[Dict] = []
    if dcn:
        comps = _parse_hlo_module(hlo_text)
        line_map: Dict[int, Tuple[_HloComputation, _HloInstr]] = {}
        for comp in comps.values():
            for ins in comp.instrs.values():
                line_map[ins.line] = (comp, ins)
        while_ctx = _while_body_context(comps)
        memo: Dict[str, bool] = {}
        for op in dcn:
            hit = line_map.get(op.line)
            if hit is None:  # unparseable line: count it exposed
                exposed += op.dcn_bytes
                continue
            comp, ins = hit
            weight = _trip_product(comp.name, while_ctx)
            nbytes = op.dcn_bytes * weight
            if ins.opcode.endswith("-start"):
                is_overlapped = _async_pair_overlapped(
                    ins, comp, comps, memo
                )
            else:
                is_overlapped = (
                    comp.name in while_ctx
                    and not _closure_has_compute(ins, comp, comps, memo)
                )
            if is_overlapped:
                overlapped += nbytes
            else:
                exposed += nbytes
            rows.append({
                "kind": op.kind,
                "line": op.line,
                "dcn_bytes": nbytes,
                "overlapped": is_overlapped,
            })
    total = exposed + overlapped
    return {
        "dcn_exposed_bytes": int(exposed),
        "dcn_overlapped_bytes": int(overlapped),
        "overlap_ratio": round(overlapped / total, 4) if total else 0.0,
        "ops": rows,
    }


#: SC006: a re-serialization may keep the ratio but still regress the
#: absolute stall (payload growth); exposed bytes get the same growth
#: tolerance as SC001, the ratio an absolute slack for float noise
OVERLAP_RATIO_SLACK = 0.02


def check_overlap_against_contract(
    program: StepProgram,
    contract: Dict,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
    report: Optional[Dict] = None,
) -> List[Violation]:
    """SC006: diff the program's exposed-vs-overlapped DCN split
    against the contract's recorded ``overlap`` section.  Fails when
    exposed bytes grew beyond tolerance or the overlap ratio dropped —
    both spell "a change re-serialized the DCN leg the schedule used
    to hide".  Silent when the contract has no ``overlap`` section
    (pre-overlap contract vintage) or on a config-hash mismatch (SC001
    already reports that)."""
    ref = contract.get("overlap")
    if not ref:
        return []
    if contract.get("config_hash") and program.config_hash and \
            contract["config_hash"] != program.config_hash:
        return []
    if report is None:
        report = overlap_report(program.hlo, program.coords())
    out: List[Violation] = []
    ref_exposed = ref.get("dcn_exposed_bytes", 0)
    got_exposed = report["dcn_exposed_bytes"]
    if got_exposed > ref_exposed * (1.0 + byte_tolerance) and \
            got_exposed > ref_exposed:
        out.append(
            program.violation(
                "SC006",
                f"exposed DCN bytes grew {ref_exposed} -> {got_exposed} "
                f"(> {byte_tolerance:.0%} tolerance): the step now "
                "STALLS on slice-boundary transfers the contract "
                "records as hidden behind compute — a change "
                "re-serialized the DCN schedule.",
            )
        )
    ref_ratio = float(ref.get("overlap_ratio", 0.0))
    got_ratio = report["overlap_ratio"]
    if ref_ratio > 0.0 and got_ratio < ref_ratio - OVERLAP_RATIO_SLACK:
        out.append(
            program.violation(
                "SC006",
                f"DCN overlap_ratio dropped {ref_ratio:.4f} -> "
                f"{got_ratio:.4f}: transfers the overlap schedule "
                "pipelined behind the accumulation scan are exposed "
                "again — justify and --fix-contracts, or restore the "
                "schedule.",
            )
        )
    return out


# ---------------------------------------------------------------------------
# SC008 — pipeline-schedule contract (bubble fraction + stage handoffs)
# ---------------------------------------------------------------------------
#
# The census counts the pp collectives; SC008 asks whether the
# SCHEDULE that issues them survived. Two dimensions, both recorded in
# the contract's ``pp_schedule`` section:
#
# - **bubble fraction** — the analytic steady-state pipeline bubble of
#   the declared schedule geometry, ``(p-1)/(m·v)`` for (interleaved)
#   1F1B over ideal compute ticks (with ``v = p`` virtual stages this
#   is the classic ``(p-1)/(p·m)``), ``(p-1)/m`` for GPipe-style
#   serial fill/drain. A change that re-serializes the schedule (drops
#   virtual stages, shrinks the microbatch count, flips to gpipe)
#   grows the fraction and fails the diff — same shape as SC006's
#   "the stall came back" check, applied to pp instead of DCN.
# - **stage-handoff pattern** — the static ``collective-permute|pp``
#   instance count of the lowered program. The explicit 1F1B engine
#   unrolls its tick table, so each scheduled hop is its own HLO op; a
#   re-serialization that rolls the handoffs into a scan (or prunes
#   scheduled hops) collapses this count even when the census bytes
#   stay plausible.

#: analytic bubble-fraction slack: the contract stores the model's
#: fraction, the program recomputes it from its own geometry — any
#: real schedule change moves it by >= 1/(m·v), far above float noise
BUBBLE_FRACTION_SLACK = 0.005
#: stage-handoff count tolerance: XLA may merge/split a permute pair
#: across versions; a schedule change moves the count by O(ticks)
PP_PERMUTE_COUNT_TOLERANCE = 0.10


def schedule_bubble_fraction(
    schedule: str, pp: int, microbatches: int, virtual_stages: int = 1
) -> float:
    """Steady-state pipeline bubble of the engine's schedules, as a
    fraction of ideal compute ticks (parallel/pp_schedule.py tick
    model: every microbatch×chunk costs one forward and one backward
    tick per stage).

    - interleaved 1f1b: fill/drain costs ``2(p-1)`` chunk-granular
      ticks against ``2·m·v`` ideal ticks -> ``(p-1)/(m·v)``; with the
      contract geometry ``v = p`` this is the paper's ``(p-1)/(p·m)``.
    - gpipe / non-interleaved 1f1b (``v = 1``): ``(p-1)/m`` — the
      fill/drain is microbatch-granular, so losing interleave DOUBLES
      the bubble at ``v = 2`` and the contract diff sees it.
    """
    p = max(1, int(pp))
    m = max(1, int(microbatches))
    v = max(1, int(virtual_stages)) if schedule == "1f1b" else 1
    if p == 1:
        return 0.0
    return (p - 1) / float(m * v)


def pp_schedule_report(
    program: StepProgram,
    collectives: Optional[List[CollectiveOp]] = None,
) -> Optional[Dict]:
    """The program's pp-schedule fingerprint, or None when the mesh
    has no pp axis. Geometry fields come from the lowering hints
    (``program.pp_schedule``); the handoff evidence from the HLO —
    every collective-permute whose pairs vary over ``pp`` (attribution
    is link-free, so single- and multislice programs fingerprint
    identically):

    - ``ppermute_calls``: static op count. The per-stage layer
      re-layout permutes at schedule entry/exit live here.
    - ``ppermute_hops``: the same ops weighted by their enclosing
      loop trip counts (SC006's honesty rule) — the tick loop rolls
      the per-tick ring hops into a ``while`` whose trip count IS the
      schedule length, so a re-serialization that stretches the
      schedule moves this number even when the static count holds."""
    p = program.axis_sizes.get("pp", 1)
    if p <= 1:
        return None
    if collectives is None:
        collectives = parse_collectives(program.hlo, program.coords())
    pp_ops = [
        op for op in collectives
        if op.kind == "collective-permute" and "pp" in op.axes.split("+")
    ]
    permutes = len(pp_ops)
    hops = 0
    if pp_ops:
        comps = _parse_hlo_module(program.hlo)
        line_map: Dict[int, str] = {}
        for comp in comps.values():
            for ins in comp.instrs.values():
                line_map[ins.line] = comp.name
        while_ctx = _while_body_context(comps)
        for op in pp_ops:
            comp_name = line_map.get(op.line)
            hops += (
                _trip_product(comp_name, while_ctx) if comp_name else 1
            )
    out = {
        "pp": int(p),
        "ppermute_calls": int(permutes),
        "ppermute_hops": int(hops),
    }
    hints = program.pp_schedule or {}
    if hints.get("schedule"):
        m = int(hints.get("microbatches", p))
        v = int(hints.get("virtual_stages", 1))
        out.update({
            "schedule": hints["schedule"],
            "microbatches": m,
            "virtual_stages": v,
            "bubble_fraction": round(
                schedule_bubble_fraction(hints["schedule"], p, m, v), 6
            ),
        })
    return out


def check_pp_schedule_against_contract(
    program: StepProgram,
    contract: Dict,
    report: Optional[Dict] = None,
) -> List[Violation]:
    """SC008: diff the program's pipeline-schedule fingerprint against
    the contract's ``pp_schedule`` section. Fails when the analytic
    bubble fraction grew (the schedule re-serialized — fewer virtual
    stages, fewer microbatches, a gpipe fallback) or the static
    stage-handoff pattern collapsed/exploded. Silent when the contract
    has no ``pp_schedule`` section (non-pp contract) or on a
    config-hash mismatch (SC001 already reports that)."""
    ref = contract.get("pp_schedule")
    if not ref:
        return []
    if contract.get("config_hash") and program.config_hash and \
            contract["config_hash"] != program.config_hash:
        return []
    if report is None:
        report = pp_schedule_report(program)
    out: List[Violation] = []
    if report is None:
        out.append(
            program.violation(
                "SC008",
                f"contract pins a pipeline schedule over pp="
                f"{ref.get('pp')} but the program's mesh has no pp "
                "axis — the pipeline engine was bypassed entirely; "
                "justify and --fix-contracts, or restore the pp "
                "layout.",
            )
        )
        return out
    ref_frac = float(ref.get("bubble_fraction", 0.0))
    got_frac = report.get("bubble_fraction")
    if ref_frac > 0.0 and got_frac is not None and \
            got_frac > ref_frac + BUBBLE_FRACTION_SLACK:
        out.append(
            program.violation(
                "SC008",
                f"pipeline bubble fraction grew {ref_frac:.4f} -> "
                f"{got_frac:.4f} (schedule "
                f"{ref.get('schedule')}/m={ref.get('microbatches')}/"
                f"v={ref.get('virtual_stages')} -> "
                f"{report.get('schedule')}/m={report.get('microbatches')}"
                f"/v={report.get('virtual_stages')}): the schedule "
                "re-serialized — stages idle through a longer "
                "fill/drain than the contract records. Justify and "
                "--fix-contracts, or restore the interleaved 1F1B "
                "schedule.",
            )
        )
    for dim, what in (
        ("ppermute_calls", "static stage-handoff op count"),
        ("ppermute_hops", "trip-weighted stage-handoff executions"),
    ):
        ref_n = int(ref.get(dim, 0))
        got_n = int(report[dim])
        if ref_n <= 0:
            continue
        lo = ref_n * (1.0 - PP_PERMUTE_COUNT_TOLERANCE)
        hi = ref_n * (1.0 + PP_PERMUTE_COUNT_TOLERANCE)
        if not (lo <= got_n <= hi):
            out.append(
                program.violation(
                    "SC008",
                    f"stage-handoff pattern changed: {what} "
                    f"{ref_n} in the contract, {got_n} in the program "
                    f"(> {PP_PERMUTE_COUNT_TOLERANCE:.0%} tolerance). "
                    "The tick loop's trip count IS the schedule "
                    "length — a grown hop count means the schedule "
                    "stretched (extra serial ticks), a collapsed one "
                    "means scheduled hops were pruned. Justify and "
                    "--fix-contracts, or restore the schedule.",
                )
            )
    return out


# ---------------------------------------------------------------------------
# StableHLO entry-signature parsing (SC002/SC003/SC004 substrate)
# ---------------------------------------------------------------------------


_ATTR_BLOCK = r"\{((?:[^{}\"]|\"[^\"]*\")*)\}"
_ARG_RE = re.compile(r"%arg(\d+): tensor<([^>]+)>\s*" + _ATTR_BLOCK)
_RESULT_RE = re.compile(r"tensor<([^>]+)>\s*(?:" + _ATTR_BLOCK + r")?")
_SHARDING_CONSTRAINT_RE = re.compile(
    r"stablehlo\.custom_call @Sharding\(.*?mhlo\.sharding = "
    r"\"([^\"]*)\".*?->\s*tensor<([^>]+)>"
)
_DOT_GENERAL_RE = re.compile(
    r"stablehlo\.dot_general\b.*?->\s*tensor<([^>]+)>"
)


@dataclasses.dataclass(frozen=True)
class EntryArg:
    index: int
    type_str: str
    sharding: Optional[str]
    aliases_output: Optional[int]


@dataclasses.dataclass(frozen=True)
class EntryResult:
    index: int
    type_str: str
    sharding: Optional[str]
    result_info: str  # jax.result_info pytree path, e.g. "[0]['params']…"


def parse_entry_signature(
    stablehlo: str,
) -> Tuple[List[EntryArg], List[EntryResult]]:
    """Args and results of the public @main func, with shardings and
    donation links. jax prints the signature on one (very long) line;
    we slice text between ``@main(`` and the body-opening ``{``."""
    start = stablehlo.find("@main(")
    if start < 0:
        return [], []
    arrow = stablehlo.find(") -> (", start)
    if arrow < 0:
        return [], []
    arg_text = stablehlo[start:arrow]
    # results end at the paren closing the tuple opened by ") -> (" —
    # scanned with quote awareness: sharding strings contain parens
    # (iota transposes like T(1,0)) and braces
    i = arrow + len(") -> (")
    depth = 1
    in_quote = False
    end = len(stablehlo)
    while i < len(stablehlo):
        c = stablehlo[i]
        if c == '"':
            in_quote = not in_quote
        elif not in_quote:
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        i += 1
    result_text = stablehlo[arrow + len(") -> ("):end]

    args: List[EntryArg] = []
    for m in _ARG_RE.finditer(arg_text):
        attrs = m.group(3)
        sh = re.search(r'mhlo\.sharding = "([^"]*)"', attrs)
        al = re.search(r"tf\.aliasing_output = (\d+)", attrs)
        args.append(
            EntryArg(
                index=int(m.group(1)),
                type_str=m.group(2),
                sharding=sh.group(1) if sh else None,
                aliases_output=int(al.group(1)) if al else None,
            )
        )
    # bare-typed args (no attr block) won't match _ARG_RE; they carry
    # neither sharding nor aliasing, which is exactly "nothing to check"

    results: List[EntryResult] = []
    for i, m in enumerate(_RESULT_RE.finditer(result_text)):
        attrs = m.group(2) or ""
        sh = re.search(r'mhlo\.sharding = "([^"]*)"', attrs)
        info = re.search(r'jax\.result_info = "([^"]*)"', attrs)
        results.append(
            EntryResult(
                index=i,
                type_str=m.group(1),
                sharding=sh.group(1) if sh else None,
                result_info=info.group(1) if info else "",
            )
        )
    return args, results


# ---------------------------------------------------------------------------
# the analysis context
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepProgram:
    """Everything shardcheck knows about one lowered step program.

    ``label`` names the program in findings (a pseudo-path, so the
    engine's Violation/report machinery can render them). Semantic
    hints (``seq_len``/``vocab``) gate SC003 — without them the rule
    stays silent rather than guessing."""

    label: str
    stablehlo: str = ""
    hlo: str = ""
    axis_sizes: Dict[str, int] = dataclasses.field(default_factory=dict)
    seq_len: Optional[int] = None
    vocab: Optional[int] = None
    world: int = 0
    config_hash: str = ""
    #: the step was built with zero-1 weight-update sharding: arms the
    #: SC002 replicated-optimizer-moment check (a moment the sharding
    #: rule left replicated across dp>1 defeats the feature's point)
    zero1: bool = False
    #: slices the device assignment spans (slice-major layout): >1
    #: arms the per-link (ici/dcn) census attribution — set for ANY
    #: multislice program, flat or hierarchical, so the census always
    #: shows what the slow link carries
    n_slices: int = 1
    #: the step was built with the latency-hiding overlap schedule
    #: (ops/hier_collectives.py overlap_value_and_grad): arms the
    #: SC006 exposed-vs-overlapped DCN-bytes contract dimension
    overlap: bool = False
    #: gradient-accumulation factor of the step — the overlap analysis
    #: weights in-scan DCN legs by the scan trip count so "exposed
    #: bytes per step" compares schedules honestly (hier-flat exposes
    #: its DCN leg once per MICROBATCH; overlap once per step)
    accum_steps: int = 1
    #: pipeline-schedule geometry hints when the program runs pp > 1:
    #: ``{"schedule": "1f1b"|"gpipe", "microbatches": m,
    #: "virtual_stages": v}`` — arms the SC008 bubble-fraction /
    #: stage-handoff contract dimension. None on non-pp programs (and
    #: on callers that lower without the hints: SC008 then checks the
    #: structural census only).
    pp_schedule: Optional[Dict] = None

    def coords(self) -> MeshCoords:
        return MeshCoords(self.axis_sizes, n_slices=self.n_slices)

    @property
    def data_axis_product(self) -> int:
        """Combined size of the batch-sharding axes (dp·fsdp·ep) — the
        ways a data-parallel tensor *could* be sharded."""
        n = 1
        for axis in ("dp", "fsdp", "ep"):
            n *= self.axis_sizes.get(axis, 1)
        return n

    def violation(
        self,
        rule: str,
        message: str,
        line: int = 1,
        snippet: str = "",
        severity: str = Severity.ERROR,
    ) -> Violation:
        return Violation(
            rule=rule,
            path=self.label,
            line=line,
            col=0,
            message=message,
            snippet=snippet[:160],
            severity=severity,
        )


# ---------------------------------------------------------------------------
# SC001 — collective census vs. contract
# ---------------------------------------------------------------------------


def check_census_against_contract(
    program: StepProgram,
    contract: Dict,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
    census: Optional[Dict[str, Dict[str, int]]] = None,
) -> List[Violation]:
    """Diff the program's census against a checked-in contract.

    Fails on: a collective cell (op × axes) the contract has never
    seen; count growth in an existing cell; byte growth beyond
    ``byte_tolerance``. Shrinkage passes but is reported as a stale
    note by the CLI (regenerate with ``--fix-contracts`` to bank the
    improvement) — mirroring the graftlint baseline workflow.

    ``census``: pass a precomputed census to skip re-parsing the HLO
    (the CLI computes it once for the check, summary and
    improvements note)."""
    out: List[Violation] = []
    if census is None:
        census = collective_census(program.hlo, program.coords())
    want: Dict[str, Dict[str, int]] = contract.get("census", {})
    if contract.get("config_hash") and program.config_hash and \
            contract["config_hash"] != program.config_hash:
        out.append(
            program.violation(
                "SC001",
                f"contract config_hash {contract['config_hash']} != "
                f"program {program.config_hash}: the contract was "
                "generated for a different model/trainer config — "
                "regenerate with --fix-contracts",
            )
        )
        return out
    for key in sorted(census):
        got = census[key]
        ref = want.get(key)
        if ref is None:
            out.append(
                program.violation(
                    "SC001",
                    f"new collective {key}: {got['count']} op(s), "
                    f"{got['bytes']} bytes — not in the contract. A new "
                    "collective on this axis means the partitioner now "
                    "moves data it did not before; justify and "
                    "--fix-contracts, or fix the sharding.",
                    snippet=key,
                )
            )
            continue
        if got["count"] > ref["count"]:
            out.append(
                program.violation(
                    "SC001",
                    f"collective {key} count grew {ref['count']} -> "
                    f"{got['count']}",
                    snippet=key,
                )
            )
        allowed = ref["bytes"] * (1.0 + byte_tolerance)
        if got["bytes"] > allowed and got["bytes"] > ref["bytes"]:
            out.append(
                program.violation(
                    "SC001",
                    f"collective {key} bytes grew {ref['bytes']} -> "
                    f"{got['bytes']} (> {byte_tolerance:.0%} tolerance)",
                    snippet=key,
                )
            )
        if contract.get("n_slices", 1) > 1:
            # the slow-link veto: a cell whose modeled DCN bytes grew
            # beyond tolerance moved traffic onto the inter-slice link
            # — the exact regression the hierarchical strategy exists
            # to prevent (a contract without slice info records no
            # dcn_bytes and skips this arm)
            ref_dcn = ref.get("dcn_bytes", 0)
            got_dcn = got.get("dcn_bytes", 0)
            if got_dcn > ref_dcn * (1.0 + byte_tolerance) and \
                    got_dcn > ref_dcn:
                out.append(
                    program.violation(
                        "SC001",
                        f"collective {key} DCN bytes grew {ref_dcn} -> "
                        f"{got_dcn}: the program moves more traffic "
                        "across the slice boundary than the contract "
                        "records — the slow link now carries what ICI "
                        "used to.",
                        snippet=key,
                    )
                )
    return out


def census_improvements(
    program_census: Dict[str, Dict[str, int]], contract: Dict
) -> List[str]:
    """Cells where the program now does LESS communication than the
    contract records (vanished, fewer ops, or fewer bytes)."""
    want: Dict[str, Dict[str, int]] = contract.get("census", {})
    notes = []
    for key in sorted(want):
        got = program_census.get(key)
        if got is None:
            notes.append(f"{key}: gone (contract has {want[key]['count']})")
        elif (
            got["count"] < want[key]["count"]
            or got["bytes"] < want[key]["bytes"]
        ):
            notes.append(
                f"{key}: {want[key]['count']}/{want[key]['bytes']}B -> "
                f"{got['count']}/{got['bytes']}B"
            )
        elif got.get("dcn_bytes", 0) < want[key].get("dcn_bytes", 0):
            notes.append(
                f"{key}: dcn {want[key]['dcn_bytes']}B -> "
                f"{got['dcn_bytes']}B (less on the slow link)"
            )
    return notes


# ---------------------------------------------------------------------------
# SC002 — replicated large tensor
# ---------------------------------------------------------------------------


def check_replicated_large(
    program: StepProgram,
    threshold_bytes: int = DEFAULT_REPLICATED_BYTES,
) -> List[Violation]:
    """An explicit ``@Sharding`` constraint that leaves a tensor above
    ``threshold_bytes`` fully replicated while the mesh has data axes
    to shard it over. Scope: constraint sites only — unannotated
    intermediates are XLA's placement choice and fire SC001 via the
    collectives they imply; entry params are the caller's placement
    (pure-dp legitimately replicates every parameter)."""
    out: List[Violation] = []
    if program.data_axis_product <= 1:
        return out
    for lineno, line in enumerate(program.stablehlo.splitlines(), start=1):
        m = _SHARDING_CONSTRAINT_RE.search(line)
        if not m:
            continue
        sharding = parse_sharding(m.group(1))
        nbytes = tensor_type_bytes(m.group(2))
        if nbytes < threshold_bytes:
            continue
        replicated = sharding.kind == "replicated" or (
            sharding.kind == "tiled"
            and sharding.replicate_ways >= program.data_axis_product
            and sharding.tile_count == 1
        )
        if replicated:
            out.append(
                program.violation(
                    "SC002",
                    f"sharding constraint pins tensor<{m.group(2)}> "
                    f"({nbytes} bytes) fully replicated "
                    f"({sharding.raw}) while the mesh has "
                    f"{program.data_axis_product} data-parallel ways to "
                    "shard it — every device holds the whole tensor.",
                    line=lineno,
                    snippet=line.strip(),
                )
            )
    return out


def check_replicated_moments(
    program: StepProgram,
    threshold_bytes: int = DEFAULT_REPLICATED_BYTES,
) -> List[Violation]:
    """SC002, zero-1 arm: a large OPTIMIZER-STATE leaf still replicated
    across dp while the step was built with weight-update sharding on.

    The moments are entry/results, not ``@Sharding`` sites, so the base
    rule never sees them; with zero-1 off their dp replication is the
    documented cost of pure-dp. With zero-1 ON it means the sharding
    rule fell back (non-divisible leading dims) on a leaf big enough
    that the fallback defeats the feature — resolve by reshaping the
    param or accepting it with a contract note. Detection reads the
    pinned output shardings of the ``[0]['opt']…`` results (the step's
    returned optimizer state): ``replicated``, or untiled with a
    replication factor covering the dp ways. Same precision limit as
    the base rule: the sharding string cannot attribute replication to
    a *specific* mesh axis, so a moment that is tiled over some other
    axis (sp/tp) yet still replicated across dp escapes — the
    conservative direction; the alternative misreads a correctly
    dp-sharded, sp-replicated moment as a fallback and (strict mode)
    vetoes a correct build."""
    out: List[Violation] = []
    dp = program.axis_sizes.get("dp", 1)
    if not program.zero1 or dp <= 1:
        return out
    _, results = parse_entry_signature(program.stablehlo)
    for res in results:
        if not res.result_info.startswith("[0]"):
            continue
        if "'opt'" not in res.result_info:
            continue
        nbytes = tensor_type_bytes(res.type_str)
        if nbytes < threshold_bytes:
            continue
        if res.sharding is None:
            continue  # unpinned outputs are SC004's finding
        sharding = parse_sharding(res.sharding)
        replicated = sharding.kind == "replicated" or (
            sharding.kind == "tiled"
            and sharding.tile_count == 1
            and sharding.replicate_ways >= dp
        )
        if replicated:
            out.append(
                program.violation(
                    "SC002",
                    f"zero-1 is on but optimizer moment "
                    f"{res.result_info} (tensor<{res.type_str}>, "
                    f"{nbytes} bytes) is replicated across dp={dp} "
                    f"({sharding.raw}): the weight-update sharding "
                    "rule fell back on this leaf — every dp rank "
                    "still holds the whole moment.",
                    snippet=f"{res.result_info}: {res.sharding}",
                )
            )
    return out


# ---------------------------------------------------------------------------
# SC003 — dense seq×vocab materialization
# ---------------------------------------------------------------------------


def check_dense_vocab(program: StepProgram) -> List[Violation]:
    """A float ``dot_general`` whose RESULT carries both the sequence
    dim and the FULL vocab dim — the dense-logits materialization
    chunked-CE exists to kill. Anchored on dot_general so the one-hot
    embedding lookup (a [B,T,V] *operand* contracted away in the same
    dot) and chunked CE (result carries chunk < vocab columns) stay
    clean. Needs the seq/vocab hints; silent without them."""
    out: List[Violation] = []
    seq, vocab = program.seq_len, program.vocab
    if not seq or not vocab or seq == vocab:
        # seq == vocab would make every attention score matrix look
        # like logits; a config that degenerate cannot be gated here
        return out
    for lineno, line in enumerate(program.stablehlo.splitlines(), start=1):
        m = _DOT_GENERAL_RE.search(line)
        if not m:
            continue
        dims, dtype = tensor_type_dims(m.group(1))
        if not dtype.startswith("f"):
            continue
        if seq in dims and vocab in dims:
            out.append(
                program.violation(
                    "SC003",
                    f"dot_general materializes tensor<{m.group(1)}> "
                    f"carrying both seq={seq} and vocab={vocab}: dense "
                    "logits are back (peak activation O(B*T*V) — use "
                    "the chunked CE path, ops/chunked_ce.py).",
                    line=lineno,
                    snippet=line.strip(),
                )
            )
    return out


# ---------------------------------------------------------------------------
# SC004 — output-sharding drift
# ---------------------------------------------------------------------------


def check_output_sharding_drift(program: StepProgram) -> List[Violation]:
    """The step donates its state and returns it as the first tuple
    element (``jax.result_info`` paths under ``[0]``); the next step
    feeds that output straight back in, so every state output's
    sharding must be PINNED and IDENTICAL to its input's. Three ways
    the lowering shows a violation:

    - the output carries no ``mhlo.sharding`` at all: out_shardings
      were not pinned, XLA is free to return the leaf re-sharded (the
      PR 2 silent-recompile bug — caught here at lower time instead of
      via AOT rejection at the first post-resize step);
    - the output is pinned but its donation alias is GONE: jax drops
      ``tf.aliasing_output`` exactly when the donated input's sharding
      cannot alias the output's — i.e. the pin differs from the input
      (jax also warns "Some donated buffers were not usable");
    - alias intact but the sharding strings differ (bitcast-compatible
      layouts can still alias).

    Skips programs with no ``[0]``-prefixed results (not a step)."""
    out: List[Violation] = []
    args, results = parse_entry_signature(program.stablehlo)
    state_results = [
        r for r in results if r.result_info.startswith("[0]")
    ]
    if not state_results:
        return out
    aliased_arg = {
        a.aliases_output: a for a in args if a.aliases_output is not None
    }
    for res in state_results:
        name = res.result_info
        if res.sharding is None:
            out.append(
                program.violation(
                    "SC004",
                    f"state leaf {name} has no pinned output sharding: "
                    "XLA is free to return it re-sharded, changing the "
                    "next step's input signature (silent recompile "
                    "under jit, hard reject under AOT). Pin "
                    "out_shardings to the input state's shardings.",
                    snippet=f"{name}: -> <unconstrained>",
                )
            )
            continue
        arg = aliased_arg.get(res.index)
        if arg is None:
            out.append(
                program.violation(
                    "SC004",
                    f"state leaf {name} is pinned to {res.sharding} but "
                    "lost its donation alias — the donated input's "
                    "sharding differs from this output pin, so step "
                    "N+1's input signature differs from step N's (and "
                    "the donation saves no memory).",
                    snippet=f"{name}: <donation dropped> -> "
                    f"{res.sharding}",
                )
            )
        elif arg.sharding is not None and arg.sharding != res.sharding:
            out.append(
                program.violation(
                    "SC004",
                    f"state leaf {name} changes sharding across the "
                    f"step: in {arg.sharding} -> out {res.sharding}.",
                    snippet=f"{name}: {arg.sharding} -> {res.sharding}",
                )
            )
    return out


# ---------------------------------------------------------------------------
# SC005 — host transfer inside the step
# ---------------------------------------------------------------------------


def check_host_transfer(program: StepProgram) -> List[Violation]:
    """Host callbacks / infeed / outfeed / host send-recv inside the
    jitted step: each one stalls every participating device on the
    host once per step (or once per scan iteration). Detected in the
    optimized HLO (the partitioner cannot remove them) with a
    StableHLO fallback for text generated before compile."""
    out: List[Violation] = []
    text = program.hlo or program.stablehlo
    for lineno, line in enumerate(text.splitlines(), start=1):
        hit = None
        tgt = re.search(
            r'custom_call_target="([^"]+)"', line
        ) or re.search(r'stablehlo\.custom_call @([\w.\-]+)', line)
        if tgt:
            target = tgt.group(1)
            if target in _BENIGN_CUSTOM_CALLS:
                continue
            if any(h in target.lower() for h in _DEVICE_KERNEL_HINTS):
                # a Pallas/Mosaic device kernel: the opposite of a host
                # transfer. Tracked by the SC007 census, never SC005.
                continue
            if any(h in target.lower() for h in _HOST_CALLBACK_HINTS):
                hit = f"host callback custom-call {target}"
        if hit is None:
            if re.search(r"\binfeed\(", line):
                hit = "infeed"
            elif re.search(r"\boutfeed\(", line):
                hit = "outfeed"
            elif re.search(
                r"\b(send|recv|send-done|recv-done)\(", line
            ) and "is_host_transfer=true" in line:
                hit = "host send/recv"
        if hit:
            out.append(
                program.violation(
                    "SC005",
                    f"{hit} inside the jitted step: the device blocks "
                    "on the host every step (debug callbacks and "
                    "io_callback do not belong in the hot path — hoist "
                    "them out or gate them off for training builds).",
                    line=lineno,
                    snippet=line.strip(),
                )
            )
    return out


# ---------------------------------------------------------------------------
# SC007 — custom-call census (the kernel contract)
# ---------------------------------------------------------------------------

_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_CC_SHAPE_RE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


def custom_call_census(hlo_text: str) -> Dict[str, Dict]:
    """Every non-benign custom-call in the lowered text, keyed by
    target: ``{target: {"count": n, "sites": ["(operands) -> result"]}}``
    with ``sites`` the sorted unique shape signatures.

    This is the kernel inventory of the step program. A Pallas kernel
    that stops lowering (dispatcher flag flipped, ``fused_ce_available``
    regressed, a jax upgrade changing the Mosaic target name) does not
    error — the model silently takes the reference path and only the
    step time notices. Diffing this census against the contract makes
    the fallback loud. Partitioner plumbing (``Sharding`` & co.) is
    excluded: it says nothing about kernels and churns with GSPMD
    internals."""
    census: Dict[str, Dict] = {}
    for line in hlo_text.splitlines():
        if "custom-call" not in line and "custom_call" not in line:
            continue
        m = _CC_TARGET_RE.search(line) or re.search(
            r"stablehlo\.custom_call @([\w.\-]+)", line
        )
        if m is None:
            continue
        target = m.group(1)
        if target in _BENIGN_CUSTOM_CALLS:
            continue
        head, sep, tail = line.partition("custom-call(")
        operands = _CC_SHAPE_RE.findall(tail.split(")", 1)[0]) if sep \
            else []
        results = _CC_SHAPE_RE.findall(head) if sep else []
        res = results[0] if len(results) == 1 else \
            "(" + ", ".join(results) + ")"
        sig = f"({', '.join(operands)}) -> {res}"
        entry = census.setdefault(target, {"count": 0, "sites": []})
        entry["count"] += 1
        if sig not in entry["sites"]:
            entry["sites"].append(sig)
    for entry in census.values():
        entry["sites"].sort()
    return census


def check_custom_calls_against_contract(
    program: StepProgram,
    contract: Dict,
    census: Optional[Dict[str, Dict]] = None,
) -> List[Violation]:
    """Diff the program's custom-call census against the contract's
    recorded ``custom_calls`` section.

    Fails on: a contracted kernel target missing from the program (the
    silent-fallback case — the kernel stopped lowering and nobody
    noticed); a target the contract has never seen (an un-contracted
    kernel entered the step); count or operand/result-shape drift in an
    existing target. Contracts written before SC007 have no
    ``custom_calls`` section and skip the rule — regenerate with
    ``--fix-contracts`` to arm it."""
    want = contract.get("custom_calls")
    if want is None:
        return []
    if contract.get("config_hash") and program.config_hash and \
            contract["config_hash"] != program.config_hash:
        return []  # SC001 already reports the hash mismatch
    if census is None:
        census = custom_call_census(program.hlo)
    out: List[Violation] = []
    for target in sorted(want):
        if target not in census:
            out.append(
                program.violation(
                    "SC007",
                    f"contracted kernel {target} vanished from the "
                    f"lowered step ({want[target]['count']} call(s) in "
                    "the contract): the program silently fell back to "
                    "the reference path — check the dispatcher flags "
                    "and kernel availability, or --fix-contracts if "
                    "the removal is deliberate.",
                    snippet=target,
                )
            )
    for target in sorted(census):
        got = census[target]
        ref = want.get(target)
        if ref is None:
            out.append(
                program.violation(
                    "SC007",
                    f"new custom-call kernel {target}: {got['count']} "
                    "call(s) not in the contract — contract every "
                    "kernel the step runs (review, then "
                    "--fix-contracts).",
                    snippet=target,
                )
            )
            continue
        if got["count"] != ref["count"] or \
                got["sites"] != ref.get("sites", []):
            out.append(
                program.violation(
                    "SC007",
                    f"kernel {target} drifted from the contract: "
                    f"count {ref['count']} -> {got['count']}, sites "
                    f"{ref.get('sites', [])} -> {got['sites']}.",
                    snippet=target,
                )
            )
    return out


# ---------------------------------------------------------------------------
# one-call entry: run all SC rules on a program
# ---------------------------------------------------------------------------


def check_program(
    program: StepProgram,
    contract: Optional[Dict] = None,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
    replicated_threshold: int = DEFAULT_REPLICATED_BYTES,
    census: Optional[Dict[str, Dict[str, int]]] = None,
) -> List[Violation]:
    """SC002–SC005 always; SC001/SC006 only when a contract is
    supplied (there is nothing to diff against otherwise)."""
    out: List[Violation] = []
    if contract is not None and program.hlo:
        out.extend(
            check_census_against_contract(
                program, contract, byte_tolerance, census=census
            )
        )
        out.extend(
            check_overlap_against_contract(
                program, contract, byte_tolerance
            )
        )
        out.extend(check_custom_calls_against_contract(program, contract))
        out.extend(check_pp_schedule_against_contract(program, contract))
    if program.stablehlo:
        out.extend(check_replicated_large(program, replicated_threshold))
        out.extend(check_replicated_moments(program, replicated_threshold))
        out.extend(check_dense_vocab(program))
        out.extend(check_output_sharding_drift(program))
    out.extend(check_host_transfer(program))
    out.sort(key=lambda v: (v.rule, v.line))
    return out


# ---------------------------------------------------------------------------
# contracts on disk
# ---------------------------------------------------------------------------


def contract_path(contracts_dir: str, mesh_spec: str) -> str:
    return os.path.join(contracts_dir, f"{mesh_spec}.json")


def load_contract(contracts_dir: str, mesh_spec: str) -> Optional[Dict]:
    try:
        with open(contract_path(contracts_dir, mesh_spec),
                  encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return None
    if not isinstance(data, dict) or "census" not in data:
        raise ValueError(
            f"{contract_path(contracts_dir, mesh_spec)}: not a shardcheck "
            "contract file"
        )
    return data


def write_contract(
    contracts_dir: str,
    mesh_spec: str,
    program: StepProgram,
    extra: Optional[Dict] = None,
) -> Dict:
    os.makedirs(contracts_dir, exist_ok=True)
    census = collective_census(program.hlo, program.coords())
    data = {
        "comment": (
            "shardcheck SC001 contract: the collective census of the "
            "lowered step program for this mesh. Regenerate with: "
            "python -m dlrover_tpu.lint --hlo <spec> --fix-contracts"
        ),
        "version": 1,
        "mesh_spec": mesh_spec,
        "axis_sizes": {
            a: s for a, s in program.axis_sizes.items() if s > 1
        },
        "world": program.world,
        "config_hash": program.config_hash,
        "census": {k: census[k] for k in sorted(census)},
        # SC007: the kernel inventory. Empty on CPU-lowered contracts
        # (no Pallas custom-calls off-TPU) — still armed: a kernel
        # APPEARING un-contracted fails just like one vanishing.
        "custom_calls": custom_call_census(program.hlo),
    }
    if program.n_slices > 1:
        # arms the per-cell dcn_bytes diff (the slow-link veto) and
        # records what the census unit means for this contract
        data["n_slices"] = program.n_slices
        data["dcn_bytes_total"] = census_dcn_bytes(census)
        # arms SC006: the exposed/overlapped split of those DCN bytes.
        # Recorded for EVERY multislice contract — a flat or fused-hier
        # program banks ratio 0.0 with its exposure baseline, so even
        # without the overlap schedule a change that inflates the
        # stalled bytes fails the contract.
        report = overlap_report(program.hlo, program.coords())
        data["overlap"] = {
            k: report[k]
            for k in (
                "dcn_exposed_bytes", "dcn_overlapped_bytes",
                "overlap_ratio",
            )
        }
    # arms SC008: the pipeline-schedule fingerprint (bubble fraction
    # of the declared geometry + static stage-handoff pattern).
    # Recorded for every pp > 1 contract.
    pp_report = pp_schedule_report(program)
    if pp_report is not None:
        data["pp_schedule"] = pp_report
    if extra:
        data.update(extra)
    path = contract_path(contracts_dir, mesh_spec)
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


# ---------------------------------------------------------------------------
# SC rule catalog (for --list-rules and the docs)
# ---------------------------------------------------------------------------

SC_RULES: List[Tuple[str, str, str]] = [
    ("SC001", "collective-census",
     "Collectives per mesh axis (and, on multislice assignments, per "
     "ici/dcn link class) diffed against a checked-in contract."),
    ("SC002", "replicated-large-tensor",
     "A big sharding-constrained tensor left fully replicated across "
     "the data axes; under zero-1, also an optimizer moment still "
     "replicated across dp."),
    ("SC003", "dense-vocab-materialization",
     "A float dot_general result carrying both seq and full-vocab dims "
     "(dense logits; chunked-CE regression gate)."),
    ("SC004", "output-sharding-drift",
     "A donated state leaf whose output sharding is unpinned or differs "
     "from its input sharding."),
    ("SC005", "host-transfer-in-jit",
     "Host callback / infeed / outfeed inside the jitted step."),
    ("SC006", "exposed-dcn-bytes",
     "Trip-weighted exposed vs. overlapped DCN bytes diffed against "
     "the contract's recorded split — vetoes a change that "
     "re-serializes slice-boundary transfers the schedule used to "
     "hide behind compute."),
    ("SC007", "custom-call-census",
     "Every non-benign custom-call (Pallas/Mosaic kernel) in the "
     "lowered step, with operand/result shapes, diffed against the "
     "contract — a contracted kernel vanishing is a silent fallback "
     "to the reference path; a new one is un-reviewed."),
    ("SC008", "pp-schedule-bubble",
     "Pipeline-schedule fingerprint (analytic steady-state bubble "
     "fraction of the declared geometry + static collective-permute|pp "
     "stage-handoff count) diffed against the contract — vetoes a "
     "change that re-serializes the interleaved 1F1B schedule."),
]
