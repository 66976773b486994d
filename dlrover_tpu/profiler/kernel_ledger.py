"""Per-kernel step-time attribution — the xpu_timer capability, TPU-native.

DLRover's xpu_timer hooks device kernel launches so a slow step names the
kernel, not the step. The XLA/TPU analogue cannot interpose launches, but
it does not need to: the compiled step's optimized HLO names every fusion,
``custom_call`` (Pallas kernels arrive as ``tpu_custom_call`` with a
Mosaic payload) and collective, with operand/result shapes inline. This
module turns one compiled executable + one measured step time into a
per-kernel breakdown:

1. **walk** the optimized HLO (``compiled.as_text()``) instruction by
   instruction, estimating a cost weight per site from a two-knob
   roofline — ``max(flops / peak_flops, bytes / peak_bw)`` (dots carry
   real contracted-dim flops; everything else is memory-bound on its
   operand+result bytes);
2. **classify** each site onto a census-named operator (attention
   fwd/bwd, fused/chunked CE, DCN buckets, optimizer, matmul, comm.*)
   from its ``metadata op_name`` path and custom-call target;
3. **attribute**: normalize the weights and scale by the *measured* step
   seconds (:func:`measure_step`'s sampled re-execution) — shares
   always sum to 1.0 across the whole program, so a top-k cut covering
   >=80 % of the step always exists.

The result lands in the :class:`KernelLedger` singleton
(``dlrover_tpu_kernel_seconds_total{op=...}`` on /metrics). It is
never laid on a timeline: measured device operations are in the
profiler's trace, and modelled durations beside them could only mislead.

The weights are a *model*, not a measurement — the point is stable,
named blame ("attention.bwd got 2x slower") rather than nanosecond
truth; the measured step seconds anchor the absolute scale.
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

#: roofline knobs (v5e-ish): only their RATIO matters for shares —
#: flops-dense sites (dots) are scored against peak MXU throughput,
#: everything else against HBM bandwidth.
PEAK_FLOPS = 2.0e14
PEAK_BW_BYTES = 8.0e11

_COLLECTIVES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-gather-start", "reduce-scatter-start",
    "collective-permute-start",
})

#: opcodes that move no data worth attributing
_FREE_OPCODES = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    "all-reduce-done", "all-gather-done", "reduce-scatter-done",
    "collective-permute-done", "copy-done", "copy-start",
})

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(\(?[^=]*?)\s([\w\-]+)\("
)
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_bytes(text: str) -> float:
    """Sum the byte sizes of every ``dtype[dims]`` shape in ``text``."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        total += elems * size
    return total


def _first_shape_elems(
    text: str, dims_wanted: Sequence[int]
) -> Optional[float]:
    """Product of the selected dims of the FIRST shape in ``text``, or
    ``None`` when no shape parses at all. A zero-sized dim yields a
    real 0.0 — distinct from the no-shape case, so degenerate operands
    (``f32[0,...]`` slices, 0-dim tensors from scalar psums) score
    zero work instead of borrowing the scalar fallback."""
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d]
    out = 1.0
    for i in dims_wanted:
        if 0 <= i < len(dims):
            out *= dims[i]
    return out


@dataclass
class KernelSite:
    """One attributable HLO instruction."""

    opcode: str
    op: str            # census-named operator (classify_site)
    flops: float
    bytes: float
    name: str = ""     # metadata op_name tail, for debugging

    @property
    def cost(self) -> float:
        """Roofline weight. Zero-sized operands (scalar psums'
        ``f32[]`` carry their 4 bytes; degenerate ``[0,...]`` slices
        carry nothing) legitimately score 0.0 — attribute_step's
        total-cost guard turns an all-zero program into all-zero
        shares instead of dividing by the zero."""
        flop_score = self.flops / PEAK_FLOPS if PEAK_FLOPS > 0 else 0.0
        byte_score = (
            self.bytes / PEAK_BW_BYTES if PEAK_BW_BYTES > 0 else 0.0
        )
        return max(flop_score, byte_score)


def classify_site(opcode: str, target: str, op_name: str) -> str:
    """Map one HLO site onto the census operator vocabulary. Pallas
    custom-calls classify by the jax source path in their metadata
    (``flash`` -> attention, ``fused_ce``/``chunked`` -> ce), falling
    back to ``pallas.<target>`` — never to a host-transfer bucket."""
    s = (op_name or "").lower()
    t = (target or "").lower()
    if opcode in _COLLECTIVES:
        if "pp_send_recv" in s:
            # pp stage handoff (ppermute under the pp executors' scope):
            # its own census row instead of folding into comm.collective-
            # permute, so /metrics can see pipeline comm
            return "comm.pp_send_recv"
        if "dcn" in s or "bucket" in s or "hier" in s:
            return "comm.dcn_bucket"
        return f"comm.{opcode.replace('-start', '')}"
    fam = _kernel_family(s)
    if opcode == "custom-call":
        if "tpu_custom_call" in t or "mosaic" in t:
            return fam or "pallas"
        return f"custom_call.{target or 'unknown'}"
    if fam:
        return fam
    if opcode in ("dot", "convolution"):
        return "matmul"
    return "other"


def _kernel_family(s: str) -> Optional[str]:
    """Family from the op_name scope path. The ops plant
    ``jax.named_scope`` markers at their custom_vjp fwd/bwd boundaries
    (attention_fwd/bwd, fused_ce_*/chunked_ce_*, optimizer_update; the
    pp executors add stage_fwd/stage_bwd + pp_send_recv), so
    every primitive they trace — Pallas custom-call or reference-path
    dot — carries its operator in the metadata; the attention einsum
    specs are the fallback for unscoped reference code."""
    bwd = "transpose(" in s or "_bwd" in s or "backward" in s
    if "attention_fwd" in s:
        return "attention.fwd"
    if "attention_bwd" in s:
        return "attention.bwd"
    if "fused_ce_fwd" in s or "chunked_ce_fwd" in s:
        return "ce.fwd"
    if "fused_ce_bwd" in s or "chunked_ce_bwd" in s:
        return "ce.bwd"
    if "optimizer_update" in s or "adam" in s:
        return "optimizer"
    if "flash" in s or "attention" in s or "bqhd,bkhd" in s \
            or "bhqk,bkhd" in s:
        return "attention.bwd" if bwd else "attention.fwd"
    if ("fused_ce" in s or "chunked_ce" in s or "cross_entropy" in s
            or "lm_head" in s or "unembed" in s):
        return "ce.bwd" if bwd else "ce.fwd"
    # pp stage slabs: anything inside the executors' stage scopes that a
    # more specific family above didn't claim (attention/ce markers win
    # because they are checked first). gpipe's backward is the AD
    # transpose of the fwd scope -> transpose(stage_fwd) counts as bwd.
    if "stage_bwd" in s:
        return "stage.bwd"
    if "stage_fwd" in s:
        return "stage.bwd" if bwd else "stage.fwd"
    return None


def iter_sites(hlo_text: str):
    """Yield a :class:`KernelSite` per attributable instruction of the
    optimized HLO. Fusion-body computations contribute only their
    flops-bearing dots/convs (their data movement is already counted on
    the calling ``fusion`` instruction)."""
    in_fused_body = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and (
            stripped.startswith("%") or stripped.startswith("ENTRY")
        ):
            # computation header: "%name (params) -> result {" or
            # "ENTRY %name (params) -> result {" — only the header's own
            # name decides fused-body mode ("fused_computation" also
            # appears in instruction-level calls= operands)
            in_fused_body = "fused_computation" in stripped.split("(", 1)[0]
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        result_type, opcode = m.group(1), m.group(2)
        if opcode in _FREE_OPCODES:
            continue
        if in_fused_body and opcode not in ("dot", "convolution"):
            continue
        args = line[m.end():]
        op_name_m = _OPNAME_RE.search(line)
        op_name = op_name_m.group(1) if op_name_m else ""
        target_m = _TARGET_RE.search(line)
        target = target_m.group(1) if target_m else ""
        flops = 0.0
        if opcode == "dot":
            out_elems = _first_shape_elems(result_type, range(8))
            cdims_m = _LHS_CDIMS_RE.search(args)
            cdims = (
                [int(d) for d in cdims_m.group(1).split(",") if d]
                if cdims_m else []
            )
            contract = _first_shape_elems(args, cdims)
            # None = shape didn't parse (scalar fallback to 1); a real
            # 0.0 from a zero-sized operand stays 0 — zero work
            flops = (
                2.0
                * (1.0 if out_elems is None else out_elems)
                * (1.0 if contract is None else contract)
            )
        nbytes = _shape_bytes(result_type) + _shape_bytes(
            args.split(", metadata=")[0].split(", calls=")[0]
        )
        yield KernelSite(
            opcode=opcode,
            op=classify_site(opcode, target, op_name),
            flops=flops,
            bytes=nbytes,
            name=op_name.rsplit("/", 1)[-1] if op_name else opcode,
        )


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def attribute_step(
    compiled, step_s: float, hlo_text: Optional[str] = None
) -> List[Dict]:
    """The breakdown: census-named operator rows
    ``{"op", "seconds", "share", "flops", "bytes", "sites"}`` sorted by
    seconds descending, shares summing to 1.0 (the residual of
    unclassifiable sites lands on ``"other"``). ``step_s`` is the
    measured wall seconds of one step — the model distributes it, it
    never invents it."""
    if hlo_text is None:
        hlo_text = compiled.as_text()
    groups: Dict[str, Dict] = {}
    total_cost = 0.0
    for site in iter_sites(hlo_text):
        g = groups.setdefault(
            site.op,
            {"op": site.op, "flops": 0.0, "bytes": 0.0, "sites": 0,
             "_cost": 0.0},
        )
        g["flops"] += site.flops
        g["bytes"] += site.bytes
        g["sites"] += 1
        g["_cost"] += site.cost
        total_cost += site.cost
    step_s = max(0.0, float(step_s))
    rows = []
    for g in groups.values():
        share = g.pop("_cost") / total_cost if total_cost > 0 else 0.0
        g["share"] = round(share, 6)
        g["seconds"] = round(share * step_s, 9)
        rows.append(g)
    rows.sort(key=lambda r: (-r["seconds"], r["op"]))
    return rows


def top_k(rows: List[Dict], min_share: float = 0.8,
          max_k: int = 8) -> List[Dict]:
    """Smallest prefix of the (sorted) breakdown covering
    ``min_share`` of the step, capped at ``max_k`` rows with the tail
    folded into an ``"other"`` row so the cut is loud, not silent."""
    out: List[Dict] = []
    covered = 0.0
    for row in rows:
        if covered >= min_share or len(out) >= max_k:
            break
        out.append(dict(row))
        covered += row["share"]
    tail = [r for r in rows[len(out):]]
    if tail:
        out.append({
            "op": "other",
            "share": round(sum(r["share"] for r in tail), 6),
            "seconds": round(sum(r["seconds"] for r in tail), 9),
            "flops": sum(r["flops"] for r in tail),
            "bytes": sum(r["bytes"] for r in tail),
            "sites": sum(r["sites"] for r in tail),
            "tail": True,
        })
    return out


def measure_step(run_fn, n: int = 3) -> float:
    """Sampled re-execution: median wall seconds of ``run_fn()`` over
    ``n`` runs (callers pass a closure that executes the compiled step
    and blocks on the result)."""
    times = []
    for _ in range(max(1, int(n))):
        t0 = time.perf_counter()
        run_fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# ---------------------------------------------------------------------------
# ledger singleton + metrics emission
# ---------------------------------------------------------------------------


@dataclass
class _OpTotals:
    seconds: float = 0.0
    steps: int = 0
    last_share: float = 0.0


class KernelLedger:
    """Cumulative per-operator attributed seconds (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ops: Dict[str, _OpTotals] = {}
        self._last_breakdown: List[Dict] = []

    def record_breakdown(self, rows: List[Dict]) -> None:
        with self._lock:
            self._last_breakdown = [dict(r) for r in rows]
            for r in rows:
                t = self._ops.setdefault(r["op"], _OpTotals())
                t.seconds += float(r.get("seconds", 0.0))
                t.steps += 1
                t.last_share = float(r.get("share", 0.0))

    def last_breakdown(self) -> List[Dict]:
        with self._lock:
            return [dict(r) for r in self._last_breakdown]

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {op: t.seconds for op, t in self._ops.items()}

    def clear(self) -> None:
        with self._lock:
            self._ops.clear()
            self._last_breakdown = []

    def prometheus_lines(self) -> List[str]:
        with self._lock:
            if not self._ops:
                return []
            lines = ["# TYPE dlrover_tpu_kernel_seconds_total gauge"]
            for op in sorted(self._ops):
                lines.append(
                    f'dlrover_tpu_kernel_seconds_total{{op="{op}"}} '
                    f"{self._ops[op].seconds:.9f}"
                )
            lines.append("# TYPE dlrover_tpu_kernel_share gauge")
            for op in sorted(self._ops):
                lines.append(
                    f'dlrover_tpu_kernel_share{{op="{op}"}} '
                    f"{self._ops[op].last_share:.6f}"
                )
            return lines


kernel_ledger = KernelLedger()


def prometheus_lines() -> List[str]:
    return kernel_ledger.prometheus_lines()


def capture_step(
    compiled,
    step_s: float,
    *,
    hlo_text: Optional[str] = None,
) -> List[Dict]:
    """The one-call on-demand capture: attribute ``step_s`` across the
    compiled program's kernel sites and record into the ledger
    (/metrics). Returns the full breakdown (use :func:`top_k` for
    display cuts)."""
    rows = attribute_step(compiled, step_s, hlo_text=hlo_text)
    kernel_ledger.record_breakdown(rows)
    return rows
