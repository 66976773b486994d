"""memcheck: static per-device memory contracts (MC rules) — the fifth
invariant layer.

shardcheck proves the lowered step moves the right *bytes over links*;
memcheck proves it fits in the right *bytes of HBM*. The one resize
failure mode no earlier layer could catch before it happens is a
grow/shrink into an OOM world: the goodput planner scores candidates
from *measured* headroom, which only exists for worlds that have
already run. This module makes "this world fits" a static, checked-in
contract, the same shape SC001 gave collective bytes:

- **measured side**: ``compiled.memory_analysis()`` on the warm-compile
  avatar build (argument / output / temp / generated-code / alias
  bytes) — the per-device arena XLA actually plans, obtainable for any
  admissible world on CPU with no TPU attached;
- **analytic side**: a per-leaf model over the state/batch avatars'
  ``(shape, dtype, PartitionSpec)`` — each leaf's global bytes divided
  by the product of the mesh axes its spec shards over, bucketed into
  the five components ``params / moments / grads_accum / activations /
  temp``. The analytic side makes the measured number *explainable*
  (which component grew, and why), and scales to worlds that were
  never compiled at all — that scaling law is the planner's
  :class:`HeadroomOracle`.

Rules:

MC001  memory-contract: per-device peak bytes and the per-component
       breakdown diffed against a checked-in per-(mesh-spec,
       config-hash) contract (``lint/contracts/mem-<spec>.json``) with
       a byte tolerance; growth past tolerance names the component.
MC002  headroom-budget: predicted per-device peak vs. a per-device-
       class HBM budget (``v5e`` / ``v5p`` / ``cpu-host`` — the
       ROADMAP item 5 vocabulary) minus a headroom fraction. The same
       check, applied to a candidate ``WorldDescriptor`` through the
       oracle, is the planner's ``oom_veto``.

Everything here is arithmetic over plain shapes and dicts — no jax
import, no device use — so the module stays importable in the dep-free
lint environment and master-side in the planner process. Compiling a
program to GET the measured bytes (CLI ``--mem``, trainer hook) is the
caller's job, and every ``memory_analysis()`` read goes through the
guarded :func:`read_memory_analysis` (backends return ``None`` or
partial objects; older jaxlib CPU has no generated-code bytes — degrade
with one warning, never ``AttributeError``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.common.log import logger
from dlrover_tpu.common.world import WorldDescriptor
from dlrover_tpu.lint.engine import Severity, Violation

#: contracts live next to the SC001 ones (``--fix-contracts`` rewrites);
#: ``mem-`` prefix keeps the two families from colliding on a spec name
DEFAULT_CONTRACTS_DIR = os.path.join(os.path.dirname(__file__), "contracts")

#: MC001 default: per-component (and peak) byte growth beyond this
#: fraction of the contract fails lint
DEFAULT_BYTE_TOLERANCE = 0.10

#: MC001: growth below this many bytes never fails, whatever the
#: fraction — keeps KB-sized components (scalars, step counters) from
#: flapping the gate on dtype-width noise
MIN_GROWTH_BYTES = 64 << 10

#: MC002 default headroom: a candidate must fit in budget * (1 - this)
DEFAULT_HEADROOM_FRAC = 0.10

#: per-device-class HBM capacities, bytes (ROADMAP item 5 vocabulary).
#: cpu-host is deliberately small: it bounds the CPU-lowered CI builds
#: and gives the fleet harness an OOM-able class without a TPU.
DEVICE_HBM_BYTES: Dict[str, int] = {
    "v5e": 16 * 10**9,
    "v5p": 95 * 10**9,
    "cpu-host": 4 * 10**9,
}

#: the component vocabulary, in reporting order
COMPONENTS = ("params", "moments", "grads_accum", "activations", "temp")

#: numpy dtype name -> bytes (plain names: avatars hand us strings so
#: this module never imports numpy/jax)
_DTYPE_BYTES = {
    "bool": 1, "int8": 1, "uint8": 1, "float8_e4m3fn": 1,
    "float8_e5m2": 1,
    "int16": 2, "uint16": 2, "float16": 2, "bfloat16": 2,
    "int32": 4, "uint32": 4, "float32": 4,
    "int64": 8, "uint64": 8, "float64": 8, "complex64": 8,
    "complex128": 16,
}


class MemcheckError(RuntimeError):
    """Raised by the strict lower-time hook (``DLROVER_TPU_MEMCHECK=2``)
    when the compiled step program violates an MC rule."""

    def __init__(self, violations: Sequence[Violation]):
        self.violations = list(violations)
        super().__init__(
            f"{len(self.violations)} memcheck violation(s):\n"
            + "\n".join(v.format() for v in self.violations)
        )


def _violation(rule: str, label: str, message: str) -> Violation:
    return Violation(
        rule=rule,
        path=label or "memcheck",
        line=0,
        col=0,
        message=message,
        snippet="",
        severity=Severity.ERROR,
    )


# ---------------------------------------------------------------------------
# satellite 1: the ONE guarded reader over memory_analysis()
# ---------------------------------------------------------------------------

#: (attr on the backend object, key we publish) — `*_bytes` names so the
#: dict is self-describing in contracts
_MEMORY_FIELDS: Tuple[Tuple[str, str], ...] = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)

#: warn-once registry: one line per (label, field) per process, then
#: silent degradation — a CI log should say a backend is partial once,
#: not once per lowering
_WARNED: set = set()


def _warn_once(key: str, message: str) -> None:
    if key in _WARNED:
        return
    _WARNED.add(key)
    logger.warning("memcheck: %s", message)


def read_memory_analysis(compiled, label: str = "step") -> Dict[str, int]:
    """The sanctioned reader over ``compiled.memory_analysis()``.

    Backends are allowed to return ``None``, raise, or hand back an
    object missing fields (older jaxlib CPU reports no generated-code
    bytes); every call site that used to spell the five ``getattr``\\ s
    itself goes through here instead. Missing pieces degrade to absent
    keys with one warning per (label, field); an empty dict means
    nothing was measurable. When at least the argument/temp side is
    present a ``peak_bytes`` estimate is added: arguments + outputs +
    temp + generated code − aliased bytes (donated inputs whose buffer
    the output reuses would otherwise be counted twice). Where the
    backend also states the peak of its own heap simulation
    (``peak_memory_in_bytes``; absent on older jaxlib, and then simply
    absent here), ``planned_peak_bytes`` is that peak + generated code:
    what the compiler holds against the device's memory. The sum above
    can overcount it (a step whose temporaries reuse donated arguments:
    18.4 GB summed for 15.5 GB planned, PERF.md section 7, PR 45).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception as exc:  # backend quirk, never a caller crash
        _warn_once(f"{label}:call",
                   f"memory_analysis() unavailable ({label}): {exc}")
        return {}
    if ma is None:
        _warn_once(f"{label}:none",
                   f"memory_analysis() returned None ({label})")
        return {}
    out: Dict[str, int] = {}
    for attr, key in _MEMORY_FIELDS:
        value = getattr(ma, attr, None)
        if value is None:
            _warn_once(f"{label}:{attr}",
                       f"memory_analysis().{attr} missing ({label}); "
                       "degrading")
            continue
        try:
            out[key] = int(value)
        except (TypeError, ValueError):
            _warn_once(f"{label}:{attr}",
                       f"memory_analysis().{attr} non-numeric ({label}); "
                       "degrading")
    if out:
        out["peak_bytes"] = measured_peak_bytes(out)
        planned = getattr(ma, "peak_memory_in_bytes", None)
        if isinstance(planned, int) and planned > 0:
            out["planned_peak_bytes"] = planned + out.get(
                "generated_code_bytes", 0)
    return out


def measured_peak_bytes(measured: Dict[str, int]) -> int:
    """Per-device peak from the measured fields (missing fields count
    zero — the estimate degrades monotonically with the backend)."""
    return max(
        0,
        measured.get("argument_bytes", 0)
        + measured.get("output_bytes", 0)
        + measured.get("temp_bytes", 0)
        + measured.get("generated_code_bytes", 0)
        - measured.get("alias_bytes", 0),
    )


# ---------------------------------------------------------------------------
# the analytic per-leaf model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafAvatar:
    """One avatar leaf, reduced to what the memory model needs — plain
    strings and ints so trainers can flatten jax pytrees into these and
    this module never touches jax itself.

    ``sharded_axes`` is the flattened mesh-axis content of the leaf's
    ``PartitionSpec`` (``P(("fsdp", "tp"), None)`` -> ``("fsdp",
    "tp")``): the axes this leaf's bytes divide across.
    """

    path: str
    shape: Tuple[int, ...]
    dtype: str
    sharded_axes: Tuple[str, ...] = ()

    def global_bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * dtype_bytes(self.dtype)

    def per_device_bytes(self, axis_sizes: Dict[str, int]) -> float:
        div = 1
        for axis in self.sharded_axes:
            div *= max(1, int(axis_sizes.get(axis, 1)))
        return self.global_bytes() / div


def dtype_bytes(name: str) -> int:
    name = str(name)
    if name in _DTYPE_BYTES:
        return _DTYPE_BYTES[name]
    # "float8_e4m3fnuz"-style strangers: trailing digit run before any
    # suffix is the bit width
    digits = "".join(c for c in name if c.isdigit())
    if digits:
        return max(1, int(digits[:3]) // 8 or 1)
    return 4


def classify_leaf(path: str) -> str:
    """Component bucket for a state-avatar leaf, by pytree path. The
    train state is ``{"params": ..., "opt": ..., step, lr_scale}``;
    anything that is not a parameter is optimizer-side state."""
    p = path.lower()
    if "params" in p:
        return "params"
    return "moments"


def analytic_components(
    state_leaves: Sequence[LeafAvatar],
    batch_leaves: Sequence[LeafAvatar],
    axis_sizes: Dict[str, int],
    measured: Optional[Dict[str, int]] = None,
) -> Dict[str, int]:
    """The explainable per-device breakdown, bytes per component.

    - ``params`` / ``moments``: state leaves at their avatar sharding;
    - ``grads_accum``: the gradient (accumulator) buffer — shaped and
      sharded exactly like the params, so it *is* the params' per-device
      bytes again;
    - ``activations``: the batch leaves at their avatar sharding (the
      live input tensors; intermediate activations land in temp);
    - ``temp``: the measured temp arena plus generated code, with the
      modeled grad accumulator (which XLA plans inside that arena)
      taken back out, clamped at zero — the honest "scratch the model
      cannot explain" remainder. Zero when nothing was measured.

    With all five summed the analytic peak tracks the measured one up
    to the donation residue (outputs − aliased bytes): arguments are
    params + moments + activations, and grads + temp reassemble the
    measured arena — that near-identity is the parity the tests assert.
    """
    params = 0.0
    moments = 0.0
    for leaf in state_leaves:
        if classify_leaf(leaf.path) == "params":
            params += leaf.per_device_bytes(axis_sizes)
        else:
            moments += leaf.per_device_bytes(axis_sizes)
    grads = params
    acts = sum(l.per_device_bytes(axis_sizes) for l in batch_leaves)
    temp = 0.0
    if measured and (measured.get("temp_bytes")
                     or measured.get("generated_code_bytes")):
        temp = max(
            0.0,
            measured.get("temp_bytes", 0)
            + measured.get("generated_code_bytes", 0)
            - grads,
        )
    return {
        "params": int(params),
        "moments": int(moments),
        "grads_accum": int(grads),
        "activations": int(acts),
        "temp": int(temp),
    }


def analytic_peak_bytes(components: Dict[str, int]) -> int:
    return int(sum(components.get(c, 0) for c in COMPONENTS))


def explain_delta_frac(
    components: Dict[str, int], measured: Dict[str, int]
) -> Optional[float]:
    """How far the analytic state+batch model sits from the measured
    argument bytes — the cross-check that makes the quoted number
    explainable. ``None`` when the backend measured nothing."""
    arg = measured.get("argument_bytes")
    if not arg:
        return None
    modeled = (
        components.get("params", 0)
        + components.get("moments", 0)
        + components.get("activations", 0)
    )
    return abs(modeled - arg) / arg


# ---------------------------------------------------------------------------
# MC001: the contract diff
# ---------------------------------------------------------------------------


def mem_contract_path(contracts_dir: str, mesh_spec: str) -> str:
    return os.path.join(contracts_dir, f"mem-{mesh_spec}.json")


def load_mem_contract(
    contracts_dir: str, mesh_spec: str
) -> Optional[Dict]:
    try:
        with open(mem_contract_path(contracts_dir, mesh_spec),
                  encoding="utf-8") as f:
            data = json.load(f)
    except FileNotFoundError:
        return None
    if not isinstance(data, dict) or "components" not in data:
        raise ValueError(
            f"{mem_contract_path(contracts_dir, mesh_spec)}: not a "
            "memcheck contract file"
        )
    return data


def write_mem_contract(
    contracts_dir: str,
    mesh_spec: str,
    components: Dict[str, int],
    peak_bytes: int,
    measured: Optional[Dict[str, int]] = None,
    extra: Optional[Dict] = None,
) -> Dict:
    os.makedirs(contracts_dir, exist_ok=True)
    data = {
        "comment": (
            "memcheck MC001 contract: the static per-device memory "
            "model of the lowered step program for this mesh. "
            "Regenerate with: python -m dlrover_tpu.lint --mem <spec> "
            "--fix-contracts"
        ),
        "version": 1,
        "mesh_spec": mesh_spec,
        "components": {c: int(components.get(c, 0)) for c in COMPONENTS},
        "peak_bytes": int(peak_bytes),
    }
    if measured:
        data["measured"] = {k: int(v) for k, v in sorted(measured.items())}
    if extra:
        data.update(extra)
    path = mem_contract_path(contracts_dir, mesh_spec)
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def check_components(
    components: Dict[str, int],
    peak_bytes: int,
    contract: Dict,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
    label: str = "step",
) -> List[Violation]:
    """MC001: diff the built breakdown against the contract. Growth past
    tolerance (and past :data:`MIN_GROWTH_BYTES`) fails, NAMING the
    component that grew — the whole point of carrying a breakdown
    instead of one peak number."""
    out: List[Violation] = []
    contracted = contract.get("components", {})
    for comp in COMPONENTS:
        old = int(contracted.get(comp, 0))
        new = int(components.get(comp, 0))
        grown = new - old
        if grown <= MIN_GROWTH_BYTES:
            continue
        if old > 0 and new <= old * (1.0 + byte_tolerance):
            continue
        pct = (grown / old * 100.0) if old else math.inf
        out.append(_violation(
            "MC001",
            label,
            f"memory component '{comp}' grew past tolerance: "
            f"{old} -> {new} bytes per device "
            f"(+{grown}, {'+inf' if old == 0 else f'{pct:+.1f}'}%"
            f", tolerance {byte_tolerance:.0%}). Review the change or "
            "regenerate with --fix-contracts.",
        ))
    old_peak = int(contract.get("peak_bytes", 0))
    if (old_peak > 0
            and peak_bytes - old_peak > MIN_GROWTH_BYTES
            and peak_bytes > old_peak * (1.0 + byte_tolerance)):
        worst = max(
            COMPONENTS,
            key=lambda c: components.get(c, 0) - contracted.get(c, 0),
        )
        out.append(_violation(
            "MC001",
            label,
            f"per-device peak grew past tolerance: {old_peak} -> "
            f"{peak_bytes} bytes (largest component delta: '{worst}').",
        ))
    return out


def component_improvements(
    components: Dict[str, int],
    peak_bytes: int,
    contract: Dict,
    byte_tolerance: float = DEFAULT_BYTE_TOLERANCE,
) -> List[str]:
    """Shrinks worth re-banking (the mirror of MC001: an improvement
    left uncommitted is tolerance headroom a future regression can
    silently spend)."""
    notes: List[str] = []
    contracted = contract.get("components", {})
    for comp in COMPONENTS:
        old = int(contracted.get(comp, 0))
        new = int(components.get(comp, 0))
        if old - new > MIN_GROWTH_BYTES and new < old * (1.0 - byte_tolerance):
            notes.append(
                f"component '{comp}' shrank {old} -> {new} bytes; "
                "re-bank with --fix-contracts"
            )
    old_peak = int(contract.get("peak_bytes", 0))
    if (old_peak - peak_bytes > MIN_GROWTH_BYTES
            and peak_bytes < old_peak * (1.0 - byte_tolerance)):
        notes.append(
            f"peak shrank {old_peak} -> {peak_bytes} bytes; re-bank "
            "with --fix-contracts"
        )
    return notes


# ---------------------------------------------------------------------------
# MC002: the headroom budget + the planner's oracle
# ---------------------------------------------------------------------------


def budget_bytes(
    device_class: str = "", budget_gb: float = 0.0
) -> float:
    """Resolve the per-device HBM budget: an explicit GB override wins,
    else the device-class table, else 0 (= budget checking off)."""
    if budget_gb and budget_gb > 0:
        return float(budget_gb) * 1e9
    return float(DEVICE_HBM_BYTES.get(device_class, 0))


def check_budget(
    peak_bytes: float,
    device_class: str = "",
    budget_gb: float = 0.0,
    headroom_frac: float = DEFAULT_HEADROOM_FRAC,
    label: str = "step",
) -> List[Violation]:
    """MC002: predicted per-device peak vs. the device-class budget
    minus headroom. No budget configured -> nothing to check."""
    budget = budget_bytes(device_class, budget_gb)
    if budget <= 0:
        return []
    usable = budget * (1.0 - headroom_frac)
    if peak_bytes <= usable:
        return []
    return [_violation(
        "MC002",
        label,
        f"predicted per-device peak {int(peak_bytes)} bytes exceeds "
        f"the {device_class or 'configured'} budget "
        f"({int(budget)} bytes - {headroom_frac:.0%} headroom = "
        f"{int(usable)} usable).",
    )]


def component_divisor(
    component: str,
    wd: WorldDescriptor,
    assume_zero1: Optional[bool] = None,
) -> int:
    """How many ways ``component`` divides across the devices of a
    world — the scaling law that turns one compiled breakdown into a
    prediction for EVERY admissible world:

    - params and the grad accumulator shard over the model axes
      (fsdp, tp);
    - optimizer moments additionally shard over dp under ZeRO-1 — the
      term that makes a *shrink* pack more state per device;
    - activations shard over the sequence/model axes (sp, tp); the
      per-device microbatch is held fixed across dp changes by the
      grad-accumulation invariant, so dp does not appear;
    - temp is per-device scratch: divisor 1.

    ``assume_zero1`` overrides the descriptor's own flag: planner-level
    node candidates are bare dp worlds, but they will run the *current
    program family* — the caller knows whether that family is ZeRO-1.
    """
    axes = wd.axis_sizes()
    fsdp = max(1, axes.get("fsdp", 1))
    tp = max(1, axes.get("tp", 1))
    sp = max(1, axes.get("sp", 1))
    dp = max(1, axes.get("dp", 1))
    zero1 = wd.zero1 if assume_zero1 is None else bool(assume_zero1)
    if component in ("params", "grads_accum"):
        return fsdp * tp
    if component == "moments":
        return fsdp * tp * (dp if zero1 else 1)
    if component == "activations":
        return sp * tp
    return 1


@dataclasses.dataclass
class HeadroomOracle:
    """The static headroom oracle: per-component GLOBAL byte totals plus
    the scaling law of :func:`component_divisor`, so any candidate
    ``WorldDescriptor`` — never-visited worlds, layout flips, the lot —
    prices out in five divisions. jax-free by construction: it runs
    master-side inside the planner and device-side inside the
    speculation filter.

    ``totals[c] / component_divisor(c, wd)`` is the predicted per-device
    bytes of component ``c`` at world ``wd`` (components with divisor 1,
    i.e. temp, store per-device bytes directly).
    """

    totals: Dict[str, float]
    base: WorldDescriptor
    device_class: str = ""
    budget_gb: float = 0.0
    headroom_frac: float = DEFAULT_HEADROOM_FRAC
    #: model candidates as running the current program family's ZeRO-1
    #: setting even when the bare candidate descriptor doesn't carry it
    assume_zero1: Optional[bool] = None

    @classmethod
    def from_components(
        cls,
        components: Dict[str, float],
        base: WorldDescriptor,
        **kwargs,
    ) -> "HeadroomOracle":
        """Lift a per-device breakdown measured AT ``base`` back to
        global totals (multiply by the base world's divisors)."""
        assume = kwargs.get("assume_zero1")
        totals = {
            c: float(components.get(c, 0))
            * component_divisor(c, base, assume)
            for c in COMPONENTS
        }
        return cls(totals=totals, base=base, **kwargs)

    @classmethod
    def from_contract(cls, contract: Dict, **kwargs) -> "HeadroomOracle":
        base = WorldDescriptor.parse(contract["mesh_spec"])
        return cls.from_components(
            contract.get("components", {}), base, **kwargs
        )

    def predict(
        self, wd: WorldDescriptor, assume_zero1: Optional[bool] = None
    ) -> Dict[str, float]:
        assume = self.assume_zero1 if assume_zero1 is None else assume_zero1
        out = {
            c: self.totals.get(c, 0.0) / component_divisor(c, wd, assume)
            for c in COMPONENTS
        }
        out["peak_bytes"] = sum(out[c] for c in COMPONENTS)
        return out

    def budget_bytes(self) -> float:
        return budget_bytes(self.device_class, self.budget_gb)

    def fits(
        self, wd: WorldDescriptor, assume_zero1: Optional[bool] = None
    ) -> Dict:
        """Price a candidate. ``{"fits": bool, "peak_bytes": ...,
        "budget_bytes": ..., "usable_bytes": ...}`` — a zero budget
        means the oracle is unarmed and everything fits."""
        pred = self.predict(wd, assume_zero1)
        budget = self.budget_bytes()
        usable = budget * (1.0 - self.headroom_frac)
        return {
            "fits": budget <= 0 or pred["peak_bytes"] <= usable,
            "peak_bytes": int(pred["peak_bytes"]),
            "budget_bytes": int(budget),
            "usable_bytes": int(usable),
        }


# ---------------------------------------------------------------------------
# MC rule catalog (for --list-rules and the docs)
# ---------------------------------------------------------------------------

MC_RULES: List[Tuple[str, str, str]] = [
    ("MC001", "memory-contract",
     "Per-device peak bytes and the params/moments/grads_accum/"
     "activations/temp breakdown of the lowered step diffed against a "
     "checked-in per-(mesh, config-hash) contract; growth past the "
     "byte tolerance names the component that grew."),
    ("MC002", "headroom-budget",
     "Predicted per-device peak vs. the per-device-class HBM budget "
     "(v5e/v5p/cpu-host) minus headroom; the same check through the "
     "HeadroomOracle is the planner's oom_veto on candidate worlds."),
]
