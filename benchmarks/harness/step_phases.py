"""The step's busy time by phase and by scope, over leaf operations.

``hlo_scopes`` says how long the device ran under one named scope. This
says where *all* of the busy time went: every operation of the ``XLA
Ops`` line that is not a ``while``, a ``conditional`` or a ``call``
around others (those are the sum of what they enclose and are not
counted themselves) is put, by its ``op_name`` in the compiled step
(``step.hlo``), into

- one *phase*. JAX writes the phase into every ``op_name``: the update
  is traced under the scopes ``optimizer_update`` / ``grad_finish``, a
  recomputed forward under ``checkpoint/rematted_computation``, the
  backward under the transform ``transpose(jvp(...))`` and the forward
  under what is left. The backward is told by ``transpose(`` with its
  parenthesis: ``lax.transpose`` is a primitive too, and ends forward
  paths (``.../attn_proj/transpose``). An operation XLA made itself
  (a layout's copy) carries no ``op_name`` and is in no phase;
- the scopes it was traced under (``trace.scopes()``: the program keeps
  the list, ``observability/trace.py``), as whole components of the
  path, the way ``hlo_scopes._in_scope`` compares them;
- *the scans' own*: a ``lax.scan`` over stacked layers slices a layer's
  parameters out and stacks what it saves and the gradients back; those
  operations sit at ``.../while/body/<primitive>`` under no scope.

A fusion takes its root's ``op_name``, and one whose root XLA made (the
tuple of a multi-output fusion, a convert) has none, though every
instruction inside it has: such a fusion *adopts* the ``op_name`` most
of its instructions carry (the rotary's two halves are such fusions:
7-9 ms a step of ``mistral7b-d5-steady`` that ``hlo_scopes`` alone, and
the metrics that read through it, leave out of ``attn_proj``). A copy
XLA made to change a value's layout adopts the ``op_name`` of what made
the value. What can adopt nothing (a copy of a loop's argument, a
zero-fill) stays without.

Everything is milliseconds a step: seconds inside the traced window
over the ``step`` spans in it, averaged over the devices.
"""

from __future__ import annotations

import collections
import functools
import glob
import json
import os
import re
import time
from typing import (Callable, Dict, FrozenSet, List, NamedTuple, Optional,
                    Sequence, Tuple)

from benchmarks.harness import hlo_scopes, trace_reduce

PHASES = ("fwd", "remat_fwd", "bwd", "optimizer")
NO_OP_NAME = "no_op_name"
OPTIMIZER_SCOPES = ("optimizer_update", "grad_finish")
REMAT = "rematted_computation"
BACKWARD = "transpose("

_SCAN_OWN = re.compile(r"(?:^|/)while/body/[^/()]+$")
_CONTROL_NAME = re.compile(r"(?:while|conditional|call)(?:\.|$)")
_CONTROL_TEXT = re.compile(r"\s(?:while|conditional|call)\(")
_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"\(%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LAYER_METRICS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "layer_metrics")


class Leaf(NamedTuple):
    start: float            # ns, clipped to the traced window
    end: float
    name: str               # the instruction's
    op_name: str            # its path in the compiled step, or ""
    phase: str              # one of PHASES, or NO_OP_NAME
    parts: FrozenSet[str]   # the path's components: scopes are whole ones


def phase_of(op_name: str) -> str:
    return _describe(op_name)[0]


@functools.lru_cache(maxsize=None)
def _describe(op_name: str) -> Tuple[str, FrozenSet[str]]:
    parts = frozenset(re.split(r"[/()]", op_name))
    if not op_name:
        phase = NO_OP_NAME
    elif parts.intersection(OPTIMIZER_SCOPES):
        phase = "optimizer"
    elif REMAT in parts:
        phase = "remat_fwd"
    else:
        phase = "bwd" if BACKWARD in op_name else "fwd"
    return phase, parts


def scan_own(op_name: str, scopes: FrozenSet[str]) -> bool:
    """Whether ``op_name`` is a scan's own slicing or stacking: at the
    top of a loop body and under none of the program's ``scopes`` (a
    loop inside a scope, the chunked loss's, is that scope's)."""
    return bool(_SCAN_OWN.search(op_name)) and not (
        _describe(op_name)[1] & scopes)


def leaf_ops(ops: Sequence[trace_reduce.Op]) -> List[trace_reduce.Op]:
    """The operations of one line less the control flow around others:
    a ``while``, a ``conditional`` or a ``call`` (by its instruction's
    name or text) inside whose interval another operation lies. Any
    other two that overlap (threads of the CPU rehearsal; on the chip's
    line a fusion opens with a custom call of no length) are both
    leaves, and the sums take the union of their intervals. Events of
    no length are dropped: they add nothing."""
    ordered = sorted((o for o in ops if o[1] > o[0]),
                     key=lambda o: (o[0], -o[1]))
    encloses = [False] * len(ordered)
    open_: List[int] = []
    for i, op in enumerate(ordered):
        while open_ and ordered[open_[-1]][1] <= op[0]:
            open_.pop()
        for j in open_:
            if ordered[j][1] >= op[1]:
                encloses[j] = True
        if _CONTROL_NAME.match(op[2]) or _CONTROL_TEXT.search(op[3]):
            open_.append(i)
    return [op for op, holds in zip(ordered, encloses) if not holds]


def adopted_op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for the instructions of ``hlo_text``
    that carry no ``op_name`` of their own and can adopt one (module
    docstring): a fusion, the one most instructions of the computation
    it calls carry, those of the fusions nested in it included; any
    other instruction (a copy XLA made for a layout), its first
    operand's, own or adopted."""
    inside: Dict[str, collections.Counter] = {}    # computation: its names
    nested: Dict[str, List[str]] = {}              # computation: it calls
    named: Dict[str, str] = {}
    nameless: List[Tuple[str, Optional[str], Optional[str]]] = []
    computation = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line) if line.endswith("{") else None
            computation = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, operands = m.group(1), m.group(2)
        found = _OP_NAME.search(line)
        if found:
            named[name] = found.group(1)
            inside.setdefault(
                computation, collections.Counter())[found.group(1)] += 1
            continue
        calls = _CALLS.search(line) if " fusion(" in line else None
        if calls:
            nested.setdefault(computation, []).append(calls.group(1))
        first = _OPERAND.search(operands)
        nameless.append((name, calls and calls.group(1),
                         first and first.group(1)))

    @functools.lru_cache(maxsize=None)
    def names_in(comp: str) -> collections.Counter:
        total = collections.Counter(inside.get(comp, ()))
        for child in nested.get(comp, ()):
            total.update(names_in(child))
        return total

    adopted: Dict[str, str] = {}
    for name, called, operand in nameless:    # the text's order: operands first
        if called is not None:
            common = names_in(called).most_common(1)
            got = common[0][0] if common else None
        else:
            got = named.get(operand) or adopted.get(operand)
        if got:
            adopted[name] = got
    return adopted


def op_name_table(ctx) -> Optional[Dict[str, str]]:
    """``hlo_scopes``'s table of the live step with the adopted names
    beside it; the text is asked for once, parsed once for each, and
    both are kept on ``ctx``."""
    if not hasattr(ctx, "step_phase_names"):
        from dlrover_tpu.observability import trace

        ctx.step_phase_names = None
        t0 = time.perf_counter()
        text = getattr(trace, "text", lambda name: None)("step.hlo")
        if text is not None:
            t1 = time.perf_counter()
            if not hasattr(ctx, "step_op_names"):
                ctx.step_op_names = hlo_scopes.op_names(text)
            adopted = adopted_op_names(text)
            ctx.step_phase_names = {**adopted, **ctx.step_op_names}
            ctx.log(f"step_phases: step.hlo {len(text)} bytes in "
                    f"{t1 - t0:.2f}s, {len(ctx.step_op_names)} op_names and "
                    f"{len(adopted)} adopted in "
                    f"{time.perf_counter() - t1:.2f}s")
    return ctx.step_phase_names


def program_scopes() -> Optional[FrozenSet[str]]:
    """The names the program has opened scopes under; None for a
    program that keeps no list (the ones before PR 35)."""
    from dlrover_tpu.observability import trace

    scopes = getattr(trace, "scopes", None)
    return None if scopes is None else frozenset(scopes())


def named_patterns() -> List["re.Pattern"]:
    """What finds a kernel by its instruction's name: every ``patterns``
    entry of the metrics beside this benchmark (``grouped_matmul``,
    ``ragged-dot``, the collectives; XLA gives those no ``op_name``)."""
    found = set()
    for path in glob.glob(os.path.join(_LAYER_METRICS, "*.json")):
        with open(path) as f:
            found.update(json.load(f).get("patterns", ()))
    return [re.compile(p) for p in sorted(found)]


def leaves(ctx) -> Optional[List[List[Leaf]]]:
    """Per device, the leaf operations inside the traced window;
    computed once and kept on ``ctx``, and logged against the device's
    busy time. None where the program offers no ``step.hlo``."""
    if hasattr(ctx, "step_leaves"):
        return ctx.step_leaves
    table = op_name_table(ctx)
    ctx.step_leaves = None
    if table is None or not ctx.trace.devices:
        return None
    t0 = time.perf_counter()
    lo, hi = ctx.trace.window_ns
    out = []
    for ops in ctx.trace.devices.values():
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        dev = []
        for s, e, name, _ in leaf_ops(inside):
            op_name = table.get(name, "")
            dev.append(Leaf(max(s, lo), min(e, hi), name, op_name,
                            *_describe(op_name)))
        out.append(dev)
    ctx.step_leaves = out
    union_s = _mean_union_s(out)
    busy_s, _ = trace_reduce.busy_and_window_s(ctx.trace)
    ctx.log(f"step_phases: leaves={sum(map(len, out))} of "
            f"{sum(map(len, ctx.trace.devices.values()))} operations "
            f"leaves_union_s={union_s:.6f} busy_s={busy_s:.6f} "
            f"apart={100.0 * (union_s / busy_s - 1.0) if busy_s else 0.0:.3f}% "
            f"in {time.perf_counter() - t0:.2f}s")
    return out


def _mean_union_s(per_device: Sequence[Sequence[Leaf]]) -> float:
    total = [
        sum(e - s for s, e in trace_reduce._union([l[:2] for l in dev]))
        for dev in per_device
    ]
    return sum(total) / len(total) / 1e9


def ms_per_step(ctx, keep: Callable[[Leaf], bool]) -> Optional[float]:
    """Milliseconds a step in the leaves ``keep`` takes (the union of
    their intervals on each device); None where there is no table, no
    step, or nothing kept."""
    steps = trace_reduce.count_spans(ctx.trace, "step")
    per_device = leaves(ctx)
    if per_device is None or not steps:
        return None
    kept = [[l for l in dev if keep(l)] for dev in per_device]
    if not any(kept):
        return None
    return _mean_union_s(kept) * 1e3 / steps


def phase_ms(ctx) -> Optional[Dict[str, float]]:
    """``{phase: ms a step}`` for the four phases and ``no_op_name``,
    kept on ``ctx``; logs what their sum leaves of the leaves' time."""
    if hasattr(ctx, "step_phase_ms"):
        return ctx.step_phase_ms
    ctx.step_phase_ms = None
    whole = ms_per_step(ctx, lambda leaf: True)
    if whole is None:
        return None
    out = {
        phase: ms_per_step(ctx, lambda leaf, p=phase: leaf.phase == p) or 0.0
        for phase in PHASES + (NO_OP_NAME,)
    }
    ctx.step_phase_ms = out
    ctx.log("step_phases: " + " ".join(
        f"{phase}={ms:.3f}" for phase, ms in out.items())
        + f" leaves={whole:.3f} remainder={whole - sum(out.values()):.3f} "
        "(ms a step)")
    return out


def read_phase(spec, ctx):
    """One phase's milliseconds a step: ``spec["phase"]``."""
    table = phase_ms(ctx)
    return None if table is None else table[spec["phase"]]


def read_scopes(spec, ctx):
    """Milliseconds a step under ``spec["scopes"]``, all phases."""
    scopes = frozenset(spec["scopes"])
    return ms_per_step(ctx, lambda leaf: bool(leaf.parts & scopes))


def read_layer_scan(spec, ctx):
    scopes = program_scopes() or frozenset()
    return ms_per_step(ctx, lambda leaf: scan_own(leaf.op_name, scopes))


def read_unscoped(spec, ctx):
    """Busy milliseconds a step that nothing names: under none of the
    program's scopes, of no kernel found by name and not a scan's own
    (``layer_scan_ms`` names those). Logs, for the operator, every
    scope's milliseconds phase by phase, the scans' own, what carries
    no ``op_name`` and the ten largest operations it counted."""
    scopes = program_scopes()
    per_device = leaves(ctx)
    steps = trace_reduce.count_spans(ctx.trace, "step")
    if scopes is None or per_device is None or not steps:
        return None
    t0 = time.perf_counter()
    regs = named_patterns()

    def unscoped(leaf):
        return not (leaf.parts & scopes or scan_own(leaf.op_name, scopes)
                    or any(r.search(leaf.name) for r in regs))

    value = ms_per_step(ctx, unscoped)
    own = read_layer_scan(spec, ctx)
    # the operator's table, in one pass: leaves of one line do not
    # overlap on the chip, so durations add (the metrics above take the
    # union, which the CPU rehearsal's threads need)
    by_scope: Dict[str, Dict[str, float]] = {}
    worst: Dict[str, List] = {}
    for dev in per_device:
        for leaf in dev:
            ns = leaf.end - leaf.start
            for scope in leaf.parts & scopes:
                row = by_scope.setdefault(scope, dict.fromkeys(PHASES, 0.0))
                row[leaf.phase] += ns
            if unscoped(leaf):
                worst.setdefault(leaf.name, [0.0, leaf])[0] += ns
    scale = 1e-6 / steps / len(per_device)
    for scope in sorted(by_scope):
        row = by_scope[scope]
        ctx.log(f"scope {scope}: " + " ".join(
            f"{phase}={row[phase] * scale:.3f}" for phase in PHASES)
            + f" all={sum(row.values()) * scale:.3f}")
    ctx.log(f"unscoped: {value or 0.0:.3f} ms a step, of it without op_name "
            f"{phase_ms(ctx)[NO_OP_NAME]:.3f}; beside it the scans' own "
            f"{own or 0.0:.3f}")
    for name, (ns, leaf) in sorted(
            worst.items(), key=lambda kv: -kv[1][0])[:10]:
        ctx.log(f"unscoped op {name}: {ns * scale:.3f} ms a step "
                f"phase={leaf.phase} op_name={leaf.op_name or '-'}")
    ctx.log(f"unscoped: read and logged in {time.perf_counter() - t0:.2f}s")
    return value
