"""Device time by the program's named scopes.

A device trace names the HLO instructions that ran (``fusion.500``,
``ragged-dot-none.3``) and carries nothing of the ``jax.named_scope``
they were traced under; a Pallas call is named after its scope
(``attention_fwd.16``), an instruction XLA makes is not. The compiled
step's text has both: every instruction's ``metadata={op_name="..."}``
is its scope path (``.../checkpoint/moe_dispatch/gather``; for a fusion,
its root's). The program offers that text as ``trace.text("step.hlo")``
(``observability/trace.py``); a program without it, as the ones before
PR 27, gives None and the metric is left out of the line.

Instructions XLA makes with no JAX metadata (its own grouped-matmul
custom calls, ``ragged-dot-none``) are found by ``patterns`` on their
names, as ``readers.trace_ms_per_step`` finds kernels.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.harness import trace_reduce

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction of
    ``hlo_text`` that carries one (names are unique in a module)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _step_op_names(ctx) -> Optional[Dict[str, str]]:
    """The live step's table, parsed once and kept on ``ctx``."""
    if not hasattr(ctx, "step_op_names"):
        from dlrover_tpu.observability import trace

        text = getattr(trace, "text", lambda name: None)("step.hlo")
        ctx.step_op_names = None if text is None else op_names(text)
    return ctx.step_op_names


def _in_scope(op_name: str, scopes: Sequence[str]) -> bool:
    # a scope is one whole component of the path, bare or wrapped by a
    # transform: moe_dispatch, jvp(moe_dispatch), transpose(jvp(...))
    parts = re.split(r"[/()]", op_name)
    return any(s in parts for s in scopes)


def matching_ops(ctx, scopes: Sequence[str], patterns: Sequence[str] = ()
                 ) -> Optional[List[Tuple[str, List[trace_reduce.Op]]]]:
    """Per device, the traced operations that belong to one of
    ``scopes`` (by the step's ``op_name`` table) or whose instruction
    name matches one of ``patterns``. None where the program offers no
    table and no pattern matched anything."""
    table = _step_op_names(ctx) if scopes else {}
    regs = [re.compile(p) for p in patterns]
    if table is None and not regs:
        return None
    table = table or {}
    out, hit = [], False
    for device, ops in ctx.trace.devices.items():
        sel = [
            o for o in ops
            if _in_scope(table.get(o[2], ""), scopes)
            or any(r.search(o[2]) for r in regs)
        ]
        hit = hit or bool(sel)
        out.append((device, sel))
    return out if hit else None


def seconds_in_window(ctx, per_device) -> float:
    """Seconds of ``per_device``'s operations inside the traced window,
    as the union of their intervals, averaged over the devices."""
    lo, hi = ctx.trace.window_ns
    total = [
        sum(e - s for s, e in trace_reduce._union(
            trace_reduce._clip(ops, lo, hi)))
        for _, ops in per_device
    ]
    return sum(total) / len(total) / 1e9


def scoped_ms_per_step(spec, ctx) -> Optional[float]:
    """Milliseconds a step spends in the device operations of
    ``spec["scopes"]`` (and of the names matching ``spec["patterns"]``):
    their seconds in the traced stretch over the ``step`` spans in it."""
    steps = trace_reduce.count_spans(ctx.trace, "step")
    per_device = matching_ops(ctx, spec.get("scopes", ()),
                              spec.get("patterns", ()))
    if per_device is None or not steps:
        return None
    return seconds_in_window(ctx, per_device) * 1e3 / steps
