"""Readers of what the program says about itself: the counters and
gauges of ``dlrover_tpu.observability.trace`` (kept in the program's
process, which is this one), and the spans it writes into the profiler's
trace as host events named ``dlrover/<span>``, on the clock of the
device's operations.

A metric's file (``layer_metrics/<name>.json``) names the span or gauge;
its ``<name>.py`` takes one of these functions as ``read``. Each returns
None where the program has no such counter or the trace no such span (a
program older than the spans, a cell that never saves), and the metric
is then left out of the line.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import stats, trace_reduce

PREFIX = "dlrover/"
UNIT_PER_SECOND = {"s": 1.0, "ms": 1e3}


@dataclasses.dataclass
class ProgramSpan:
    name: str               # without the prefix
    start_ns: float
    end_ns: float
    line: str               # the host thread it ran on: "<name>/<n>"
    stats: Dict[str, object]


def load(path: str) -> List[ProgramSpan]:
    """Every host event of the ``.xplane.pb`` at ``path`` that the
    program's spans wrote, by start."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        # the profiler names every Python thread's line "python"
        for n, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(ProgramSpan(
                        e.name[len(PREFIX):], e.start_ns,
                        e.start_ns + e.duration_ns, f"{line.name}/{n}",
                        dict(e.stats),
                    ))
    spans.sort(key=lambda s: (s.start_ns, s.end_ns))
    return spans


def spans_of(ctx) -> List[ProgramSpan]:
    """The traced run's program spans, read once and kept on ``ctx``."""
    if getattr(ctx, "program_spans", None) is None:
        path = ctx.trace_dir and trace_reduce.find_xplane(ctx.trace_dir)
        ctx.program_spans = load(path) if path else []
    return ctx.program_spans


def _program_table(which: str) -> dict:
    """``trace.counters()`` or ``trace.gauges()``; empty for a program
    that has neither."""
    from dlrover_tpu.observability import trace

    return getattr(trace, which, dict)()


def counter_seconds_mean(spec, ctx) -> Optional[float]:
    """Mean seconds of the program's spans named ``spec["span"]`` over
    the whole run, from its counters: seconds over times closed."""
    row = _program_table("counters").get(spec["span"])
    if not row or not row[0]:
        return None
    return row[1] / row[0]


def gauge(spec, ctx) -> Optional[float]:
    """The program's gauge ``spec["gauge"]``, over ``spec["divide_by"]``."""
    value = _program_table("gauges").get(spec["gauge"])
    if value is None:
        return None
    return value / float(spec.get("divide_by", 1))


def span_ms_median(spec, ctx) -> Optional[float]:
    """Median milliseconds of the spans named ``spec["span"]`` in the
    traced stretch, on the profiler's clock."""
    durs = [s.end_ns - s.start_ns for s in spans_of(ctx)
            if s.name == spec["span"]]
    if not durs:
        return None
    return stats.median(durs) / 1e6


def idle_ns_inside(trace: trace_reduce.Trace,
                   intervals: List[Tuple[float, float]]) -> float:
    """Nanoseconds of the window in which the first device ran nothing
    and one of ``intervals`` was open: the same device, window and union
    of operations as ``trace_reduce.idle_gaps``."""
    lo, hi = trace.window_ns
    if not trace.devices or hi <= lo:
        return 0.0
    ops = next(iter(trace.devices.values()))
    busy = trace_reduce._union(trace_reduce._clip(ops, lo, hi))
    inside = trace_reduce._union(
        [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi])
    idle_ns = sum(b - a for a, b in inside)
    i = 0
    for a, b in inside:         # both lists are sorted and disjoint
        while i < len(busy) and busy[i][1] <= a:
            i += 1
        j = i
        while j < len(busy) and busy[j][0] < b:
            idle_ns -= min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
    return idle_ns


def idle_in_span(spec, ctx) -> Optional[float]:
    """Device idle time inside the spans named ``spec["span"]``, in
    ``spec["unit"]``: per ``step`` span of the loop (``"per": "step"``)
    or per occurrence of the span."""
    mine = [(s.start_ns, s.end_ns) for s in spans_of(ctx)
            if s.name == spec["span"]]
    if not mine:
        return None
    if spec.get("per", "occurrence") == "step":
        over = trace_reduce.count_spans(ctx.trace, "step")
    else:
        over = len(mine)
    if not over:
        return None
    idle_s = idle_ns_inside(ctx.trace, mine) / 1e9
    return idle_s * UNIT_PER_SECOND[spec["unit"]] / over
