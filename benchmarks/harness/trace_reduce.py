"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. The trace is
reduced once into a ``Trace``: per device, the device operations as
``(start_ns, end_ns, name, text)``, and from the host plane the spans
the measured loop wrote itself (``jax.profiler.TraceAnnotation``).
Everything the benchmark reports from a trace is computed from that:

- busy seconds of a device: the union of the intervals in which an
  operation ran on it (operations nest and overlap, so a sum of
  durations would count time twice);
- the seconds of the operations whose name or text matches a pattern;
- the idle gaps, each put down to the loop's span it falls into.

Where the operations are (read off a v5e trace by hand, PR 24): every
``/device:TPU:<n>`` plane has a line ``XLA Ops``, whose events are the
HLO instructions as they ran. (A line ``Async XLA Ops``, on the first
device's plane only, has the copies and collective-permutes in flight
beside them; on four chips something is in flight 95 % of a step, so it
is not read.) An event's name is the
whole text of its instruction (``%attention_fwd.16 = (bf16[...]...)
custom-call(...)``) and it carries no scope of its own, so the reduction
keeps the instruction's name (``attention_fwd.16``; XLA builds it from
the JAX scope, which is how a kernel is found) and the text. The wait
for an asynchronous operation shows on ``XLA Ops`` as its ``-done``.
The CPU backend has no device plane; its operations are the events that
carry an ``hlo_op`` stat on the host plane's client threads. That path
exists so the reduction can be rehearsed without a chip, and a CPU
reading is never reported as a device number (``run.py`` prints
``platform: cpu``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Op = Tuple[float, float, str, str]        # start_ns, end_ns, name, text
Span = Tuple[float, float, str]           # start_ns, end_ns, name


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]          # per device, the XLA Ops line
    spans: List[Span]

    @property
    def window_ns(self) -> Tuple[float, float]:
        """What the reduction takes as the traced window: from the start
        of the loop's first span to the end of its last; without spans,
        from the first device operation to the last."""
        if self.spans:
            return (min(s[0] for s in self.spans),
                    max(s[1] for s in self.spans))
        ops = [op for d in self.devices.values() for op in d]
        if not ops:
            return (0.0, 0.0)
        return (min(o[0] for o in ops), max(o[1] for o in ops))


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def _op(event) -> Op:
    text = event.name
    name = text.split(" = ", 1)[0].lstrip("%") if text.startswith("%") else text
    return (event.start_ns, event.start_ns + event.duration_ns, name, text)


def load(path: str, span_names: Iterable[str]) -> Trace:
    """Reduce the file at ``path``. ``span_names`` are the names of the
    loop's own annotations; host events of other names are left out."""
    from jax.profiler import ProfileData

    want = set(span_names)
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    host_ops: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    devices[plane.name] = [_op(e) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        spans.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        )
                    elif e.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in e.stats
                    ):
                        host_ops.append(_op(e))
    if not devices and host_ops:
        devices["cpu"] = host_ops
    for ops in devices.values():
        ops.sort()
    spans.sort()
    return Trace(devices, spans)


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _clip(ops: Sequence[Op], lo: float, hi: float):
    return [
        (max(o[0], lo), min(o[1], hi)) for o in ops
        if o[1] > lo and o[0] < hi
    ]


def busy_and_window_s(trace: Trace) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds)."""
    lo, hi = trace.window_ns
    if hi <= lo or not trace.devices:
        return 0.0, max(0.0, (hi - lo) / 1e9)
    busy = [
        sum(e - s for s, e in _union(_clip(ops, lo, hi)))
        for ops in trace.devices.values()
    ]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def matching_s(trace: Trace, patterns: Sequence[str],
               text_patterns: Sequence[str] = ()) -> Optional[float]:
    """Seconds of the device operations inside the window whose name
    matches one of ``patterns`` or whose instruction text matches one of
    ``text_patterns`` (regular expressions, searched), as the union of
    their intervals on each device, averaged over the devices. ``None``
    when nothing matched: a reader with nothing to read reports nothing,
    not zero."""
    lo, hi = trace.window_ns
    regs = [re.compile(p) for p in patterns]
    text_regs = [re.compile(p) for p in text_patterns]
    per_device = []
    hit = False
    for ops in trace.devices.values():
        sel = [
            o for o in ops
            if any(r.search(o[2]) for r in regs)
            or any(r.search(o[3]) for r in text_regs)
        ]
        hit = hit or bool(sel)
        per_device.append(sum(e - s for s, e in _union(_clip(sel, lo, hi))))
    if not hit:
        return None
    return sum(per_device) / len(per_device) / 1e9


def count_spans(trace: Trace, name: str) -> int:
    return sum(1 for s in trace.spans if s[2] == name)


def top_ops(trace: Trace, limit: int = 10) -> List[List]:
    """The device operations that took most time, by name, in seconds
    summed over the window and averaged over the devices. Operations
    that enclose others (a ``while`` around a scanned layer stack) are
    listed with what they enclose, so the rows do not add up to the busy
    time; the name tells them apart."""
    lo, hi = trace.window_ns
    total: Dict[str, float] = {}
    for ops in trace.devices.values():
        for s, e, name, _ in ops:
            if e > lo and s < hi:
                total[name] = total.get(name, 0.0) + (min(e, hi) - max(s, lo))
    n = max(1, len(trace.devices))
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, sec / n / 1e9] for name, sec in rows]


def idle_gaps(trace: Trace, limit: int = 10) -> List[List]:
    """The device's idle time by what the host was doing: every gap
    between device operations (on the first device) is split over the
    loop's spans it overlaps, innermost span first; what no span covers
    goes to ``unannotated``. Rows are ``[span name, seconds]``, largest
    first."""
    lo, hi = trace.window_ns
    if not trace.devices or hi <= lo:
        return []
    ops = next(iter(trace.devices.values()))
    busy = _union(_clip(ops, lo, hi))
    gaps = []
    cursor = lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    # innermost first: shorter spans win over the spans that hold them
    spans = sorted(trace.spans, key=lambda sp: sp[1] - sp[0])
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for s0, s1, name in spans:
            nxt = []
            for a, b in left:
                o0, o1 = max(a, s0), min(b, s1)
                if o1 > o0:
                    total[name] = total.get(name, 0.0) + (o1 - o0)
                    if a < o0:
                        nxt.append((a, o0))
                    if o1 < b:
                        nxt.append((o1, b))
                else:
                    nxt.append((a, b))
            left = nxt
            if not left:
                break
        rest = sum(b - a for a, b in left)
        if rest > 0:
            total["unannotated"] = total.get("unannotated", 0.0) + rest
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, sec / 1e9] for name, sec in rows]
