"""Unified trace spine: typed spans in a bounded process-wide ring.

The repo's instruments grew as disjoint ledgers — the compile ledger
(train/warm_compile.py), ResizeLedger (train/live_reshard.py), the comm
ledger (profiler/comm.py), checkpoint restore stats, the PyTracer ring
and the native interposer timeline — each with its own format and its
own clock. This module is the join: every instrument records *typed
spans* into one ring with one clock basis, and the ring exports
chrome-trace JSON that merges with every other rank's (and the
interposer's ``/timeline`` dump) into a single perfetto-loadable job
timeline (``python -m dlrover_tpu.profiler.analysis job-timeline``).

Clock basis
-----------
Spans are stamped with ``time.monotonic()`` (immune to NTP steps while
the process lives); the ring captures one ``(monotonic, wallclock)``
pair at construction so exports map every span to absolute epoch
microseconds. Ranks on NTP-synced hosts therefore merge on real time
with no cross-process handshake; the merge CLI re-bases sources that
lack the epoch metadata (interposer dumps) best-effort.

Hot-path contract
-----------------
``record()`` is two clock reads, a dict build and a lock+append —
never a device sync (graftlint JG002 stays green for the emitters in
``ElasticTrainer.step``). When ``DLROVER_TPU_TRACE`` is off (the
default) every entry point returns after one dict lookup.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger

#: the span classification (docs/design/observability.md). ``downtime`` is
#: master-side only (the SpeedMonitor's bracket spans); ``host`` is the
#: catch-all PyTracer user spans map onto; ``kernel`` is the per-kernel
#: breakdown lane the kernel ledger (profiler/kernel_ledger.py) emits —
#: its spans nest INSIDE step spans, which is why the kind is absent
#: from KIND_CATEGORY below (it decomposes "productive", it does not
#: add to it).
SPAN_KINDS = (
    "step",
    "compile",
    "rendezvous",
    "state_transfer",
    "ckpt_save",
    "ckpt_restore",
    "input_wait",
    "gc_pause",
    "eval",
    "downtime",
    "host",
    "kernel",
)


def enabled() -> bool:
    """Spine kill-switch, re-read per call (tests flip it at runtime)."""
    return bool(flags.TRACE.get())


class TraceRing:
    """Process-wide bounded span recorder (thread-safe).

    Spans: ``{"kind", "name", "t" (monotonic start, s), "dur" (s),
    "tid", "attrs"?}``. Per-kind cumulative seconds survive ring
    overflow — the attribution consumers read those, the timeline
    consumers read the (windowed) spans.
    """

    def __init__(self, capacity: Optional[int] = None):
        self._lock = threading.Lock()
        self._events: List[Dict] = []
        self._cap_override = capacity
        self._mono0 = time.monotonic()
        self._wall0 = time.time()
        self._kind_seconds: Dict[str, float] = {}

    # -- recording -----------------------------------------------------

    @property
    def capacity(self) -> int:
        if self._cap_override is not None:
            return int(self._cap_override)
        return max(16, int(flags.TRACE_RING_CAP.get()))

    def enabled(self) -> bool:
        return enabled()

    def record(
        self,
        kind: str,
        name: str,
        start_mono: float,
        dur_s: float,
        tid: Optional[int] = None,
        **attrs,
    ) -> None:
        """Record one completed span. ``start_mono`` is a
        ``time.monotonic()`` stamp; emitters that already measured a
        duration call this with their own numbers."""
        if not enabled():
            return
        ev: Dict[str, Any] = {
            "kind": kind,
            "name": name,
            "t": float(start_mono),
            "dur": max(0.0, float(dur_s)),
            "tid": tid if tid is not None else threading.get_ident() % 100000,
        }
        clean = {k: v for k, v in attrs.items() if v not in (None, "")}
        if clean:
            ev["attrs"] = clean
        with self._lock:
            self._events.append(ev)
            self._kind_seconds[kind] = (
                self._kind_seconds.get(kind, 0.0) + ev["dur"]
            )
            cap = self.capacity
            if len(self._events) > cap:
                del self._events[: len(self._events) // 2]

    @contextlib.contextmanager
    def span(self, kind: str, name: Optional[str] = None, **attrs):
        """``with trace_ring.span("ckpt_restore", tier="disk"): ...``"""
        if not enabled():
            yield
            return
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.record(kind, name or kind, t0, time.monotonic() - t0,
                        **attrs)

    # -- reading -------------------------------------------------------

    def events(self) -> List[Dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def kind_seconds(self) -> Dict[str, float]:
        """Cumulative seconds per span kind (ring-overflow-proof)."""
        with self._lock:
            return dict(self._kind_seconds)

    def clear(self):
        with self._lock:
            self._events.clear()
            self._kind_seconds.clear()

    # -- export --------------------------------------------------------

    def to_epoch_us(self, mono: float) -> int:
        """Map a monotonic stamp onto absolute epoch microseconds via
        the ring's captured basis pair."""
        return int((self._wall0 + (mono - self._mono0)) * 1e6)

    def chrome_events(self, pid: int = 1) -> List[Dict]:
        out = []
        for ev in self.events():
            args = dict(ev.get("attrs") or {})
            args["kind"] = ev["kind"]
            out.append({
                "name": ev["name"],
                "cat": ev["kind"],
                "ph": "X",
                "ts": self.to_epoch_us(ev["t"]),
                "dur": int(ev["dur"] * 1e6),
                "pid": pid,
                "tid": ev["tid"],
                "args": args,
            })
        return out

    def chrome_trace(self, role: str = "worker", **meta) -> Dict:
        """Perfetto-loadable document. The ``dlrover`` block is what
        lets the ``job-timeline`` merge identify the source and its
        clock (``epoch_us``)."""
        return {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "dlrover": {
                "role": role,
                "clock": "epoch_us",
                "wall0": self._wall0,
                "pid": os.getpid(),
                **{k: v for k, v in meta.items() if v not in (None, "")},
            },
        }

    def dump(self, path: str, role: str = "worker", **meta):
        doc = self.chrome_trace(role=role, **meta)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


#: the process singleton every emitter records into
trace_ring = TraceRing()


def record(kind: str, name: str, start_mono: float, dur_s: float, **attrs):
    trace_ring.record(kind, name, start_mono, dur_s, **attrs)


def span(kind: str, name: Optional[str] = None, **attrs):
    return trace_ring.span(kind, name, **attrs)


def default_dump_dir() -> str:
    """``DLROVER_TPU_TRACE_DIR``, defaulting next to the agent logs so
    the job-timeline CLI finds every role's dump in one place."""
    configured = flags.TRACE_DIR.get()
    if configured:
        return configured
    return os.path.join(
        tempfile.gettempdir(), "dlrover_tpu_logs",
        str(flags.JOB_NAME.get()), "traces",
    )


def dump_events(events: List[Dict], role: str, **meta) -> Optional[str]:
    """Write a pre-built chrome-event list as one job-timeline source
    (``trace-<role>-<pid>.json`` under the dump dir, atomic write, the
    standard ``dlrover`` metadata block). For producers whose spans are
    not in the process ring — the master's SpeedMonitor events. No-op
    (None) when the spine is off; raises OSError on write failure."""
    if not enabled():
        return None
    d = default_dump_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"trace-{role}-{os.getpid()}.json")
    doc = {
        "traceEvents": list(events),
        "displayTimeUnit": "ms",
        "dlrover": {
            "role": role,
            "clock": "epoch_us",
            "pid": os.getpid(),
            **{k: v for k, v in meta.items() if v not in (None, "")},
        },
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


_dump_registered = False


def dump_at_exit(role: str = "worker", **meta) -> bool:
    """Register an atexit dump of the spine ring (idempotent; no-op
    when the spine is off at registration time). Dump path:
    ``<dir>/trace-<role>-n<node>[-p<proc>]-<pid>.json`` — unique per
    process so concurrent ranks never clobber each other."""
    global _dump_registered
    if not enabled() or _dump_registered:
        return False
    _dump_registered = True
    import atexit

    def _dump():
        if not enabled():
            return
        try:
            d = default_dump_dir()
            os.makedirs(d, exist_ok=True)
            parts = [f"trace-{role}"]
            if meta.get("node_id") is not None:
                parts.append(f"n{meta['node_id']}")
            if meta.get("process_id") is not None:
                parts.append(f"p{meta['process_id']}")
            parts.append(str(os.getpid()))
            path = os.path.join(d, "-".join(parts) + ".json")
            trace_ring.dump(path, role=role, **meta)
            logger.info("trace spine dumped to %s", path)
        except OSError as e:
            logger.warning("trace spine dump failed: %s", e)

    atexit.register(_dump)
    return True


# ---------------------------------------------------------------------------
# consumers: attribution + /metrics
# ---------------------------------------------------------------------------

#: span kind -> lost-time attribution category (the same vocabulary the
#: master's SpeedMonitor.attribution() uses; docs/design/observability.md).
#: ``kernel`` is deliberately unmapped: kernel spans are a breakdown of
#: the step spans they nest inside — mapping them to "productive" would
#: double-count step time in the attribution sums.
KIND_CATEGORY = {
    "step": "productive",
    "eval": "productive",
    "compile": "compile",
    "rendezvous": "rendezvous",
    "state_transfer": "state_transfer",
    "ckpt_save": "checkpoint",
    "ckpt_restore": "checkpoint",
    "input_wait": "input_stall",
    "gc_pause": "input_stall",
}

ATTRIBUTION_CATEGORIES = (
    "productive", "compile", "rendezvous", "state_transfer",
    "checkpoint", "input_stall", "straggler_wait", "unattributed",
)


def attribution_from_kind_seconds(
    kind_seconds: Dict[str, float], wall_s: float
) -> Dict:
    """Single-process wall-time decomposition from the ring's per-kind
    totals (bench's ``goodput`` detail block). Categories sum to
    ``wall_s`` by construction: ``unattributed`` is the residual, and
    when measured categories overlap past the wall (nested spans) they
    are scaled down proportionally rather than summing past it."""
    cats = {c: 0.0 for c in ATTRIBUTION_CATEGORIES}
    for kind, secs in kind_seconds.items():
        cat = KIND_CATEGORY.get(kind)
        if cat is not None:
            cats[cat] += max(0.0, float(secs))
    wall = max(0.0, float(wall_s))
    measured = sum(cats.values())
    if measured > wall > 0.0:
        scale = wall / measured
        for c in cats:
            cats[c] *= scale
        measured = wall
    cats["unattributed"] = max(0.0, wall - measured)
    cats = {c: round(v, 6) for c, v in cats.items()}
    return {
        "wall_s": round(wall, 6),
        "categories": cats,
        "unattributed_s": cats["unattributed"],
        "unattributed_frac": (
            round(cats["unattributed"] / wall, 6) if wall > 0 else 0.0
        ),
    }


def prometheus_lines() -> List[str]:
    """Spine gauges for the worker ``/metrics`` endpoint
    (profiler/comm.py): cumulative seconds per span kind plus the last
    drained step-time digest window."""
    lines: List[str] = []
    kinds = trace_ring.kind_seconds()
    if kinds:
        lines.append("# TYPE dlrover_tpu_trace_seconds_total gauge")
        for kind in sorted(kinds):
            lines.append(
                f'dlrover_tpu_trace_seconds_total{{kind="{kind}"}} '
                f"{kinds[kind]:.6f}"
            )
    from dlrover_tpu.observability.digest import last_window

    d = last_window()
    if d:
        lines.append("# TYPE dlrover_tpu_step_time_seconds gauge")
        for stat in ("mean", "p50", "p95", "max"):
            key = f"{stat}_s"
            if key in d:
                lines.append(
                    f'dlrover_tpu_step_time_seconds{{stat="{stat}"}} '
                    f"{float(d[key]):.6f}"
                )
        lines.append(
            f"dlrover_tpu_step_window_steps {int(d.get('count', 0))}"
        )
    return lines
