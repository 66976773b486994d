"""Unified trace spine: one ``span()`` call, three sinks.

The repo's instruments grew as disjoint ledgers — the compile ledger
(train/warm_compile.py), ResizeLedger (train/live_reshard.py), the comm
ledger (profiler/comm.py), checkpoint restore stats, the PyTracer ring
and the native interposer timeline — each with its own format and its
own clock. This module is the join: every instrument records *typed
spans* into one ring with one clock basis, and the ring exports
chrome-trace JSON that merges with every other rank's (and the
interposer's ``/timeline`` dump) into a single perfetto-loadable job
timeline (``python -m dlrover_tpu.profiler.analysis job-timeline``).

Sinks
-----
Every span closed through ``span()`` reaches

- the *counters*, always: a per-name ``(count, seconds)`` table, beside
  the gauges ``gauge(name, value)`` sets and the texts
  ``provide_text(name, producer)`` offers (``step.hlo``: the live step's
  compiled HLO, whose ``op_name`` metadata says which named scope each
  instruction of a device trace came from). ``counters()`` / ``gauges()``
  hand out copies; a reader in the same process (the benchmark's
  per-layer metrics) needs nothing passed to it;
- the *profiler's host plane*, whenever JAX is loaded and a profiler
  session is on: a ``jax.profiler.TraceAnnotation`` named
  ``dlrover/<name>`` with ``kind``, ``id``, ``parent``, ``step`` and the
  span's attributes as stats, in the same ``.xplane.pb`` and on the same
  clock as the device's ``XLA Ops`` line. This module never imports JAX
  (master and agent import it): it finds ``jax.profiler`` in
  ``sys.modules`` or goes without;
- the *ring*, behind ``DLROVER_TPU_TRACE``: the operator's dumps and the
  job timeline, as before, with ``id``, ``parent`` and ``step`` in
  ``attrs``.

Back-dated ``record()`` (an emitter that measured its own duration, the
synthetic resize lane) reaches the ring only.

Device side
-----------
``scope(name)`` is ``span()``'s sibling for what runs on the device: it
returns ``jax.named_scope(name)``, so every instruction traced under it
carries ``name`` in its ``op_name`` metadata (the compiled step's text,
``step.hlo``, is where a reader finds it: a device trace has none), and
it records ``name``. ``scopes()`` hands the names out: a reader that
asks what *no* scope names needs the list of those that are, and a list
kept anywhere else goes stale with the next scope. Trace time only:
nothing of it is on a step's path.

Identity
--------
``id`` is a process-wide counter. ``parent`` is the span that caused
this one: the enclosing span of the same thread (a context variable),
or, for work handed to another thread, the ``cause=<id>`` the caller
passes. ``step`` is the identifier the spans of one piece of work
share (the host step for trainer spans, the checkpoint's step for every
span of one save, on either thread); a span without one inherits its
enclosing span's.

Clock basis
-----------
The profiler stamps its events itself, on the clock of the device
trace. Ring spans are stamped with ``time.monotonic()`` (immune to NTP
steps while the process lives); the ring captures one ``(monotonic,
wallclock)`` pair at construction so exports map every span to absolute
epoch microseconds. Ranks on NTP-synced hosts therefore merge on real
time with no cross-process handshake; the merge CLI re-bases sources
that lack the epoch metadata (interposer dumps) best-effort.

Hot-path contract
-----------------
A span is two clock reads, an inert profiler check and one locked table
update — never a device sync (graftlint JG002 stays green for the
emitters in ``ElasticTrainer.step``). When ``DLROVER_TPU_TRACE`` is off
(the default) nothing is appended to the ring.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger

#: the span classification (docs/design/observability.md). ``downtime`` is
#: master-side only (the SpeedMonitor's bracket spans); ``host`` is the
#: catch-all PyTracer user spans map onto.
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
)

#: what the profiler's host plane calls a span of this module
PROFILER_PREFIX = "dlrover/"


def enabled() -> bool:
    """Spine kill-switch, re-read per call (tests flip it at runtime)."""
    return bool(flags.TRACE.get())


_span_ids = itertools.count(1)
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "dlrover_tpu_span", default=None
)


def _named_scope(name: str):
    """``jax.named_scope(name)`` if the process has loaded JAX, else a
    context that does nothing. Looked up, never imported."""
    cls = getattr(sys.modules.get("jax"), "named_scope", None)
    return contextlib.nullcontext() if cls is None else cls(name)


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` if the process has loaded JAX and
    a profiler session is on, else None. Looked up, never imported."""
    mod = sys.modules.get("jax.profiler")
    cls = getattr(mod, "TraceAnnotation", None)
    if cls is None or not cls.is_enabled():
        return None
    return cls


class Span:
    """One open span; ``with ring.span(...) as sp`` hands it out so the
    block can ``sp.set(bytes=...)`` what it learns and pass ``sp.id`` as
    the ``cause`` of work it hands to another thread. ``dur`` holds the
    seconds once the block has closed."""

    __slots__ = ("_ring", "kind", "name", "attrs", "id", "parent", "step",
                 "dur", "_nested", "_t0", "_token", "_annotation")

    def __init__(self, ring, kind, name, cause, step, attrs):
        self._ring = ring
        self.kind = kind
        self.name = name
        self.attrs = attrs
        self.parent = cause
        self.step = step
        self.dur = 0.0

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        outer = _current_span.get()
        self.id = next(_span_ids)
        # a span inside one of its own kind decomposes it: it adds
        # nothing to the kind's total
        self._nested = outer is not None and outer.kind == self.kind
        if outer is not None:
            if self.parent is None:
                self.parent = outer.id
            if self.step is None:
                self.step = outer.step
        self._token = _current_span.set(self)
        self._annotation = None
        cls = _profiler_annotation()
        if cls is not None:
            stats = {"kind": self.kind, "id": self.id,
                     "parent": self.parent or 0}
            if self.step is not None:
                stats["step"] = self.step
            self._annotation = cls(PROFILER_PREFIX + self.name, **stats)
            self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.dur = time.monotonic() - self._t0
        if self._annotation is not None:
            clean = _clean(self.attrs)
            if clean:
                self._annotation.set_metadata(**clean)
            self._annotation.__exit__(*exc)
        _current_span.reset(self._token)
        self._ring._close(self)
        return False


def _clean(attrs: Dict) -> Dict:
    return {k: v for k, v in attrs.items() if v not in (None, "")}


class TraceRing:
    """Process-wide span recorder (thread-safe): the always-on counters
    and gauges, and the bounded ring behind ``DLROVER_TPU_TRACE``.

    Ring spans: ``{"kind", "name", "t" (monotonic start, s), "dur" (s),
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
        self._counters: Dict[str, List] = {}     # name -> [count, seconds]
        self._gauges: Dict[str, float] = {}
        self._texts: Dict[str, Any] = {}
        self._scopes: set = set()

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
        """Record one completed span in the ring (and nowhere else).
        ``start_mono`` is a ``time.monotonic()`` stamp; emitters that
        already measured a duration call this with their own numbers."""
        if not enabled():
            return
        ev = self._event(kind, name, start_mono, dur_s, tid, attrs)
        with self._lock:
            self._append(ev, counts_for_kind=True)

    @staticmethod
    def _event(kind, name, start_mono, dur_s, tid, attrs) -> Dict[str, Any]:
        ev: Dict[str, Any] = {
            "kind": kind,
            "name": name,
            "t": float(start_mono),
            "dur": max(0.0, float(dur_s)),
            "tid": tid if tid is not None else threading.get_ident() % 100000,
        }
        clean = _clean(attrs)
        if clean:
            ev["attrs"] = clean
        return ev

    def _append(self, ev: Dict, counts_for_kind: bool):
        """Under ``self._lock``."""
        self._events.append(ev)
        if counts_for_kind:
            kind = ev["kind"]
            self._kind_seconds[kind] = (
                self._kind_seconds.get(kind, 0.0) + ev["dur"]
            )
        cap = self.capacity
        if len(self._events) > cap:
            del self._events[: len(self._events) // 2]

    def span(self, kind: str, name: Optional[str] = None, *,
             cause: Optional[int] = None, step: Optional[int] = None,
             **attrs) -> Span:
        """``with trace_ring.span("ckpt_restore", tier="disk") as sp: ...``
        — to all three sinks (module docstring)."""
        return Span(self, kind, name or kind, cause, step, attrs)

    def _close(self, sp: Span):
        ev = None
        if enabled():
            ev = self._event(
                sp.kind, sp.name, sp._t0, sp.dur, None,
                dict(sp.attrs, id=sp.id, parent=sp.parent, step=sp.step),
            )
        with self._lock:
            row = self._counters.get(sp.name)
            if row is None:
                self._counters[sp.name] = [1, sp.dur]
            else:
                row[0] += 1
                row[1] += sp.dur
            if ev is not None:
                self._append(ev, counts_for_kind=not sp._nested)

    def scope(self, name: str):
        """``with trace.scope("dense_mlp"): ...`` around the tracing of
        device work: ``jax.named_scope(name)``, and ``name`` joins
        ``scopes()`` (module docstring, "Device side")."""
        with self._lock:
            self._scopes.add(name)
        return _named_scope(name)

    def gauge(self, name: str, value: float) -> None:
        """A fact that is not a duration (bytes of the compiled step's
        peak, bytes the last save staged): the last value set wins."""
        with self._lock:
            self._gauges[name] = float(value)

    def provide_text(self, name: str, producer) -> None:
        """A text too long to keep and rarely wanted (the live step's
        compiled HLO): ``producer()`` makes it when a reader asks, the
        last one set wins, and setting it costs nothing."""
        with self._lock:
            self._texts[name] = producer

    # -- reading -------------------------------------------------------

    def text(self, name: str) -> Optional[str]:
        with self._lock:
            producer = self._texts.get(name)
        return None if producer is None else producer()

    def scopes(self) -> List[str]:
        """The names ``scope()`` has been given in this process, sorted.
        ``clear()`` keeps them: JAX caches what it traced, so a scope
        inside a cached function is not opened a second time."""
        with self._lock:
            return sorted(self._scopes)

    def events(self) -> List[Dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def kind_seconds(self) -> Dict[str, float]:
        """Cumulative seconds per span kind (ring-overflow-proof)."""
        with self._lock:
            return dict(self._kind_seconds)

    def counters(self) -> Dict[str, Tuple[int, float]]:
        """``{span name: (times closed, seconds in all)}``, a copy."""
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self._counters.items()}

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def clear(self):
        with self._lock:
            self._events.clear()
            self._kind_seconds.clear()
            self._counters.clear()
            self._gauges.clear()
            self._texts.clear()

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


span = trace_ring.span
scope = trace_ring.scope
scopes = trace_ring.scopes
gauge = trace_ring.gauge
counters = trace_ring.counters
gauges = trace_ring.gauges
provide_text = trace_ring.provide_text
text = trace_ring.text


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
    totals. Categories sum to
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
    """Spine rows for the worker ``/metrics`` endpoint
    (profiler/comm.py): times closed and cumulative seconds per span
    name, the gauges, cumulative seconds per span kind (ring on) plus
    the last drained step-time digest window."""
    lines: List[str] = []
    spans = trace_ring.counters()
    if spans:
        lines.append("# TYPE dlrover_tpu_span_seconds_total counter")
        lines.append("# TYPE dlrover_tpu_span_count_total counter")
        for name in sorted(spans):
            count, seconds = spans[name]
            lines.append(
                f'dlrover_tpu_span_seconds_total{{name="{name}"}} '
                f"{seconds:.6f}"
            )
            lines.append(
                f'dlrover_tpu_span_count_total{{name="{name}"}} {count}'
            )
    gauge_rows = trace_ring.gauges()
    if gauge_rows:
        lines.append("# TYPE dlrover_tpu_trace_gauge gauge")
        for name in sorted(gauge_rows):
            lines.append(
                f'dlrover_tpu_trace_gauge{{name="{name}"}} '
                f"{gauge_rows[name]:.6g}"
            )
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
