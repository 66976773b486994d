"""Unified trace spine: one ``span()`` call, three sinks.

The repo's instruments grew as disjoint ledgers — the compile ledger
(train/warm_compile.py), ResizeLedger (train/live_reshard.py), the comm
ledger (profiler/comm.py), checkpoint restore stats and the native
interposer timeline — each with its own format and its own clock. This
module is the join: every instrument records *typed
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

Garbage collections are spans too (``install_gc_hook()``: kind
``gc_pause``, named ``gc.gen<n>``), in all three sinks, but through no
``Span`` and no lock: a collection can begin while this thread holds
the ring's lock, so the hook keeps its sums to itself and the readers
(``counters()``, ``kind_seconds()``, ``events()``) merge them in.

Step rows
---------
``StepAccount`` is the stepping thread's account of one interval between
two dispatches of the training step: what the wall clock, the thread's
and the process's CPU clocks, the kernel's run queue, the collector and
the program's own spans say happened in it (``StepAccount.close`` lists
the fields). ``step_row()`` keeps the last ``STEP_ROWS_CAP`` rows always
(``step_rows()`` hands out a copy) and, with ``DLROVER_TPU_TRACE`` on,
one ring event of kind ``step`` named ``step_row`` a row. A row much
longer than the running median is *late*, and ``late_account()`` puts
its excess down to a cause (``LATE_CAUSES``); the sums are the counters
``late.<cause>``.

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
emitters in ``ElasticTrainer.step``). A step row is eight host reads
(two clocks, ``getrusage``, one 64-byte ``pread`` of the thread's
``schedstat`` on a descriptor kept open, the collector's sums, the
thread's named seconds, the profiler check) and one locked append, all
of it after the step's dispatch has returned, when the device has its
work and the host waits; a collection costs two clock reads and four
list updates. When
``DLROVER_TPU_TRACE`` is off (the default) nothing is appended to the
ring. Measured: docs/design/observability.md, "Hot-path contract".
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import itertools
import json
import os
import resource
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from dlrover_tpu.common import flags
from dlrover_tpu.common.log import logger

#: the span classification (docs/design/observability.md). ``downtime`` is
#: master-side only (the SpeedMonitor's bracket spans); ``host`` is the
#: catch-all for user spans of no other kind.
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

#: the kinds whose seconds a step row names (``named_s``): the program's
#: own work between two steps, which is no late step
NAMED_KINDS = frozenset((
    "ckpt_save", "ckpt_restore", "eval", "compile", "state_transfer",
    "rendezvous", "input_wait",
))
#: rows the spine keeps (the oldest goes first)
STEP_ROWS_CAP = 4096
#: what a late row's excess is put down to, in this order
LATE_CAUSES = ("named", "gc", "runq", "cpu", "blocked")
_GC_NAMES = ("gc.gen0", "gc.gen1", "gc.gen2")


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
                 "dur", "_nested", "_in_named", "_named", "_t0", "_token",
                 "_annotation")

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
        # the outermost span of a named kind carries the named seconds
        # of all it encloses
        inside = outer is not None and outer._in_named
        self._in_named = inside or self.kind in NAMED_KINDS
        self._named = self._in_named and not inside
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


class _PerThread(threading.local):
    #: seconds of the spans of ``NAMED_KINDS`` closed on this thread
    named = 0.0


class TraceRing:
    """Process-wide span recorder (thread-safe): the always-on counters
    and gauges, and the bounded ring behind ``DLROVER_TPU_TRACE``.

    Ring spans: ``{"kind", "name", "t" (monotonic start, s), "dur" (s),
    "tid", "attrs"?}``. Per-kind cumulative seconds are kept whether or
    not the ring is on and survive its overflow — the attribution
    consumers read those, the timeline consumers read the (windowed)
    spans.
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
        self._thread = _PerThread()
        # the collector's hook writes these and takes no lock (module
        # docstring); collections never overlap, so it is one writer
        self._gc_n = [0, 0, 0]
        self._gc_s = [0.0, 0.0, 0.0]
        self._gc_open = None                # (start, annotation)
        self._gc_events: List[Dict] = []    # not yet in the ring
        self._rows: collections.deque = collections.deque(
            maxlen=STEP_ROWS_CAP)
        self._rows_seen = 0                 # rows that are no edge
        self._base: Optional[Dict] = None   # the running median row

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
        self._drain_gc_events()
        self._events.append(ev)
        if counts_for_kind:
            self._add_kind_seconds(ev["kind"], ev["dur"])
        cap = self.capacity
        if len(self._events) > cap:
            del self._events[: len(self._events) // 2]

    def _add_kind_seconds(self, kind: str, dur: float):
        """Under ``self._lock``."""
        self._kind_seconds[kind] = self._kind_seconds.get(kind, 0.0) + dur

    def _drain_gc_events(self):
        """Under ``self._lock``: what the collector's hook left."""
        while self._gc_events:
            self._events.append(self._gc_events.pop(0))

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
        if sp._named:
            self._thread.named += sp.dur
        with self._lock:
            self._count(sp.name, 1, sp.dur)
            if ev is not None:
                self._append(ev, counts_for_kind=False)
            if not sp._nested:
                self._add_kind_seconds(sp.kind, sp.dur)

    def _count(self, name: str, times: int, seconds: float):
        """Under ``self._lock``."""
        row = self._counters.get(name)
        if row is None:
            self._counters[name] = [times, seconds]
        else:
            row[0] += times
            row[1] += seconds

    def named_seconds(self) -> float:
        """Seconds of the spans of ``NAMED_KINDS`` that have closed on
        the calling thread, since it started."""
        return self._thread.named

    # -- the collector -------------------------------------------------

    def on_gc(self, phase: str, info: Dict):
        """A ``gc.callbacks`` entry (``install_gc_hook``): one span of
        kind ``gc_pause`` a collection, from ``start`` to ``stop``.
        Takes no lock and opens no ``Span`` (module docstring)."""
        gen = min(info["generation"], len(_GC_NAMES) - 1)
        if phase == "start":
            annotation = None
            cls = _profiler_annotation()
            if cls is not None:
                annotation = cls(PROFILER_PREFIX + _GC_NAMES[gen],
                                 kind="gc_pause")
                annotation.__enter__()
            self._gc_open = (time.monotonic(), annotation)
            return
        opened, self._gc_open = self._gc_open, None
        if opened is None:      # installed between the two phases
            return
        t0, annotation = opened
        dur = time.monotonic() - t0
        self._gc_n[gen] += 1
        self._gc_s[gen] += dur
        if annotation is not None:
            annotation.set_metadata(collected=info.get("collected", 0))
            annotation.__exit__(None, None, None)
        if enabled():
            self._gc_events.append(self._event(
                "gc_pause", _GC_NAMES[gen], t0, dur, None,
                {"collected": info.get("collected", 0)}))

    def gc_totals(self) -> Tuple[Tuple[int, ...], Tuple[float, ...]]:
        """Collections and their seconds by generation, process-wide (a
        collection stops every thread that wants the interpreter)."""
        return tuple(self._gc_n), tuple(self._gc_s)

    # -- step rows -----------------------------------------------------

    def step_row(self, row: Dict) -> Dict:
        """Keep one closed interval of the stepping thread
        (``StepAccount.close`` makes it). Sets the row's ``late_s``:
        its excess over the running median row if it is late by
        ``late_account``'s rule, else 0; a late row's excess folds into
        the counters ``late.<cause>`` (times: late rows; seconds: the
        cause's part). An ``edge`` row folds into nothing."""
        ev = None
        if enabled():
            attrs = {k: v for k, v in row.items()
                     if k not in ("t", "interval_s")}
            ev = self._event("step", "step_row", row["t"],
                             row["interval_s"], None, attrs)
        with self._lock:
            self._rows.append(row)
            row["late_s"] = 0.0 if row["edge"] else self._fold_late(row)
            if ev is not None:
                ev.setdefault("attrs", {})["late_s"] = row["late_s"]
                self._append(ev, counts_for_kind=False)
        return row

    #: the running median row is taken over this many of the newest rows
    #: and taken again every ``BASE_EVERY`` rows; no row is judged before
    #: ``BASE_MIN`` have been seen
    BASE_ROWS, BASE_EVERY, BASE_MIN = 128, 64, 8

    def _fold_late(self, row: Dict) -> float:
        """Under ``self._lock``."""
        self._rows_seen += 1
        seen = self._rows_seen
        if seen < self.BASE_MIN:
            return 0.0
        if (self._base is None or seen <= self.BASE_EVERY
                or seen % self.BASE_EVERY == 0):
            self._base = baseline(itertools.islice(
                reversed(self._rows), self.BASE_ROWS))
        account = late_account((row,), self._base)
        if not account["n"]:
            return 0.0
        for cause in LATE_CAUSES:
            self._count("late." + cause, 1, account[cause])
        return sum(account[cause] for cause in LATE_CAUSES)

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
            self._drain_gc_events()
            return [dict(e) for e in self._events]

    def kind_seconds(self) -> Dict[str, float]:
        """Cumulative seconds per span kind, ring on or off."""
        with self._lock:
            out = dict(self._kind_seconds)
        if any(self._gc_n):
            out["gc_pause"] = out.get("gc_pause", 0.0) + sum(self._gc_s)
        return out

    def counters(self) -> Dict[str, Tuple[int, float]]:
        """``{span name: (times closed, seconds in all)}``, a copy."""
        with self._lock:
            out = {k: (v[0], v[1]) for k, v in self._counters.items()}
        for name, n, s in zip(_GC_NAMES, self._gc_n, self._gc_s):
            if n:
                out[name] = (n, s)
        return out

    def step_rows(self) -> List[Dict]:
        """The newest ``STEP_ROWS_CAP`` step rows, oldest first, a copy."""
        with self._lock:
            return [dict(r) for r in self._rows]

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
            self._rows.clear()
            self._rows_seen = 0
            self._base = None
            self._gc_events.clear()
            self._gc_n[:] = [0, 0, 0]
            self._gc_s[:] = [0.0, 0.0, 0.0]

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


def _row_seconds(row: Dict, field: str) -> Optional[float]:
    value = row[field]
    return sum(value) if field == "gc_s" else value


#: a late row's causes that a field of the row measures (what is left
#: is ``blocked``), in ``LATE_CAUSES``' order
_CAUSE_FIELDS = (("named", "named_s"), ("gc", "gc_s"), ("runq", "runq_s"),
                 ("cpu", "cpu_s"))


def _tick(values: List[float]) -> float:
    """The step of a CPU clock that ticks, from its readings: what
    every reading is a whole multiple of, if that is a millisecond or
    more (a sandboxed kernel's thread clock steps by 10 ms); 0 for a
    clock that does not."""
    distinct = sorted({round(v, 6) for v in values})
    if len(distinct) < 2:
        return 0.0
    step = min(b - a for a, b in zip(distinct, distinct[1:]))
    if step < 1e-3:
        return 0.0
    whole = all(abs(v / step - round(v / step)) < 1e-3 for v in distinct)
    return step if whole else 0.0


def baseline(rows: Iterable[Dict]) -> Optional[Dict]:
    """The median row of ``rows``: the median, field by field, of
    ``interval_s``, ``named_s``, ``gc_s`` (all generations), ``runq_s``
    and ``cpu_s`` over the rows that are no ``edge``, and
    ``cpu_tick_s``, the step of the thread's CPU clock where it ticks
    (``_tick``). None without such rows; a field no row measures
    (``runq_s`` without ``schedstat``) reads None."""
    columns: Dict[str, List[float]] = {"interval_s": []}
    columns.update((field, []) for _, field in _CAUSE_FIELDS)
    for row in rows:
        if row["edge"]:
            continue
        for field, column in columns.items():
            value = _row_seconds(row, field)
            if value is not None:
                column.append(value)
    if not columns["interval_s"]:
        return None
    out = {field: statistics.median(column) if column else None
           for field, column in columns.items()}
    out["cpu_tick_s"] = _tick(columns["cpu_s"])
    return out


def late_account(rows: Iterable[Dict], base: Dict) -> Dict:
    """The rule of a late step: rows and their median row
    (``baseline``) in, ``{"n": late rows, <cause>: seconds}`` out.

    With ``m`` the median interval, a row that is no ``edge`` is late
    where ``interval_s - m > max(10 ms, 0.02 m)``. Its excess goes, in
    ``LATE_CAUSES``' order and never more than is left of it, to
    ``named`` (its ``named_s`` over the median row's: a save, an
    evaluation, a wait for input), ``gc``, ``runq`` (runnable, and no
    CPU to run on), ``cpu`` (running: the host's own work; where the
    thread's CPU clock ticks, only what is over the median by more than
    one tick, which is what a reading of such a clock is uncertain by)
    and ``blocked``, which is the rest: the thread neither ran nor
    waited for a CPU, it waited for a completion that came late, and
    that is the device's doing or the runtime's."""
    out: Dict[str, Any] = {"n": 0}
    out.update((cause, 0.0) for cause in LATE_CAUSES)
    m = base["interval_s"]
    for row in rows:
        left = row["interval_s"] - m
        if row["edge"] or left <= max(0.010, 0.02 * m):
            continue
        out["n"] += 1
        for cause, field in _CAUSE_FIELDS:
            mine, usual = _row_seconds(row, field), base[field]
            if mine is None or usual is None:
                continue
            if field == "cpu_s":
                usual += base.get("cpu_tick_s", 0.0)
            part = min(left, max(0.0, mine - usual))
            out[cause] += part
            left -= part
        out["blocked"] += left
    return out


#: the process singleton every emitter records into
trace_ring = TraceRing()


class StepAccount:
    """The stepping thread's account of the interval between two
    dispatches of the training step. ``ElasticTrainer.step`` calls
    ``close`` once its dispatch has returned, inside the ``train_step``
    span: the device has its work by then, so for a loop that fetches
    every loss, as for one that runs ahead, what the account costs is
    not on the step's path. Host reads only, microseconds in all; the
    one file is the thread's ``schedstat``, opened once and read with
    ``pread``. Where the kernel has none, ``runq_s`` is None."""

    _SCHEDSTAT = "/proc/thread-self/schedstat"

    def __init__(self, ring: TraceRing = trace_ring):
        self._ring = ring
        self._fd: Optional[int] = None      # -1: the kernel has no file
        self._fd_thread = None
        self._open: Optional[tuple] = None  # the probes at the last close

    def __del__(self):
        self._close_fd()

    def _close_fd(self):
        if self._fd is not None and self._fd >= 0:
            try:
                os.close(self._fd)
            except Exception:   # the interpreter is shutting down
                pass
        self._fd = None

    def reset(self):
        """Forget the open interval: the next ``close`` opens one and
        closes none (a step build is no step)."""
        self._open = None

    def _runq_s(self) -> Optional[float]:
        """Seconds this thread has been runnable with no CPU to run on
        (``schedstat``'s second field), since it started."""
        thread = threading.get_ident()
        if self._fd_thread != thread:
            # the path names the thread that opens it
            self._close_fd()
            self._fd_thread = thread
            try:
                self._fd = os.open(self._SCHEDSTAT, os.O_RDONLY)
            except OSError:
                self._fd = -1
        if self._fd < 0:
            return None
        try:
            return int(os.pread(self._fd, 64, 0).split()[1]) * 1e-9
        except (OSError, IndexError, ValueError):
            self._close_fd()
            self._fd = -1
            return None

    def _probe(self) -> tuple:
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        return (
            time.monotonic(), usage.ru_utime + usage.ru_stime,
            time.process_time(),
            self._runq_s(), usage.ru_nivcsw, usage.ru_majflt,
            self._ring.gc_totals(), self._ring.named_seconds(),
            profiling(),
        )

    def close(self, step: int, dispatched: Span) -> Optional[Dict]:
        """Close the interval that the last ``close`` opened, open the
        next, and hand the closed one to the spine as a row (None at the
        first call after ``reset``). ``dispatched`` is the open
        ``train_step`` span whose dispatch has just returned. The row:

        - ``step``: the host step whose dispatch closed the interval
          (the device work waited for inside it was the step before);
          ``t``: the interval's start (monotonic); ``interval_s``: from
          one dispatch's return to the next, the whole loop;
          ``dispatch_s``: the closing dispatch, from its span's start;
        - ``named_s``: seconds of the spans of ``NAMED_KINDS`` that
          closed on this thread inside it;
        - ``gc_n``, ``gc_s``: collections and their seconds by
          generation, process-wide;
        - ``cpu_s``: this thread's CPU seconds (``getrusage``'s user
          and system time); ``proc_cpu_s``: the
          process's, every runtime thread with it; ``runq_s``: seconds
          this thread was runnable and had no CPU; ``nivcsw``,
          ``majflt``: its involuntary context switches and major faults;
        - ``traced``: a profiler session was on at both ends; ``edge``:
          at one end only (the interval holds ``start_trace`` or
          ``stop_trace`` and is no step);
        - ``late_s``: ``TraceRing.step_row`` sets it."""
        now = self._probe()
        before, self._open = self._open, now
        if before is None:
            return None
        (t0, cpu0, proc0, runq0, nivcsw0, majflt0, (gc_n0, gc_s0), named0,
         on0) = before
        (t1, cpu1, proc1, runq1, nivcsw1, majflt1, (gc_n1, gc_s1), named1,
         on1) = now
        return self._ring.step_row({
            "step": step,
            "t": t0,
            "interval_s": t1 - t0,
            "dispatch_s": t1 - dispatched._t0,
            "named_s": named1 - named0,
            "gc_n": tuple(b - a for a, b in zip(gc_n0, gc_n1)),
            "gc_s": tuple(b - a for a, b in zip(gc_s0, gc_s1)),
            "cpu_s": cpu1 - cpu0,
            "proc_cpu_s": proc1 - proc0,
            "runq_s": (None if runq0 is None or runq1 is None
                       else runq1 - runq0),
            "nivcsw": nivcsw1 - nivcsw0,
            "majflt": majflt1 - majflt0,
            "traced": int(on0 and on1),
            "edge": int(on0 != on1),
        })


def profiling() -> bool:
    """Whether a profiler session is on (and JAX loaded)."""
    return _profiler_annotation() is not None


def install_gc_hook() -> bool:
    """Make every garbage collection of this process a span of kind
    ``gc_pause`` named ``gc.gen<n>`` (``TraceRing.on_gc``). Idempotent;
    the first ``ElasticTrainer`` and ``bootstrap.init`` call it."""
    if trace_ring.on_gc in gc.callbacks:
        return False
    gc.callbacks.append(trace_ring.on_gc)
    return True


def record(kind: str, name: str, start_mono: float, dur_s: float, **attrs):
    trace_ring.record(kind, name, start_mono, dur_s, **attrs)


span = trace_ring.span
scope = trace_ring.scope
scopes = trace_ring.scopes
gauge = trace_ring.gauge
counters = trace_ring.counters
gauges = trace_ring.gauges
step_rows = trace_ring.step_rows
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
    name (the ``gc.gen<n>`` collections and the ``late.<cause>`` sums
    among them), the gauges, cumulative seconds per span kind plus the
    last drained step-time digest window."""
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
