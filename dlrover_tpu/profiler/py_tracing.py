"""Python-side tracing: GC pauses + user spans into a chrome-trace ring.

Parity: reference ``xpu_timer/python/py_tracing_manager.cc`` +
``py_tracing_loader`` — it intercepts CPython functions (GC, dataloader
fetch) and merges their spans into the kernel timeline. TPU-natively the
device timeline comes from the PJRT interposer; this module supplies the
host-side spans that explain gaps in it:

- **GC pauses** via ``gc.callbacks`` (a stop-the-world pause during a
  training step is a classic straggler cause);
- **user spans** (``with py_tracer.span("dataloader.next")``) for input
  pipeline / host preprocessing;

both recorded into a bounded ring and exportable as chrome-trace JSON that
can be merged with the interposer's ``/timeline`` dump (same clock basis:
``time.monotonic``)."""

from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
from typing import Dict, List, Optional

from dlrover_tpu.common import flags
from dlrover_tpu.observability import trace

#: PyTracer categories -> trace-spine span kinds: GC pauses and
#: dataloader fetches adopt the spine's classification, everything else is a
#: generic host span (docs/design/observability.md)
_CAT_TO_KIND = {"gc": "gc_pause", "dataloader": "input_wait"}


class PyTracer:
    """Process-wide host-span recorder (bounded ring, thread-safe).

    Capacity and enablement live on the typed flag registry
    (``DLROVER_TPU_PY_TRACING`` / ``DLROVER_TPU_PY_TRACING_CAP``): an
    explicit constructor capacity still wins (tests), but the singleton
    sizes itself from the flag, and ``maybe_start()`` lets any call
    site turn the tracer on without plumbing a constructor knob."""

    def __init__(self, capacity: Optional[int] = None):
        self._events: List[Dict] = []
        self._cap_override = capacity
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._gc_start: Optional[float] = None
        self._gc_installed = False
        self._enabled = False

    @property
    def _cap(self) -> int:
        if self._cap_override is not None:
            return int(self._cap_override)
        return max(16, int(flags.PY_TRACING_CAP.get()))

    # -- lifecycle -----------------------------------------------------

    def start(self):
        self._enabled = True
        if not self._gc_installed:
            gc.callbacks.append(self._on_gc)
            self._gc_installed = True

    def maybe_start(self) -> bool:
        """Start iff the registry asks for it: ``DLROVER_TPU_PY_TRACING``
        or (the spine needs these emitters) ``DLROVER_TPU_TRACE``."""
        if self._enabled:
            return True
        if flags.PY_TRACING.get() or flags.TRACE.get():
            self.start()
            return True
        return False

    def stop(self):
        self._enabled = False
        if self._gc_installed:
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
            self._gc_installed = False

    # -- recording -----------------------------------------------------

    def _now_us(self) -> int:
        return int((time.monotonic() - self._t0) * 1e6)

    def _record(self, name: str, cat: str, start_us: int, dur_us: int):
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": start_us, "dur": dur_us,
            "pid": 1, "tid": threading.get_ident() % 100000,
        }
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._cap:
                del self._events[: len(self._events) // 2]
        # mirror into the unified trace spine (no-op when it is off):
        # GC + user spans adopt the typed-span classification, so one merged
        # job timeline carries them next to step/compile/ckpt spans
        trace.record(
            _CAT_TO_KIND.get(cat, "host"), name,
            self._t0 + start_us / 1e6, dur_us / 1e6,
        )

    def _on_gc(self, phase: str, info: Dict):
        if not self._enabled:
            return
        if phase == "start":
            self._gc_start = self._now_us()
        elif phase == "stop" and self._gc_start is not None:
            start = self._gc_start
            self._gc_start = None
            self._record(
                f"gc.collect(gen{info.get('generation', '?')})",
                "gc", start, self._now_us() - start,
            )

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host"):
        """``with py_tracer.span("dataloader.next"): ...``"""
        if not self._enabled:
            yield
            return
        start = self._now_us()
        try:
            yield
        finally:
            self._record(name, cat, start, self._now_us() - start)

    # -- export --------------------------------------------------------

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> str:
        return json.dumps({"traceEvents": self.events()})

    def dump(self, path: str):
        with open(path, "w") as f:
            f.write(self.chrome_trace())


#: process singleton, mirroring the interposer's per-process TimerManager
py_tracer = PyTracer()
