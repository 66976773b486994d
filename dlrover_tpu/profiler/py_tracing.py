"""Host-side spans for user code, through the trace spine.

Parity: reference ``xpu_timer/python/py_tracing_manager.cc`` +
``py_tracing_loader`` — it intercepts CPython functions (GC, dataloader
fetch) and merges their spans into the kernel timeline. Here both are
spans of ``dlrover_tpu.observability.trace``, always on: garbage
collections through ``trace.install_gc_hook()`` (kind ``gc_pause``,
named ``gc.gen<n>``), the dataloader's fetch through the
``trace.span("input_wait", "dataloader.next")`` that ``train/data.py``
opens. They reach the spine's counters always, the profiler's host plane
in a session and the ring behind ``DLROVER_TPU_TRACE``; the ring's dump
and ``job-timeline`` are the operator's path to a timeline.

What is left here is the name user code knows:
``with py_tracer.span("preprocess"): ...``."""

from __future__ import annotations

from dlrover_tpu.observability import trace

#: categories -> trace-spine span kinds; everything else is a generic
#: host span (docs/design/observability.md)
_CAT_TO_KIND = {"gc": "gc_pause", "dataloader": "input_wait"}


class PyTracer:
    """``py_tracer.span(name, cat)`` is ``trace.span(kind, name)``."""

    @staticmethod
    def span(name: str, cat: str = "host") -> trace.Span:
        return trace.span(_CAT_TO_KIND.get(cat, "host"), name)


py_tracer = PyTracer()
