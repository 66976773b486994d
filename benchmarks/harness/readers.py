"""The general readers of per-layer metrics. A metric's file
(``layer_metrics/<name>.json``) names one of these under ``reader``, or
brings a reader of its own as ``layer_metrics/<name>.py`` with a
function ``read(spec, ctx)``. ``ctx.counters`` is what the job counted,
``ctx.trace`` the reduced trace. A reader that finds nothing to read
returns None and the metric is left out of the line."""

from benchmarks.harness import trace_reduce


def counter(spec, ctx):
    """A number the job counted or clocked itself: ``spec["counter"]``."""
    return ctx.counters.get(spec["counter"])


def trace_ms_per_step(spec, ctx):
    """Milliseconds a step spends in the device operations whose name
    matches ``spec["patterns"]`` (or whose instruction text matches
    ``spec["text_patterns"]``): their seconds in the traced stretch
    over the ``step`` spans in it."""
    steps = trace_reduce.count_spans(ctx.trace, "step")
    seconds = trace_reduce.matching_s(
        ctx.trace, spec["patterns"], spec.get("text_patterns", ()))
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps


def idle_share(spec, ctx):
    """100 x (1 - busy / window) of the traced stretch."""
    busy_s, window_s = trace_reduce.busy_and_window_s(ctx.trace)
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
