"""``g4h_step_dispatch_ms``: see ``g4h_step_dispatch_ms.json``."""
from benchmarks.harness.program_spans import span_ms_median as read  # noqa: F401
