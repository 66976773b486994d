"""``g4h_build_lower_s``: see ``g4h_build_lower_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
