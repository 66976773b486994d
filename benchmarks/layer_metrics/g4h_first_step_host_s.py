"""``g4h_first_step_host_s``: see ``g4h_first_step_host_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
