"""``sala_first_step_host_s``: see ``sala_first_step_host_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
