"""``stage_shm_write_s``: see ``stage_shm_write_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
