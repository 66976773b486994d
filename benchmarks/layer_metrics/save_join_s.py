"""``save_join_s``: see ``save_join_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
