"""``save_snapshot_s``: see ``save_snapshot_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
