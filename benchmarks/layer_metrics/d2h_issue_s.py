"""``d2h_issue_s``: see ``d2h_issue_s.json``."""
from benchmarks.harness.program_spans import counter_seconds_mean as read  # noqa: F401
