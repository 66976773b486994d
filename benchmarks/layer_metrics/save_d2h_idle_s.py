"""``save_d2h_idle_s``: see ``save_d2h_idle_s.json``."""
from benchmarks.harness.program_spans import idle_in_span as read  # noqa: F401
