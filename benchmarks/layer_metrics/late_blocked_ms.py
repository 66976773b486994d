"""``late_blocked_ms``: see ``late_blocked_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
