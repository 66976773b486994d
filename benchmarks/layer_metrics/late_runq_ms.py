"""``late_runq_ms``: see ``late_runq_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
