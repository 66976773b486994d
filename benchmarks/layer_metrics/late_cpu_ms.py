"""``late_cpu_ms``: see ``late_cpu_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
