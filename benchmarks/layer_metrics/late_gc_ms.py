"""``late_gc_ms``: see ``late_gc_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
