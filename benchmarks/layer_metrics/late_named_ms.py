"""``late_named_ms``: see ``late_named_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
