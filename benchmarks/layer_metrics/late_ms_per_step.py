"""``late_ms_per_step``: see ``late_ms_per_step.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
