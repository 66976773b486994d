"""``late_steps_pct``: see ``late_steps_pct.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
