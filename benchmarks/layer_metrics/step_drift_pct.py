"""``step_drift_pct``: see ``step_drift_pct.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
