"""``step_interval_mean_ms``: see ``step_interval_mean_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
