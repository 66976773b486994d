"""``gc_pause_ms``: see ``gc_pause_ms.json``."""
from benchmarks.harness.step_rows import read  # noqa: F401
