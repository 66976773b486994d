"""``unscoped_ms``: see ``unscoped_ms.json``."""
from benchmarks.harness.step_phases import read_unscoped as read  # noqa: F401
