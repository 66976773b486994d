"""``bwd_ms``: see ``bwd_ms.json``."""
from benchmarks.harness.step_phases import read_phase as read  # noqa: F401
