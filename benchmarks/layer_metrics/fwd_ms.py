"""``fwd_ms``: see ``fwd_ms.json``."""
from benchmarks.harness.step_phases import read_phase as read  # noqa: F401
