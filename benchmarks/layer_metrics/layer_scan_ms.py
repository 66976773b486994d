"""``layer_scan_ms``: see ``layer_scan_ms.json``."""
from benchmarks.harness.step_phases import read_layer_scan as read  # noqa: F401
