"""``kvl_hbm_peak_gib``: see ``kvl_hbm_peak_gib.json``."""
from benchmarks.harness.program_spans import gauge as read  # noqa: F401
