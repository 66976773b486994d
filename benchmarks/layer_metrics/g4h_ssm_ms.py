"""``g4h_ssm_ms``: see ``g4h_ssm_ms.json``."""
from benchmarks.harness.granite_hybrid_flops import read_ssm_ms as read  # noqa: F401
