"""``g4h_ssm_chunk_ms``: see ``g4h_ssm_chunk_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
