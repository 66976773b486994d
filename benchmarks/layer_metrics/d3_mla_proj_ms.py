"""``d3_mla_proj_ms``: see ``d3_mla_proj_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
