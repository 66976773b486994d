"""``g4h_moe_dispatch_ms``: see ``g4h_moe_dispatch_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
