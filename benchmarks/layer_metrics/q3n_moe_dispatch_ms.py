"""``q3n_moe_dispatch_ms``: see ``q3n_moe_dispatch_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
