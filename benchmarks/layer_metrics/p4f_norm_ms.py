"""``p4f_norm_ms``: see ``p4f_norm_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
