"""``q3n_gdn_ms``: see ``q3n_gdn_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
