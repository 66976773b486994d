"""``dsa_index_ms``: see ``dsa_index_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
