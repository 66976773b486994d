"""``sala_lightning_ms``: see ``sala_lightning_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
