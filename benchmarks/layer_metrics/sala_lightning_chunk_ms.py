"""``sala_lightning_chunk_ms``: see ``sala_lightning_chunk_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
