"""``sala_trainer_idle_ms``: see ``sala_trainer_idle_ms.json``."""
from benchmarks.harness.program_spans import idle_in_span as read  # noqa: F401
