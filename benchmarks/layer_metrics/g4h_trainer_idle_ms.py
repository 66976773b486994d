"""``g4h_trainer_idle_ms``: see ``g4h_trainer_idle_ms.json``."""
from benchmarks.harness.program_spans import idle_in_span as read  # noqa: F401
