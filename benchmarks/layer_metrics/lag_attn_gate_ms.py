"""``lag_attn_gate_ms``: see ``lag_attn_gate_ms.json``."""
from benchmarks.harness.hlo_scopes import scoped_ms_per_step as read  # noqa: F401
