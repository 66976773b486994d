"""``st_attn_proj_ms``: see ``st_attn_proj_ms.json``."""
from benchmarks.harness.step_phases import read_scopes as read  # noqa: F401
