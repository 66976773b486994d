"""``d3_dense_mlp_ms``: see ``d3_dense_mlp_ms.json``."""
from benchmarks.harness.step_phases import read_scopes as read  # noqa: F401
