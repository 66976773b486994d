"""``p4f_mamba_ms``: see ``p4f_mamba_ms.json``."""
from benchmarks.harness.phi4flash_flops import read_mamba_ms as read  # noqa: F401
