"""``lag_swa_flash_ms``: see ``lag_swa_flash_ms.json``."""
from benchmarks.harness.laguna_flops import read_flash_ms as read  # noqa: F401
