"""``lag_full_flash_roofline``: see ``lag_full_flash_roofline.json``."""
from benchmarks.harness.laguna_flops import read_flash_roofline as read  # noqa: F401
