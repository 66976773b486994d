"""``q3n_gattn_flash_roofline``: see ``q3n_gattn_flash_roofline.json``."""
from benchmarks.harness.qwen3_next_flops import read_gattn_flash_roofline as read  # noqa: F401
