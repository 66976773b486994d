"""``d3_dsa_flash_roofline``: see ``d3_dsa_flash_roofline.json``."""
from benchmarks.harness.dots3_flops import read_flash_roofline as read  # noqa: F401
