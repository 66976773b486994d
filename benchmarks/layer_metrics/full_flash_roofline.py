"""``full_flash_roofline``: see ``full_flash_roofline.json``."""
from benchmarks.harness.smallthinker_flops import read_flash_roofline as read  # noqa: F401
