"""``p4f_full_flash_roofline``: see ``p4f_full_flash_roofline.json``."""
from benchmarks.harness.phi4flash_flops import read_flash_roofline as read  # noqa: F401
