"""``kvl_dsa_flash_roofline``: see ``kvl_dsa_flash_roofline.json``."""
from benchmarks.harness.keye_vl_flops import read_flash_roofline as read  # noqa: F401
