"""``p4f_sscan_roofline``: see ``p4f_sscan_roofline.json``."""
from benchmarks.harness.phi4flash_flops import read_sscan_roofline as read  # noqa: F401
