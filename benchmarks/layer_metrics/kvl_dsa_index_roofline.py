"""``kvl_dsa_index_roofline``: see ``kvl_dsa_index_roofline.json``."""
from benchmarks.harness.keye_vl_flops import read_index_roofline as read  # noqa: F401
