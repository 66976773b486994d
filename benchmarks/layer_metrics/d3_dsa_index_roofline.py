"""``d3_dsa_index_roofline``: see ``d3_dsa_index_roofline.json``."""
from benchmarks.harness.dots3_flops import read_index_roofline as read  # noqa: F401
