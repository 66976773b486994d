"""``kvl_dsa_probs_roofline``: see ``kvl_dsa_probs_roofline.json``."""
from benchmarks.harness.keye_vl_flops import read_probs_roofline as read  # noqa: F401
