"""``kvl_moe_experts_roofline``: see ``kvl_moe_experts_roofline.json``."""
from benchmarks.harness.keye_vl_flops import read_experts_roofline as read  # noqa: F401
