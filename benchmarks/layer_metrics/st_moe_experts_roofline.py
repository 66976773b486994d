"""``st_moe_experts_roofline``: see ``st_moe_experts_roofline.json``."""
from benchmarks.harness.smallthinker_flops import read_experts_roofline as read  # noqa: F401
