"""``lag_moe_experts_roofline``: see ``lag_moe_experts_roofline.json``."""
from benchmarks.harness.laguna_flops import read_experts_roofline as read  # noqa: F401
