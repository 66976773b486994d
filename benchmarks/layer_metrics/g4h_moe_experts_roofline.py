"""``g4h_moe_experts_roofline``: see ``g4h_moe_experts_roofline.json``."""
from benchmarks.harness.granite_hybrid_flops import read_experts_roofline as read  # noqa: F401
