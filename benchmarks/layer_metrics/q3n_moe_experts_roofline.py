"""``q3n_moe_experts_roofline``: see ``q3n_moe_experts_roofline.json``."""
from benchmarks.harness.qwen3_next_flops import read_experts_roofline as read  # noqa: F401
