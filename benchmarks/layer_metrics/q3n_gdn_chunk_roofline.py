"""``q3n_gdn_chunk_roofline``: see ``q3n_gdn_chunk_roofline.json``."""
from benchmarks.harness.qwen3_next_flops import read_gdn_chunk_roofline as read  # noqa: F401
