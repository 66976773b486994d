"""``g4h_ssm_chunk_roofline``: see ``g4h_ssm_chunk_roofline.json``."""
from benchmarks.harness.granite_hybrid_flops import read_ssm_chunk_roofline as read  # noqa: F401
