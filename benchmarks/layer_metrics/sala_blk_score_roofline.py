"""``sala_blk_score_roofline``: see ``sala_blk_score_roofline.json``."""
from benchmarks.harness.minicpm_sala_flops import read_score_roofline as read  # noqa: F401
