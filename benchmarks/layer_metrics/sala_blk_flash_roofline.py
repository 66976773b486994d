"""``sala_blk_flash_roofline``: see ``sala_blk_flash_roofline.json``."""
from benchmarks.harness.minicpm_sala_flops import read_flash_roofline as read  # noqa: F401
