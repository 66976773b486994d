"""``sala_lightning_chunk_roofline``: see ``sala_lightning_chunk_roofline.json``."""
from benchmarks.harness.minicpm_sala_flops import read_lightning_chunk_roofline as read  # noqa: F401
