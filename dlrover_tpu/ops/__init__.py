"""TPU compute ops: norms, rotary, flash attention (Pallas), and two
sequence-parallel strategies (ring, ulysses all-to-all).

Green-field relative to the reference, which owns no kernels (SURVEY.md
§2.8) — its compute path is whatever torch framework it launches.
"""

from dlrover_tpu.ops.attention import flash_attention, mha_reference  # noqa: F401
from dlrover_tpu.ops.chunked_ce import chunked_cross_entropy  # noqa: F401
from dlrover_tpu.ops.fused_ce import (  # noqa: F401
    cross_entropy_sums,
    fused_ce_available,
    fused_ce_enabled,
    fused_cross_entropy,
)
from dlrover_tpu.ops.embedding import embed_lookup  # noqa: F401
from dlrover_tpu.ops.norms import rms_norm  # noqa: F401
from dlrover_tpu.ops.ring_attention import ring_attention  # noqa: F401
from dlrover_tpu.ops.ulysses import ulysses_attention  # noqa: F401
from dlrover_tpu.ops.rotary import (  # noqa: F401
    apply_mrope,
    apply_rope,
    mrope_tables,
    rope_frequencies,
    yarn_frequencies,
    yarn_mscale,
)
