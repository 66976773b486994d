"""Operations a training step needs, computed from shapes.

Copied from ``bench.py:_model_flops_per_step`` (sound arithmetic, see
PERF.md section 3): 6 x matmul parameters x tokens for forward and
backward, plus causal attention (QK^T and AV, forward 2x and backward
4x, halved for the causal mask). The embedding lookup and whatever
rematerialization recomputes are not credited, so a utilization built
on this is conservative.
"""


def dense_decoder_matmul_params(
    *, n_layers: int, dim: int, n_heads: int, n_kv_heads: int,
    head_dim: int, ffn_dim: int, vocab_size: int,
) -> int:
    """Parameters that sit in a matmul of a dense GQA + SwiGLU decoder
    with an untied head (the embedding table is a lookup)."""
    per_layer = (
        dim * n_heads * head_dim            # wq
        + 2 * dim * n_kv_heads * head_dim   # wk, wv
        + n_heads * head_dim * dim          # wo
        + 3 * dim * ffn_dim                 # w_gate, w_up, w_down
    )
    return n_layers * per_layer + dim * vocab_size


def dense_decoder_flops_per_token(*, seq: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``."""
    mm = 6.0 * dense_decoder_matmul_params(**sizes)
    attn = 6.0 * sizes["n_layers"] * sizes["n_heads"] * seq * sizes["head_dim"]
    return mm + attn
