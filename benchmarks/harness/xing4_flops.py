"""Operations and bytes of the ``xing4`` family's training step, computed
from shapes (``flops.py`` has the dense decoder's, ``moe_flops.py`` the
sparse-expert decoder's; this file adds and changes nothing there).

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus causal attention at two head widths. A token
passes through, in every block, latent attention's five projections and
the two sublayers' stream-coefficient products; in a dense block the
SwiGLU; in an expert block the router, the shared expert and the held
experts' share of the ``experts_per_token`` it chose (uniform routing
sends ``held / n_experts`` of a token's choices here: an expert on
another chip does no work on this one); once, ``W_eh``; twice, the head
(the main loss and the multi-token loss). The blocks are the dense ones,
the expert ones and the multi-token module's one. The embedding lookups,
the sort, the gathers, the stream mixing's elementwise work, the
Sinkhorn and whatever rematerialization recomputes are not credited.
"""


def attention_matmul_params(*, dim, n_heads, q_lora_rank, kv_lora_rank,
                            qk_nope_dim, qk_rope_dim, v_head_dim) -> int:
    return (
        dim * q_lora_rank                                       # W_qa
        + q_lora_rank * n_heads * (qk_nope_dim + qk_rope_dim)   # W_qb
        + dim * (kv_lora_rank + qk_rope_dim)                    # W_kva
        + kv_lora_rank * n_heads * (qk_nope_dim + v_head_dim)   # W_kvb
        + n_heads * v_head_dim * dim                            # W_o
    )


def active_matmul_params(
    *, n_dense_layers, n_moe_layers, mtp_depth, dim, n_heads, q_lora_rank,
    kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim, dense_ffn_dim,
    expert_ffn_dim, n_experts, experts_held, experts_per_token,
    n_shared_experts, hc_mult, vocab_size,
) -> float:
    """Matmul parameters one token passes through on this chip."""
    every_block = attention_matmul_params(
        dim=dim, n_heads=n_heads, q_lora_rank=q_lora_rank,
        kv_lora_rank=kv_lora_rank, qk_nope_dim=qk_nope_dim,
        qk_rope_dim=qk_rope_dim, v_head_dim=v_head_dim,
    ) + 2 * hc_mult * dim * hc_mult * (hc_mult + 2)             # phi, twice
    dense = every_block + 3 * dim * dense_ffn_dim
    expert = (
        every_block
        + dim * n_experts                                       # router
        + n_shared_experts * 3 * dim * expert_ffn_dim
        + experts_per_token * experts_held / n_experts
        * 3 * dim * expert_ffn_dim
    )
    heads = (1 + mtp_depth) * dim * vocab_size
    return (n_dense_layers * dense + (n_moe_layers + mtp_depth) * expert
            + mtp_depth * 2 * dim * dim + heads)


def flops_per_token(*, seq: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: causal
    attention does ``seq / 2`` score and value products a token and head,
    2 x (qk width + v width) FLOPs each, three times (forward, and twice
    that backward)."""
    blocks = (sizes["n_dense_layers"] + sizes["n_moe_layers"]
              + sizes["mtp_depth"])
    attn = 3.0 * blocks * sizes["n_heads"] * seq * (
        sizes["qk_nope_dim"] + sizes["qk_rope_dim"] + sizes["v_head_dim"])
    return 6.0 * active_matmul_params(**sizes) + attn


def hc_mix_bytes_per_step(*, tokens: int, dim: int, hc_mult: int,
                          sublayers: int, itemsize: int = 2,
                          remat: bool = True) -> float:
    """HBM bytes the stream mixing of a step has to move at the least, in
    units of one ``(tokens, dim)`` slab: a sublayer's forward reads the n
    streams (coefficients and pre-mix come out of one read) and writes
    ``y``: n + 1; reads the streams and ``z`` and writes the new streams:
    2n + 1. Its backward reads ``dX'``, the streams and ``z`` and writes
    ``dz``: 2n + 2; then reads ``dy``, the streams and ``dX'`` again and
    writes ``dX``: 3n + 1. Under remat the forward runs twice. The
    coefficients themselves (n (n + 2) floats a token) are left out."""
    n = hc_mult
    forward = 3 * n + 2
    backward = 5 * n + 3
    units = forward * (2 if remat else 1) + backward
    return float(sublayers) * units * tokens * dim * itemsize


def attention_flops_per_call(*, batch: int, seq: int, n_heads: int,
                             qk_dim: int, v_dim: int) -> dict:
    """FLOPs the three flash kernels of one causal attention call must
    do: forward 2 products (scores over the qk width, values over the v
    width), dq 3 (scores, dP over v, dQ over qk), dk/dv 4 (scores, dV
    and dP over v, dK over qk), each over half the (seq, seq) plane."""
    half = batch * n_heads * seq * seq / 2.0
    return {
        "fwd": 2.0 * half * (qk_dim + v_dim),
        "dq": 2.0 * half * (2 * qk_dim + v_dim),
        "dkv": 2.0 * half * (2 * qk_dim + 2 * v_dim),
    }
