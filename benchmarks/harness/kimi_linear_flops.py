"""Operations of the ``kimi_linear`` family's training step, computed
from shapes (``flops.py`` has the dense decoder's, ``moe_flops.py`` the
sparse-expert decoder's, ``xing4_flops.py`` latent attention's; this
file adds and changes nothing there).

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus causal latent attention in its layers, plus
the chunked delta rule in the KDA layers. A token passes through, in a
KDA layer, the three wide projections, the two low-rank pairs, the step
projection and ``W_o``; in a latent layer ``W_q``, ``W_kva``, ``W_kvb``
and ``W_o``; in a dense layer the SwiGLU; in an expert layer the router,
the shared expert and the held experts' share of the
``experts_per_token`` it chose (uniform routing sends ``held /
n_experts`` of a token's choices here); once, the head. The embedding
lookup, the convolutions (4 taps a channel), the norms and gates, the
sort, the gathers and whatever rematerialization recomputes are not
credited.
"""

SUB = 16      # rows of a sub-block of a chunk, as ops/kda.py


def kda_matmul_params(*, dim, kda_heads, kda_head_dim) -> int:
    wide = kda_heads * kda_head_dim
    return (
        3 * dim * wide                                # W_q, W_k, W_v
        + 2 * (dim * kda_head_dim + kda_head_dim * wide)   # decay, gate
        + dim * kda_heads                             # w_b
        + wide * dim                                  # W_o
    )


def latent_matmul_params(*, dim, n_heads, kv_lora_rank, qk_nope_dim,
                         qk_rope_dim, v_head_dim) -> int:
    return (
        dim * n_heads * (qk_nope_dim + qk_rope_dim)             # W_q
        + dim * (kv_lora_rank + qk_rope_dim)                    # W_kva
        + kv_lora_rank * n_heads * (qk_nope_dim + v_head_dim)   # W_kvb
        + n_heads * v_head_dim * dim                            # W_o
    )


def active_matmul_params(
    *, n_layers, kda_layers, full_attn_layers, n_dense_layers, dim,
    kda_heads, kda_head_dim, n_heads, kv_lora_rank, qk_nope_dim,
    qk_rope_dim, v_head_dim, dense_ffn_dim, expert_ffn_dim, n_experts,
    experts_held, experts_per_token, n_shared_experts, vocab_size, **_,
) -> float:
    """Matmul parameters one token passes through on this chip; the
    sizes are ``models/kimi_linear.py KimiLinearConfig``'s."""
    kda = kda_matmul_params(
        dim=dim, kda_heads=kda_heads, kda_head_dim=kda_head_dim)
    latent = latent_matmul_params(
        dim=dim, n_heads=n_heads, kv_lora_rank=kv_lora_rank,
        qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
        v_head_dim=v_head_dim)
    expert = (
        dim * n_experts                                         # router
        + n_shared_experts * 3 * dim * expert_ffn_dim
        + experts_per_token * experts_held / n_experts
        * 3 * dim * expert_ffn_dim
    )
    return (len(kda_layers) * kda + len(full_attn_layers) * latent
            + n_dense_layers * 3 * dim * dense_ffn_dim
            + (n_layers - n_dense_layers) * expert + dim * vocab_size)


def kda_chunk_flops(*, chunk: int, dk: int, dv: int) -> float:
    """FLOPs the chunked form (``ops/kda.py``) needs for one chunk of
    one head, forward: the two decay products over the sub-blocks on and
    under the diagonal (``n (n + 1) / 2`` of ``n^2``, ``n = chunk /
    SUB``), the unit-triangular solve against ``dv + dk`` columns
    (``chunk^2 / 2`` multiply-adds a column), and the scan's body: ``W_k
    S``, ``(Q e^G) S`` and ``(K e^(G_C - G))^T U`` whole, ``A_qk U``
    under its diagonal."""
    n = chunk // SUB
    products = 2 * (n * (n + 1) // 2) * 2 * SUB * SUB * dk
    solve = chunk * chunk * (dv + dk)
    body = 3 * 2 * chunk * dk * dv + chunk * chunk * dv
    return float(products + solve + body)


def kda_chunk_flops_per_step(*, tokens: int, kda_layers,
                             kda_heads: int, kda_head_dim: int,
                             chunk: int, **_) -> float:
    """FLOPs a step's chunked delta rule needs, forward once and
    backward (twice the forward); what remat recomputes is not
    credited."""
    per_chunk = kda_chunk_flops(chunk=chunk, dk=kda_head_dim,
                                dv=kda_head_dim)
    return 3.0 * len(kda_layers) * kda_heads * (tokens / chunk) * per_chunk


def flops_per_token(*, seq: int, chunk: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: causal
    latent attention does ``seq / 2`` score and value products a token
    and head, 2 x (qk width + v width) FLOPs each, three times (forward,
    and twice that backward); the delta rule's count does not grow with
    ``seq``."""
    attn = 3.0 * len(sizes["full_attn_layers"]) * sizes["n_heads"] * seq * (
        sizes["qk_nope_dim"] + sizes["qk_rope_dim"] + sizes["v_head_dim"])
    kda = kda_chunk_flops_per_step(tokens=1, chunk=chunk, **sizes)
    return 6.0 * active_matmul_params(**sizes) + attn + kda
