"""Operations of a sparse-expert decoder's training step and of its
grouped matmuls, computed from shapes (``harness/flops.py`` has the dense
decoder's; this file adds and changes nothing there).

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through — the attention projections, the router, the
``experts_per_token`` chosen experts' three matrices (not all
``n_experts``: an expert a token did not choose does no work for it),
the untied head — plus causal attention as ``flops.py`` counts it. The
embedding lookup, the sort, the gathers and whatever rematerialization
recomputes are not credited.
"""


def moe_decoder_active_matmul_params(
    *, n_layers: int, dim: int, n_heads: int, n_kv_heads: int,
    head_dim: int, ffn_dim: int, n_experts: int, experts_per_token: int,
    vocab_size: int,
) -> int:
    """Matmul parameters one token passes through."""
    per_layer = (
        dim * n_heads * head_dim                # wq
        + 2 * dim * n_kv_heads * head_dim       # wk, wv
        + n_heads * head_dim * dim              # wo
        + dim * n_experts                       # router
        + experts_per_token * 3 * dim * ffn_dim  # gate, up, down of k experts
    )
    return n_layers * per_layer + dim * vocab_size


def moe_decoder_flops_per_token(*, seq: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``."""
    mm = 6.0 * moe_decoder_active_matmul_params(**sizes)
    attn = 6.0 * sizes["n_layers"] * sizes["n_heads"] * seq * sizes["head_dim"]
    return mm + attn


def grouped_matmul_flops(rows: int, dim: int, ffn_dim: int) -> float:
    """One grouped product over ``rows`` (token, choice) pairs between
    the model width and an expert's width: forward (rows, dim) x
    (dim, ffn), d-lhs (rows, ffn) x (ffn, dim) and d-rhs (dim, rows) x
    (rows, ffn) all multiply-add ``rows x dim x ffn`` times, whichever
    expert a row belongs to."""
    return 2.0 * rows * dim * ffn_dim


def grouped_matmul_bytes(rows: int, dim: int, ffn_dim: int, n_experts: int,
                         itemsize: int = 2) -> float:
    """HBM bytes one such product has to move at the least: the rows on
    both sides once and every expert's matrix once."""
    return float(itemsize) * (
        rows * dim + rows * ffn_dim + n_experts * dim * ffn_dim)
