"""What is left of the pre-chip sweep: one function.

``benchmarks/tests/test_flops.py::test_flops_agree_with_bench_py`` loads
this file by path and holds ``benchmarks/harness/flops.py`` to
``_model_flops_per_step``, and ISSUE 29 could not edit ``benchmarks/``.
The next ``benchmark`` issue deletes that test and this file together
(``ROADMAP.md``, Queue 3). The yardstick is ``benchmarks/run.py``.
"""


def _model_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Model FLOPs for one fwd+bwd step: 6*N_matmul*tokens + causal
    attention (QK^T and AV matmuls, fwd 2x + bwd 4x, halved for the
    causal mask). Embedding gather and remat recompute excluded; the
    lm_head term counts the fwd+bwd matmul exactly once."""
    hd = cfg.head_dim
    per_layer = (
        cfg.dim * cfg.n_heads * hd            # wq
        + 2 * cfg.dim * cfg.n_kv_heads * hd   # wk, wv
        + cfg.n_heads * hd * cfg.dim          # wo
        + 3 * cfg.dim * cfg.ffn_dim           # w_gate, w_up, w_down
    )
    n_mm = cfg.n_layers * per_layer + cfg.dim * cfg.vocab_size  # + lm_head
    tokens = batch * seq
    mm = 6.0 * n_mm * tokens
    attn = 6.0 * cfg.n_layers * batch * cfg.n_heads * seq * seq * hd
    return mm + attn
