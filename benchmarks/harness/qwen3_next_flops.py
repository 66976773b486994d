"""Operations and bytes of the ``qwen3_next`` family's training step,
computed from shapes (``flops.py``, ``moe_flops.py``, ``dots3_flops.py``
and the others have their families'; this file adds and changes nothing
there), and the readers of the family's roofline metrics.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus causal attention over ``seq / 2`` keys in the
gated attention layers, plus the chunked delta rule in the Gated
DeltaNet layers. A token passes through, in a Gated DeltaNet layer,
``W_qkvz``, ``W_ba`` and ``W_o``; in a gated attention layer ``W_q``
(queries and gates), ``W_k``, ``W_v`` and ``W_o``; in every layer the
router over its whole width, the shared expert with its gate and the
held experts' share of the ``num_experts_per_tok`` it chose (uniform
routing sends ``held / published`` of a token's choices here); once, the
head. The embedding lookup, the convolutions (4 taps a channel), the
norms, gates and rotary, the sort, the gathers and whatever
rematerialization recomputes are not credited.

**The chunked rule's count** is of the chunked per-head form
(``ops/kda.py``, second form) as a function of tokens, layers, head
counts, ``dk``, ``dv`` and the chunk alone, so that it reads the same
work whether XLA's ops or a kernel ran it. A chunk of ``C`` rows,
forward: a key head's two ``(C, C)`` products over the pairs on and
under the diagonal; a value head's unit-triangular solve against ``dv +
dk`` columns (``C^2 / 2`` multiply-adds a column), its three whole
products with the ``(dk, dv)`` state (``W_k S``, ``Q S``, ``K^T U``) and
the triangular ``A_qk U``. The backward is twice the forward.
"""

from benchmarks.harness.dots3_flops import (
    _share_of_peak, attention_flops_per_call, causal_pairs)
from benchmarks.harness.smallthinker_flops import kernel_patterns


def kinds_of(config: dict):
    """``"G"`` (Gated DeltaNet) or ``"F"`` (gated attention) of each
    layer, first to last."""
    every = config["full_attention_interval"]
    return ["F" if (i + 1) % every == 0 else "G"
            for i in range(config["num_hidden_layers"])]


def gdn_matmul_params(c: dict) -> int:
    kw = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    vw = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    d = c["hidden_size"]
    return (d * (2 * kw + 2 * vw)                     # W_qkvz
            + d * 2 * c["linear_num_value_heads"]     # W_ba
            + vw * d)                                 # W_o


def gattn_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    return (d * h * 2 * hd                            # W_q: q and gate
            + 2 * d * kvh * hd                        # W_k, W_v
            + h * hd * d)                             # W_o


def expert_matmul_params(c: dict) -> float:
    """The expert layer's, a token: the held experts' share of the
    chosen."""
    d = c["hidden_size"]
    n_experts = c.get("published_num_experts", c["num_experts"])
    return (d * n_experts                                           # router
            + 3 * d * c["shared_expert_intermediate_size"] + d      # + w_s
            + c["num_experts_per_tok"] * c["num_experts"] / n_experts
            * 3 * d * c["moe_intermediate_size"])


def active_matmul_params(c: dict) -> float:
    """Matmul parameters one token passes through on this chip."""
    kinds = kinds_of(c)
    return (kinds.count("G") * gdn_matmul_params(c)
            + kinds.count("F") * gattn_matmul_params(c)
            + len(kinds) * expert_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def gdn_chunk_flops(*, chunk: int, key_heads: int, value_heads: int,
                    dk: int, dv: int) -> float:
    """FLOPs one chunk of one layer needs over all its heads, forward
    (the module docstring's count)."""
    products = 2 * (chunk * (chunk + 1) // 2) * 2 * dk
    solve = chunk * chunk * (dv + dk)
    body = 3 * 2 * chunk * dk * dv + chunk * chunk * dv
    return float(key_heads * products + value_heads * (solve + body))


def gdn_chunk_flops_per_step(*, tokens: int, layers: int, key_heads: int,
                             value_heads: int, dk: int, dv: int,
                             chunk: int) -> float:
    """FLOPs a step's chunked delta rule needs, forward once and
    backward (twice the forward); what remat recomputes is not
    credited."""
    return 3.0 * layers * (tokens / chunk) * gdn_chunk_flops(
        chunk=chunk, key_heads=key_heads, value_heads=value_heads,
        dk=dk, dv=dv)


def gdn_chunk_bytes_per_step(*, tokens: int, layers: int, key_heads: int,
                             value_heads: int, dk: int, dv: int,
                             itemsize: int = 2) -> float:
    """HBM bytes a step's delta rule has to move at the least: the
    forward reads q, k, v (``itemsize`` an element) and g, beta (float32)
    once and writes o; the backward reads them and o's cotangent and
    writes a gradient of each."""
    operands = (itemsize * (2 * key_heads * dk + value_heads * dv)
                + 4 * 2 * value_heads)
    out = itemsize * value_heads * dv
    return float(tokens * layers * ((operands + out)
                                    + (operands + out + operands)))


def _gdn_sizes(c: dict) -> dict:
    return dict(layers=kinds_of(c).count("G"),
                key_heads=c["linear_num_key_heads"],
                value_heads=c["linear_num_value_heads"],
                dk=c["linear_key_head_dim"], dv=c["linear_value_head_dim"])


def flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: causal
    attention does ``seq / 2`` score and value products a token and
    head, 2 x (q/k width + v width) FLOPs each, three times (forward,
    and twice that backward); the delta rule's count does not grow with
    ``seq``."""
    c = config
    attn = 3.0 * kinds_of(c).count("F") * c["num_attention_heads"] * seq * (
        2 * c["head_dim"])
    gdn = gdn_chunk_flops_per_step(
        tokens=1, chunk=int(c["assumed"]["gdn_chunk"]), **_gdn_sizes(c))
    return 6.0 * active_matmul_params(c) + attn + gdn


# ---------------------------------------------------------------------------
# Readers (layer_metrics/q3n_*.py)
# ---------------------------------------------------------------------------

def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "qwen3_next")


def read_gdn_chunk_roofline(spec, ctx):
    """``q3n_gdn_chunk_roofline``: the least time the chip could take for
    the step's chunked rule (the larger of its FLOPs over the bf16 peak
    and its bytes over the HBM peak; the log names the side that binds)
    over the device seconds under ``gdn_chunk``."""
    from benchmarks.harness import hlo_scopes, peaks, trace_reduce

    if not _is_ours(ctx):
        return None
    per_device = hlo_scopes.matching_ops(ctx, spec["scopes"])
    steps = trace_reduce.count_spans(ctx.trace, "step")
    if per_device is None or not steps:
        return None
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if seconds <= 0:
        return None
    params, c = ctx.cell["params"], ctx.config
    tokens = int(params["seq"]) * int(params["batch"]) // len(ctx.devices)
    sizes = _gdn_sizes(c)
    flops = gdn_chunk_flops_per_step(
        tokens=tokens, chunk=int(c["assumed"]["gdn_chunk"]), **sizes)
    moved = gdn_chunk_bytes_per_step(tokens=tokens, **sizes)
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = moved / peak["hbm_bytes_per_s"]
    ctx.log(f"gdn_chunk a step: {flops / 1e9:.1f} GFLOP = "
            f"{by_flops * 1e3:.3f} ms at the bf16 peak, {moved / 1e9:.3f} GB "
            f"= {by_bytes * 1e3:.3f} ms at the HBM peak: "
            f"{'bytes' if by_bytes > by_flops else 'FLOPs'} bind; "
            f"{seconds * 1e3 / steps:.3f} ms traced")
    return 100.0 * steps * max(by_flops, by_bytes) / seconds


def read_gattn_flash_roofline(spec, ctx):
    """``q3n_gattn_flash_roofline``: the traced calls of the three causal
    flash kernels x the FLOPs of the causal pairs at 256 / 256, over
    their device seconds x the bf16 peak (FLOPs bind at these shapes)."""
    if not _is_ours(ctx):
        return None
    params, c = ctx.cell["params"], ctx.config
    return _share_of_peak(
        ctx, kernel_patterns(False), attention_flops_per_call(
            batch=int(params["batch"]) // len(ctx.devices),
            n_heads=c["num_attention_heads"], qk_dim=c["head_dim"],
            v_dim=c["head_dim"], pairs=causal_pairs(int(params["seq"]))))


def read_experts_roofline(spec, ctx):
    """``q3n_moe_experts_roofline``: ``st_moe_experts_roofline``'s reader
    (the traced grouped-product calls x what one call must do over the
    counted live rows, the larger of its FLOPs and its bytes over the
    peaks, over the calls' device seconds), which reads an expert's
    width and the held count under smallthinker's keys: given this
    family's under those names for the length of the call."""
    from benchmarks.harness import smallthinker_flops

    if not _is_ours(ctx):
        return None
    config = ctx.config
    ctx.config = dict(
        config, moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_num_primary_experts=config["num_experts"])
    try:
        return smallthinker_flops.read_experts_roofline(spec, ctx)
    finally:
        ctx.config = config
