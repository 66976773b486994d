"""Operations and bytes of the ``dots3`` family's training step, computed
from shapes (``flops.py``, ``moe_flops.py``, ``xing4_flops.py`` and
``smallthinker_flops.py`` have the other families'; this file adds and
changes nothing there), and the readers of its roofline shares.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus attention at each layer kind's own count of
(query, key) pairs and its own widths, plus the indexer. A token passes
through, in every layer, latent attention's five projections at the
layer kind's ranks and **held** heads and the gate; in a full layer the
indexer's three projections; in the dense layer the SwiGLU; in an expert
layer the router, the shared expert and the held experts' share of the
``experts_per_token`` it chose (uniform routing sends ``held /
n_experts`` of a token's choices here); once, the head over the
vocabulary slice.

**The pairs credited are the pairs the model's definition attends
over**: a full layer's queries select ``min(t + 1, index_topk)`` keys
(14,681,088 a head at 8192 positions and top-2048), a window layer's see
``min(t + 1, window)`` (4,071,168 at window 513), whatever blocks the
kernels walk to get them: a kernel that later skips work reads the same
numerator. The indexer scores every causal pair (it has to, to choose),
so its numerator is the causal count. The embedding lookup, rotary, the
norms, the threshold's counting passes, the KL's elementwise work, the
sort, the gathers and whatever rematerialization recomputes are not
credited.
"""

import re

from benchmarks.harness import xing4_flops


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one head under a top-``topk`` causal
    selection: ``sum_t min(t + 1, topk)``. 8192 positions, 2048:
    14,681,088 of 33,558,528 causal."""
    n = min(seq, topk)
    return n * (n + 1) // 2 + (seq - n) * n


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def kinds_of(config: dict):
    """``F`` / ``S`` a layer, first to last, from ``layer_types``."""
    return ["F" if t == "full_attention" else "S"
            for t in config["layer_types"]]


def latent_matmul_params(dim, heads, q_rank, kv_rank, nope, rope, v) -> int:
    """Latent attention's five projections (``xing4_flops.py``'s count)
    and the gate a head."""
    return xing4_flops.attention_matmul_params(
        dim=dim, n_heads=heads, q_lora_rank=q_rank, kv_lora_rank=kv_rank,
        qk_nope_dim=nope, qk_rope_dim=rope, v_head_dim=v) + dim * heads


def layer_matmul_params(config: dict, kind: str) -> int:
    """Attention's matmul parameters of one layer of ``kind`` on this
    chip (the held heads; a full layer's indexer whole)."""
    c, dim = config, config["hidden_size"]
    if kind == "S":
        return latent_matmul_params(
            dim, c["swa_num_attention_heads"], c["swa_q_lora_rank"],
            c["swa_kv_lora_rank"], c["swa_qk_nope_head_dim"],
            c["swa_qk_rope_head_dim"], c["swa_v_head_dim"])
    hi, di = c["index_n_heads"], c["index_head_dim"]
    return latent_matmul_params(
        dim, c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
        c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
    ) + c["q_lora_rank"] * hi * di + dim * di + dim * hi


def active_matmul_params(config: dict) -> float:
    """Matmul parameters one token passes through on this chip."""
    c, dim = config, config["hidden_size"]
    kinds = kinds_of(c)
    dense = c["first_k_dense_replace"]
    n_experts = c.get("published_n_routed_experts", c["n_routed_experts"])
    expert = (
        dim * n_experts                                     # router
        + c["n_shared_experts"] * 3 * dim * c["moe_intermediate_size"]
        + c["num_experts_per_tok"] * c["n_routed_experts"] / n_experts
        * 3 * dim * c["moe_intermediate_size"])
    return (sum(layer_matmul_params(c, k) for k in kinds)
            + dense * 3 * dim * c["intermediate_size"]
            + (len(kinds) - dense) * expert
            + dim * c["vocab_size"])


def attention_flops_per_call(*, batch: int, n_heads: int, qk_dim: int,
                             v_dim: int, pairs: int) -> dict:
    """FLOPs the three flash kernels of one attention call must do over
    ``pairs`` (query, key) pairs a head: forward scores over the q/k
    width and values over the v width; dq scores, dP (v width), dQ; dk/dv
    scores, dV, dP, dK; 2 FLOPs a multiply-add."""
    unit = 2.0 * batch * n_heads * pairs
    return {"fwd": unit * (qk_dim + v_dim),
            "dq": unit * (2 * qk_dim + v_dim),
            "dkv": unit * (2 * qk_dim + 2 * v_dim)}


def index_flops_per_call(*, batch: int, seq: int, heads: int, dim: int
                         ) -> dict:
    """FLOPs of the indexer's two score kernels over the causal pairs:
    ``dsa_index_fwd`` one product a head; ``dsa_index_bwd`` (one kernel
    since PR 57) three: a tile's scores once, then dQ and dK from them
    (d``w`` is a weighted row sum on the VPU and is not credited)."""
    unit = 2.0 * batch * heads * causal_pairs(seq) * dim
    return {"fwd": unit, "bwd": 3 * unit}


def probs_flops_per_call(*, batch: int, n_heads: int, qk_dim: int,
                         pairs: int) -> float:
    """The head-summed probabilities: the scores again over the selected
    pairs."""
    return 2.0 * batch * n_heads * pairs * qk_dim


def _attention(config: dict, kind: str, seq: int):
    """``(heads held, qk width, v width, pairs)`` of a layer kind."""
    c = config
    if kind == "S":
        return (c["swa_num_attention_heads"],
                c["swa_qk_nope_head_dim"] + c["swa_qk_rope_head_dim"],
                c["swa_v_head_dim"],
                selected_pairs(seq, c["sliding_window_size"]))
    return (c["num_attention_heads"],
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"],
            selected_pairs(seq, c["index_topk"]))


def flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: 6 x the
    matmul parameters; a layer's attention over its kind's pairs, forward
    once and backward at twice that; a full layer's indexer over the
    causal pairs (its forward, and its backward at twice that, which the
    indexer's own loss needs) and the probabilities it learns from."""
    total = 6.0 * active_matmul_params(config)
    for kind in kinds_of(config):
        heads, qk, v, pairs = _attention(config, kind, seq)
        total += 3.0 * 2 * heads * pairs * (qk + v) / seq
        if kind == "F":
            hi, di = config["index_n_heads"], config["index_head_dim"]
            total += 3.0 * 2 * hi * causal_pairs(seq) * di / seq
            total += 2.0 * heads * pairs * qk / seq
    return total


# ---------------------------------------------------------------------------
# Readers (layer_metrics/d3_*.py)
# ---------------------------------------------------------------------------

def _suffixed(names, suffix: str) -> dict:
    return {k: "^" + name + suffix + r"(\.\d+)?$" for k, name in names.items()}


_FLASH = {"fwd": "attention_fwd", "dq": "attention_bwd_dq",
          "dkv": "attention_bwd_dkv"}
_INDEX = {"fwd": "dsa_index_fwd", "bwd": "dsa_index_bwd"}


def flash_patterns(kind: str) -> dict:
    """The three flash kernels of a layer kind as the device trace names
    them (``attention_fwd_sel.3``)."""
    return _suffixed(_FLASH, "_sel" if kind == "F" else "_swa")


def _share_of_peak(ctx, patterns: dict, flops: dict):
    """The traced calls matching ``patterns`` x ``flops`` of each, over
    their device seconds x the bf16 peak, in per cent; None where none
    ran."""
    from benchmarks.harness import hlo_scopes, peaks

    per_device = hlo_scopes.matching_ops(ctx, (), list(patterns.values()))
    if per_device is None:
        return None
    lo, hi = ctx.trace.window_ns
    calls = [o for _, ops in per_device for o in ops if o[1] > lo and o[0] < hi]
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    needed = sum(
        flops[k] * sum(1 for o in calls if re.search(pattern, o[2]))
        for k, pattern in patterns.items()) / len(per_device)
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * needed / peak / seconds


def log_index_kernels(ctx, flops: dict) -> None:
    """Log each traced index kernel's own share of the bf16 peak (both
    families' index rooflines read the two kernels together)."""
    for k, pattern in _suffixed(_INDEX, "").items():
        alone = _share_of_peak(ctx, {k: pattern}, flops)
        if alone is not None:
            ctx.log(f"indexer {pattern}: {alone:.2f} % of the bf16 peak "
                    f"({flops[k] / 1e9:.1f} GFLOP a call)")


def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "dots3")


def read_flash_roofline(spec, ctx):
    """``d3_dsa_flash_roofline`` / ``d3_swa_flash_roofline``: one layer
    kind's three kernels over the pairs the definition attends over."""
    if not _is_ours(ctx):
        return None
    params = ctx.cell["params"]
    heads, qk, v, pairs = _attention(
        ctx.config, spec["kind"], int(params["seq"]))
    return _share_of_peak(
        ctx, flash_patterns(spec["kind"]), attention_flops_per_call(
            batch=int(params["batch"]) // len(ctx.devices), n_heads=heads,
            qk_dim=qk, v_dim=v, pairs=pairs))


def read_index_roofline(spec, ctx):
    """``d3_dsa_index_roofline``: the indexer's two score kernels
    (``dsa_index_fwd``, ``dsa_index_bwd``) over the causal pairs; the log
    has each kernel's own share."""
    if not _is_ours(ctx):
        return None
    params, c = ctx.cell["params"], ctx.config
    flops = index_flops_per_call(
        batch=int(params["batch"]) // len(ctx.devices),
        seq=int(params["seq"]), heads=c["index_n_heads"],
        dim=c["index_head_dim"])
    value = _share_of_peak(ctx, _suffixed(_INDEX, ""), flops)
    if value is not None:
        log_index_kernels(ctx, flops)
    return value
