"""Operations of the ``smallthinker`` family's training step, computed
from shapes (``flops.py`` has the dense decoder's, ``moe_flops.py`` the
sparse-expert decoder's; this file adds and changes nothing there), and
the readers of its roofline shares.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus attention at each layer kind's own count of
(query, key) pairs. A token passes through, in every layer, the four
attention projections, the router and the held experts' share of the
``experts_per_token`` it chose (uniform routing sends ``held /
n_experts`` of a token's choices here: an expert on another chip does no
work on this one); once, the head. The embedding lookup, rotary, the
norms, the sort, the gathers and whatever rematerialization recomputes
are not credited.
"""

import re


def band_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head under the mask itself: query ``i``
    sees key ``j`` iff ``0 <= i - j < window`` (None: every ``j <= i``).
    16384 positions: 134,225,920 causal, 58,722,304 at window 4096."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_call(*, batch: int, n_heads: int, head_dim: int,
                             pairs: int) -> dict:
    """FLOPs the three flash kernels of one attention call must do over
    ``pairs`` (query, key) pairs a head: forward 2 products (scores,
    values), dq 3 (scores, dP, dQ), dk/dv 4 (scores, dV, dP, dK), each 2
    x ``head_dim`` a pair."""
    unit = 2.0 * batch * n_heads * pairs * head_dim
    return {"fwd": 2 * unit, "dq": 3 * unit, "dkv": 4 * unit}


def active_matmul_params(*, n_layers, dim, n_heads, n_kv_heads, head_dim,
                         ffn_dim, n_experts, experts_held, experts_per_token,
                         vocab_size, **_) -> float:
    """Matmul parameters one token passes through on this chip."""
    layer = (
        2 * dim * n_heads * head_dim            # wq, wo
        + 2 * dim * n_kv_heads * head_dim       # wk, wv
        + dim * n_experts                       # router
        + experts_per_token * experts_held / n_experts * 3 * dim * ffn_dim
    )
    return n_layers * layer + dim * vocab_size


def flops_per_token(*, seq: int, window: int, window_layout, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: a
    layer's attention does ``pairs / seq`` score and value products a
    token and head, 4 x ``head_dim`` FLOPs each, three times (forward,
    and twice that backward), ``pairs`` the layer kind's own."""
    pairs = sum(band_pairs(seq, window if w else None) for w in window_layout)
    attn = 3.0 * 4 * sizes["head_dim"] * sizes["n_heads"] * pairs / seq
    return 6.0 * active_matmul_params(**sizes) + attn


# the three kernels of a window call and of a plain one, as the device
# trace names them (ops/attention.py): attention_fwd_swa.3 / attention_fwd.3
_KERNELS = {"fwd": "attention_fwd", "dq": "attention_bwd_dq",
            "dkv": "attention_bwd_dkv"}


def kernel_patterns(window: bool) -> dict:
    tail = r"_swa(\.\d+)?$" if window else r"(\.\d+)?$"
    return {k: "^" + name + tail for k, name in _KERNELS.items()}


def read_flash_roofline(spec, ctx):
    """``swa_flash_roofline`` / ``full_flash_roofline``: the traced calls
    of one layer kind's three kernels x the FLOPs of the pairs under the
    mask, over their device seconds x the bf16 peak. None off the TPU,
    for another family's configuration, and where no such kernel ran."""
    from benchmarks.harness import hlo_scopes, peaks

    config = ctx.config
    if (ctx.devices[0].platform != "tpu"
            or "sliding_window_layout" not in config):
        return None
    window = spec["kind"] == "window"
    patterns = kernel_patterns(window)
    per_device = hlo_scopes.matching_ops(ctx, (), list(patterns.values()))
    if per_device is None:
        return None
    lo, hi = ctx.trace.window_ns
    calls = [o for _, ops in per_device for o in ops if o[1] > lo and o[0] < hi]
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    params = ctx.cell["params"]
    seq = int(params["seq"])
    flops = attention_flops_per_call(
        batch=int(params["batch"]) // len(ctx.devices),
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        pairs=band_pairs(
            seq, config["sliding_window_size"] if window else None))
    needed = sum(
        flops[k] * sum(1 for o in calls if re.search(pattern, o[2]))
        for k, pattern in patterns.items()) / len(per_device)
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * needed / peak / seconds


def read_experts_roofline(spec, ctx):
    """``st_moe_experts_roofline``: the traced calls of the grouped
    products x what one call must do over the rows that chose a held
    expert (the job's counter ``live_rows``, a layer's mean), the larger
    of its FLOPs over the bf16 peak and its bytes over the HBM peak,
    over the calls' device seconds. None off the TPU, for a job that
    counted no live rows, and where no such kernel ran."""
    from benchmarks.harness import hlo_scopes, moe_flops, peaks

    rows = ctx.counters.get("live_rows")
    if ctx.devices[0].platform != "tpu" or not rows:
        return None
    per_device = hlo_scopes.matching_ops(ctx, (), spec["patterns"])
    if per_device is None:
        return None
    lo, hi = ctx.trace.window_ns
    calls = sum(
        sum(1 for o in ops if o[1] > lo and o[0] < hi)
        for _, ops in per_device) / len(per_device)
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    config = ctx.config
    dim, ffn = config["hidden_size"], config["moe_ffn_hidden_size"]
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    least = max(
        moe_flops.grouped_matmul_flops(rows, dim, ffn)
        / peak["bf16_flops_per_s"],
        moe_flops.grouped_matmul_bytes(
            rows, dim, ffn, config["moe_num_primary_experts"])
        / peak["hbm_bytes_per_s"],
    )
    return 100.0 * calls * least / seconds

