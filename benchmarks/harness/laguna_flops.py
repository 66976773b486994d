"""Operations of the ``laguna`` family's training step, computed from
shapes (``flops.py`` has the dense decoder's, ``moe_flops.py`` the
grouped products'; this file adds and changes nothing there), and the
readers of its per-layer metrics (``lag_*``).

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus attention at each layer's own count of
(query, key) pairs and its own query heads. A token passes through, in
every layer, the four attention projections **at the layer's head
count** and the gate's ``hidden x heads``; in a dense layer the SwiGLU;
in a sparse layer the router, the shared expert and the held experts'
share of the ``num_experts_per_tok`` it chose (uniform routing sends
``held / published`` of a token's choices here: an expert on another
chip does no work on this one); once, the sliced head. The embedding
lookup, both rotaries, the norms, the gate's sigmoid and its pass over
the heads' outputs, the sort, the gathers and whatever
rematerialization recomputes are not credited.
"""

import re

from benchmarks.harness.smallthinker_flops import (
    attention_flops_per_call,
    band_pairs,
    kernel_patterns,
)

FULL, WINDOW = "full_attention", "sliding_attention"


def sizes_of(config: dict) -> dict:
    """What the functions below read of a configuration's file."""
    return dict(
        dim=config["hidden_size"], head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        window=config["sliding_window"],
        dense_ffn_dim=config["intermediate_size"],
        expert_ffn_dim=config["moe_intermediate_size"],
        shared_ffn_dim=config["shared_expert_intermediate_size"],
        n_experts=config.get("published_num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
    )


def active_matmul_params(*, dim, head_dim, n_kv_heads, mlp_layer_types,
                         heads_per_layer, dense_ffn_dim, expert_ffn_dim,
                         shared_ffn_dim, n_experts, experts_held,
                         experts_per_token, vocab_size, **_) -> float:
    """Matmul parameters one token passes through on this chip."""
    total = float(dim * vocab_size)
    for heads, mlp in zip(heads_per_layer, mlp_layer_types):
        total += (2 * dim * heads * head_dim          # wq, wo
                  + 2 * dim * n_kv_heads * head_dim   # wk, wv
                  + dim * heads)                      # the gate
        if mlp == "dense":
            total += 3 * dim * dense_ffn_dim
        else:
            total += (dim * n_experts                 # router
                      + 3 * dim * shared_ffn_dim
                      + experts_per_token * experts_held / n_experts
                      * 3 * dim * expert_ffn_dim)
    return total


def expert_flops_per_row(dim: int, ffn_dim: int) -> float:
    """Forward FLOPs of one (token, choice) pair through its expert: the
    three products of a SwiGLU, 2 x ``dim`` x ``ffn_dim`` each."""
    return 2.0 * 3 * dim * ffn_dim


def pairs_of(layer_type: str, seq: int, window: int) -> int:
    return band_pairs(seq, window if layer_type == WINDOW else None)


def attention_flops_per_token(*, seq, head_dim, layer_types,
                              heads_per_layer, window, **_) -> float:
    """A layer's attention does ``pairs / seq`` score and value products
    a token and head, 4 x ``head_dim`` FLOPs each, three times (forward,
    and twice that backward): band pairs on a window layer, causal pairs
    on a full one, at the layer's own heads."""
    return sum(
        3.0 * 4 * head_dim * heads * pairs_of(kind, seq, window) / seq
        for kind, heads in zip(layer_types, heads_per_layer))


def flops_per_token(*, seq: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``."""
    return (6.0 * active_matmul_params(**sizes)
            + attention_flops_per_token(seq=seq, **sizes))


# ---------------------------------------------------------------------------
# The readers. Each returns None off the TPU, for another family's
# configuration and where nothing of its kind ran (the parent: the line
# then leaves the metric out).
# ---------------------------------------------------------------------------

def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "laguna")


def _heads_of(config: dict, layer_type: str) -> int:
    heads = {h for t, h in zip(config["layer_types"],
                               config["num_attention_heads_per_layer"])
             if t == layer_type}
    return heads.pop() if len(heads) == 1 else 0


def _flash_calls(spec, ctx):
    """``(calls in the window, their device seconds, {kernel: pattern},
    devices)`` of one layer kind's three kernels."""
    from benchmarks.harness import hlo_scopes

    patterns = kernel_patterns(spec["kind"] == "window")
    per_device = hlo_scopes.matching_ops(ctx, (), list(patterns.values()))
    if per_device is None:
        return None
    lo, hi = ctx.trace.window_ns
    calls = [o for _, ops in per_device for o in ops
             if o[1] > lo and o[0] < hi]
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    return calls, seconds, patterns, len(per_device)


def read_flash_ms(spec, ctx):
    """``lag_swa_flash_ms``: device milliseconds a step in one layer
    kind's three flash kernels."""
    from benchmarks.harness import trace_reduce

    found = _flash_calls(spec, ctx) if _is_ours(ctx) else None
    steps = trace_reduce.count_spans(ctx.trace, "step") if found else 0
    return found[1] * 1e3 / steps if steps else None


def read_flash_roofline(spec, ctx):
    """``lag_swa_flash_roofline`` / ``lag_full_flash_roofline``: the
    traced calls of one layer kind's three kernels x the FLOPs of the
    pairs under the mask itself at that kind's heads, over their device
    seconds x the bf16 peak. Logs the kernels' own shares."""
    from benchmarks.harness import peaks

    found = _flash_calls(spec, ctx) if _is_ours(ctx) else None
    if found is None:
        return None
    calls, seconds, patterns, devices = found
    config, params = ctx.config, ctx.cell["params"]
    kind = WINDOW if spec["kind"] == "window" else FULL
    heads = _heads_of(config, kind)
    pairs = pairs_of(kind, int(params["seq"]), config["sliding_window"])
    flops = attention_flops_per_call(
        batch=int(params["batch"]) // len(ctx.devices), n_heads=heads,
        head_dim=config["head_dim"], pairs=pairs)
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    needed, own = 0.0, []
    for k, pattern in patterns.items():
        mine = [o for o in calls if re.search(pattern, o[2])]
        needed += flops[k] * len(mine) / devices
        busy = sum(o[1] - o[0] for o in mine) / devices / 1e9
        if busy:
            share = 100.0 * flops[k] * len(mine) / devices / peak / busy
            own.append(f"{k} {len(mine) // devices} calls {share:.1f} %")
    ctx.log(f"laguna {spec['kind']} flash kernels ({heads} heads, {pairs} "
            f"pairs a head): " + "; ".join(own))
    return 100.0 * needed / peak / seconds


def read_experts_roofline(spec, ctx):
    """``lag_moe_experts_roofline``: the traced calls of the grouped
    products x what one call must do over the rows that chose a held
    expert (the job's counter ``live_rows``, a layer's mean) at width
    512, the larger of its FLOPs over the bf16 peak and its bytes over
    the HBM peak, over the calls' device seconds."""
    from benchmarks.harness import hlo_scopes, moe_flops, peaks

    rows = ctx.counters.get("live_rows")
    if not _is_ours(ctx) or not rows:
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
    dim, ffn = config["hidden_size"], config["moe_intermediate_size"]
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    least = max(
        moe_flops.grouped_matmul_flops(rows, dim, ffn)
        / peak["bf16_flops_per_s"],
        moe_flops.grouped_matmul_bytes(rows, dim, ffn, config["num_experts"])
        / peak["hbm_bytes_per_s"],
    )
    return 100.0 * calls * least / seconds
