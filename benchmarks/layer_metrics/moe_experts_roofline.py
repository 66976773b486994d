"""``moe_experts_roofline``: see ``moe_experts_roofline.json``."""

from benchmarks.harness import hlo_scopes, moe_flops, peaks


def read(spec, ctx):
    if ctx.devices[0].platform != "tpu":
        return None     # a share of a chip that was not there
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
    config, params = ctx.config, ctx.cell["params"]
    rows = (int(params["seq"]) * int(params["batch"])
            * config["num_experts_per_tok"] // len(ctx.devices))
    dim, ffn = config["hidden_size"], config["intermediate_size"]
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    least = max(
        moe_flops.grouped_matmul_flops(rows, dim, ffn)
        / peak["bf16_flops_per_s"],
        moe_flops.grouped_matmul_bytes(rows, dim, ffn, config["num_experts"])
        / peak["hbm_bytes_per_s"],
    )
    return 100.0 * calls * least / seconds
