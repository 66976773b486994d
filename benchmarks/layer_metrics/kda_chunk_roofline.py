"""``kda_chunk_roofline``: see ``kda_chunk_roofline.json``."""

from benchmarks.harness import (
    hlo_scopes, kimi_linear_flops, peaks, trace_reduce)


def read(spec, ctx):
    if ctx.devices[0].platform != "tpu":
        return None     # a share of a chip that was not there
    linear = ctx.config.get("linear_attn_config")
    per_device = hlo_scopes.matching_ops(ctx, spec["scopes"])
    if per_device is None or linear is None:
        return None
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    steps = trace_reduce.count_spans(ctx.trace, "step")
    if seconds <= 0 or not steps:
        return None
    params = ctx.cell["params"]
    needed = kimi_linear_flops.kda_chunk_flops_per_step(
        tokens=int(params["seq"]) * int(params["batch"]) // len(ctx.devices),
        kda_layers=linear["kda_layers"],
        kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        chunk=int(ctx.config["assumed"]["kda_chunk"]))
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * steps * needed / peak / seconds
