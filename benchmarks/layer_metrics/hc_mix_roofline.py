"""``hc_mix_roofline``: see ``hc_mix_roofline.json``."""

from benchmarks.harness import hlo_scopes, peaks, trace_reduce, xing4_flops


def read(spec, ctx):
    if ctx.devices[0].platform != "tpu":
        return None     # a share of a chip that was not there
    per_device = hlo_scopes.matching_ops(ctx, spec["scopes"])
    if per_device is None or "hc_mult" not in ctx.config:
        return None
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    steps = trace_reduce.count_spans(ctx.trace, "step")
    if seconds <= 0 or not steps:
        return None
    config, params = ctx.config, ctx.cell["params"]
    blocks = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    needed = xing4_flops.hc_mix_bytes_per_step(
        tokens=int(params["seq"]) * int(params["batch"]) // len(ctx.devices),
        dim=config["hidden_size"], hc_mult=config["hc_mult"],
        sublayers=2 * blocks,
        remat=config["assumed"]["remat"] != "off",
    )
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * steps * needed / peak / seconds
