"""``mla_flash_roofline``: see ``mla_flash_roofline.json``."""

import re

from benchmarks.harness import hlo_scopes, peaks, xing4_flops


def read(spec, ctx):
    if ctx.devices[0].platform != "tpu":
        return None     # a share of a chip that was not there
    per_device = hlo_scopes.matching_ops(ctx, (), spec["patterns"])
    if per_device is None or "qk_nope_head_dim" not in ctx.config:
        return None
    lo, hi = ctx.trace.window_ns
    forward = re.compile(spec["patterns"][0])
    calls = [o for _, ops in per_device for o in ops if o[1] > lo and o[0] < hi]
    n_fwd = sum(1 for o in calls if forward.search(o[2])) / len(per_device)
    n_bwd = len(calls) / len(per_device) - n_fwd     # dq and dk/dv calls
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    config, params = ctx.config, ctx.cell["params"]
    flops = xing4_flops.attention_flops_per_call(
        batch=int(params["batch"]) // len(ctx.devices),
        seq=int(params["seq"]), n_heads=config["num_attention_heads"],
        qk_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"])
    needed = n_fwd * flops["fwd"] + n_bwd * (flops["dq"] + flops["dkv"]) / 2
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * needed / peak / seconds
