"""Model FLOP/s utilization of the window: operations the forward and
backward passes need per token (``harness/flops.py``; what remat
recomputes is not credited) times the window's tokens per second, over
the chips used times the published bf16 peak of their kind."""

from benchmarks.harness import peaks


def read(spec, ctx):
    if ctx.devices[0].platform != "tpu":
        return None     # a utilization of a chip that was not there
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    c = ctx.counters
    return 100.0 * c["flops_per_token"] * c["tokens_per_s"] / (
        c["chips"] * peak)
