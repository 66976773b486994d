"""``moe_rows_per_expert``: see ``moe_rows_per_expert.json``."""

from benchmarks.harness import program_spans

_ALSO_LOGGED = ("moe.experts", "moe.top_k", "moe.load_max_over_mean",
                "attn.block_q", "attn.block_k", "step.hbm_peak_bytes")


def read(spec, ctx):
    gauges = program_spans._program_table("gauges")
    ctx.log("gauges: " + " ".join(
        f"{name}={gauges[name]:.6g}" for name in _ALSO_LOGGED
        if name in gauges))
    return program_spans.gauge(spec, ctx)
