"""``kda_ms``: see ``kda_ms.json``. Also logs what the family's gauges
say of the build and the step's device milliseconds scope by scope (the
operator's; the line carries neither)."""

from benchmarks.harness import hlo_scopes, program_spans

_GAUGES = ("kda.", "mla.", "attn.", "moe.", "fused_ce.", "step.hbm_")
_SCOPES = ("kda_proj", "kda_conv", "kda_gate", "kda_chunk", "kda_out",
           "mla_proj", "attention_fwd", "attention_bwd", "moe_route",
           "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
           "embed_lookup", "fused_ce_fwd", "fused_ce_bwd")


def read(spec, ctx):
    value = hlo_scopes.scoped_ms_per_step(spec, ctx)
    if value is None:
        return None     # a program without the scopes: nothing to log
    from dlrover_tpu.observability import trace

    gauges = program_spans._program_table("gauges")
    ctx.log("gauges: " + " ".join(
        f"{name}={value_:.6g}" for name, value_ in sorted(gauges.items())
        if name.startswith(_GAUGES))
        + f" layers.pattern={trace.text('layers.pattern')}")
    by_scope = {
        scope: hlo_scopes.scoped_ms_per_step({"scopes": [scope]}, ctx)
        for scope in _SCOPES
    }
    ctx.log("ms a step by scope: " + " ".join(
        f"{scope}={ms:.3f}" for scope, ms in by_scope.items()
        if ms is not None))
    return value
