"""``mtp_ms``: see ``mtp_ms.json``. Also logs what the family's gauges
say of the build and the step's device milliseconds scope by scope (the
operator's; the line carries neither)."""

from benchmarks.harness import hlo_scopes, program_spans

_GAUGES = ("mla.", "attn.", "hc.", "moe.", "mtp.", "fused_ce.", "step.hbm_")
_SCOPES = ("mla_proj", "attention_fwd", "attention_bwd", "hc_coeff", "hc_mix",
           "moe_route", "moe_dispatch", "moe_experts", "moe_combine",
           "moe_shared", "mtp", "embed_lookup", "fused_ce_fwd",
           "fused_ce_bwd")


def read(spec, ctx):
    gauges = program_spans._program_table("gauges")
    ctx.log("gauges: " + " ".join(
        f"{name}={value:.6g}" for name, value in sorted(gauges.items())
        if name.startswith(_GAUGES)))
    by_scope = {
        scope: hlo_scopes.scoped_ms_per_step({"scopes": [scope]}, ctx)
        for scope in _SCOPES
    }
    ctx.log("ms a step by scope: " + " ".join(
        f"{scope}={ms:.3f}" for scope, ms in by_scope.items()
        if ms is not None))
    return by_scope["mtp"]
