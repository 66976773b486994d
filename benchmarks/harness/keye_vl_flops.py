"""Operations and bytes of the ``keye_vl`` family's training step, computed
from shapes (the other ``*_flops.py`` files have the other families';
this file adds and changes nothing there), and the readers of its
roofline shares.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through and whose input takes a gradient (attention's four
projections, the router, the held experts' share of the 8 a token
chose: uniform routing sends ``held / published`` of a token's choices
here; once, the head over the vocabulary slice), 4 x the indexer's three
projections (they read the layer's input under ``stop_gradient``:
forward and the weights' gradient, no input's), plus, a layer:

- attention over the **selected** pairs, ``sum_t min(t + 1, topk)`` a
  head (31,458,304 at 16384 positions and top-2048), at 128 / 128,
  forward once and backward at twice that, whatever the flash kernels'
  masked walk visits to get them (the 134,225,920 causal pairs);
- the indexer over **every causal pair** (it has to score them to
  choose), 16 heads of 64: its forward and, for its own loss, its
  backward at twice that;
- ``dsa_probs`` over the selected pairs: the scores again, once.

Rotary, the norms, the threshold's counting passes, the KL's elementwise
work, the sort, the gathers, the embedding lookup and whatever remat
recomputes are not credited.
"""

from benchmarks.harness import dots3_flops
from benchmarks.harness.dots3_flops import causal_pairs, selected_pairs
from benchmarks.harness.minicpm_sala_flops import _kernel_roofline


def sizes(c: dict) -> dict:
    sa = c["sa_config"]
    return dict(
        dim=c["hidden_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        topk=sa["topk"], ffn=c["moe_intermediate_size"],
        experts=c.get("published_num_experts", c["num_experts"]),
        held=c["num_experts"], per_token=c["num_experts_per_tok"],
        layers=c["num_hidden_layers"], vocab=c["vocab_size"])


def attention_matmul_params(c: dict) -> int:
    z = sizes(c)
    return 2 * z["dim"] * z["head_dim"] * (z["heads"] + z["kv_heads"])


def indexer_matmul_params(c: dict) -> int:
    z = sizes(c)
    return z["dim"] * (z["index_heads"] * z["index_dim"] + z["index_dim"]
                       + z["index_heads"])


def expert_matmul_params(c: dict) -> float:
    """The router and the held experts' share of a token's choices."""
    z = sizes(c)
    return (z["dim"] * z["experts"]
            + z["per_token"] * z["held"] / z["experts"]
            * 3 * z["dim"] * z["ffn"])


def flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq``."""
    z = sizes(config)
    pairs, causal = selected_pairs(seq, z["topk"]), causal_pairs(seq)
    layer = (
        6.0 * (attention_matmul_params(config) + expert_matmul_params(config))
        + 4.0 * indexer_matmul_params(config)
        + 3.0 * 2 * z["heads"] * pairs * 2 * z["head_dim"] / seq
        + 3.0 * 2 * z["index_heads"] * causal * z["index_dim"] / seq
        + 2.0 * z["heads"] * pairs * z["head_dim"] / seq)
    return z["layers"] * layer + 6.0 * z["dim"] * z["vocab"]


def index_bytes_per_call(*, batch: int, seq: int, heads: int, dim: int
                         ) -> dict:
    """HBM bytes the indexer's two score kernels have to move at the
    least: the float32 ``(s, s)`` array's causal half once (the forward
    writes it, the one backward kernel reads its cotangent once), the
    bf16 q, k and float32 w once, and for the backward dq, dk and dw,
    as large, once."""
    half = 4.0 * batch * causal_pairs(seq)
    rows = batch * seq * (2.0 * heads * dim + 2.0 * dim + 4.0 * heads)
    return {"fwd": half + rows, "bwd": half + 2 * rows}


def probs_bytes_per_call(*, batch: int, seq: int, heads: int, kv_heads: int,
                         dim: int) -> float:
    """``dsa_probs`` at the least: the float32 result's causal half
    written once, the int8 mask's read once, q, k and ``lse`` once."""
    return batch * (5.0 * causal_pairs(seq)
                    + seq * (2.0 * dim * (heads + kv_heads) + 4.0 * heads))


# ---------------------------------------------------------------------------
# Readers (layer_metrics/kvl_*.py)
# ---------------------------------------------------------------------------

_SCOPES = ("mrope", "attn_proj", "dsa_index", "dsa_select", "dsa_loss",
           "attention_fwd", "attention_bwd", "moe_route", "moe_dispatch",
           "moe_experts", "moe_combine", "embed_lookup", "fused_ce_fwd",
           "fused_ce_bwd", "norm")
#: the selection's machinery: what the layer runs because it selects
_SELECTION = ("dsa_index", "dsa_select", "dsa_loss", "attention_fwd",
              "attention_bwd")


def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "keye_vl")


def _batch(ctx) -> int:
    return int(ctx.cell["params"]["batch"]) // len(ctx.devices)


def _any_call(name: str) -> str:
    return "^" + name + r"(\.\d+)?$"


def _log_breakdown(ctx) -> None:
    """Log the step's device milliseconds scope by scope, with the
    selection's machinery as a share of their sum (the operator's; since
    PR 58 the line carries the selection's four, the expert layer's and
    the lookup's as ``dsa_*_ms``, ``moe_*_ms`` and ``embed_ms``, which
    read the same scopes through the same function)."""
    from benchmarks.harness import hlo_scopes

    by_scope = {
        scope: hlo_scopes.scoped_ms_per_step({"scopes": [scope]}, ctx)
        for scope in _SCOPES}
    by_scope = {k: v for k, v in by_scope.items() if v is not None}
    ctx.log("ms a step by scope: " + " ".join(
        f"{scope}={ms:.3f}" for scope, ms in by_scope.items()))
    selection = sum(by_scope.get(s, 0.0) for s in _SELECTION)
    ctx.log(f"the selection's machinery ({' + '.join(_SELECTION)}): "
            f"{selection:.3f} ms a step, "
            f"{100 * selection / max(sum(by_scope.values()), 1e-9):.1f} % of "
            "the scoped device time")


def read_index_roofline(spec, ctx):
    """``kvl_dsa_index_roofline``: the indexer's two score kernels
    (``dsa_index_fwd``, ``dsa_index_bwd``) by name over the causal pairs
    at 16 heads of 64; the log has each kernel's calls, work and binding
    side (FLOPs, at these sizes) and its own share of the bf16 peak. Also
    logs the step's breakdown by scope (`_log_breakdown`)."""
    if not _is_ours(ctx):
        return None
    z = sizes(ctx.config)
    shape = dict(batch=_batch(ctx), seq=int(ctx.cell["params"]["seq"]),
                 heads=z["index_heads"], dim=z["index_dim"])
    flops = dots3_flops.index_flops_per_call(**shape)
    moved = index_bytes_per_call(**shape)
    value = _kernel_roofline(ctx, "indexer", {
        _any_call("dsa_index_" + k): (flops[k], moved[k]) for k in flops})
    if value is not None:
        dots3_flops.log_index_kernels(ctx, flops)
        _log_breakdown(ctx)
    return value


def read_probs_roofline(spec, ctx):
    """``kvl_dsa_probs_roofline``: ``dsa_probs`` by name; its product over
    the causal pairs its walk visits, 32 heads of 128 on 4 key heads."""
    if not _is_ours(ctx):
        return None
    z, seq = sizes(ctx.config), int(ctx.cell["params"]["seq"])
    return _kernel_roofline(ctx, "probabilities", {_any_call("dsa_probs"): (
        dots3_flops.probs_flops_per_call(
            batch=_batch(ctx), n_heads=z["heads"], qk_dim=z["head_dim"],
            pairs=causal_pairs(seq)),
        probs_bytes_per_call(
            batch=_batch(ctx), seq=seq, heads=z["heads"],
            kv_heads=z["kv_heads"], dim=z["head_dim"]))})


def read_flash_roofline(spec, ctx):
    """``kvl_dsa_flash_roofline``: the three ``_sel`` kernels over the
    pairs the queries selected; the log has the share over the causal
    pairs the walk visits."""
    if not _is_ours(ctx):
        return None
    z, seq = sizes(ctx.config), int(ctx.cell["params"]["seq"])
    pairs, visited = selected_pairs(seq, z["topk"]), causal_pairs(seq)
    value = dots3_flops._share_of_peak(
        ctx, dots3_flops.flash_patterns("F"),
        dots3_flops.attention_flops_per_call(
            batch=_batch(ctx), n_heads=z["heads"], qk_dim=z["head_dim"],
            v_dim=z["head_dim"], pairs=pairs))
    if value is not None:
        ctx.log(f"_sel kernels: {value:.2f} % of the bf16 peak over the "
                f"{pairs} selected pairs a head (FLOPs bind), "
                f"{value * visited / pairs:.2f} % over the {visited} the "
                "walk visits")
    return value


def read_experts_roofline(spec, ctx):
    """``kvl_moe_experts_roofline``: ``st_moe_experts_roofline``'s reader
    (the traced grouped-product calls x what one call must do over the
    counted live rows), which reads an expert's width and the held count
    under smallthinker's keys: given this family's under those names for
    the length of the call."""
    from benchmarks.harness import smallthinker_flops

    if not _is_ours(ctx):
        return None
    config = ctx.config
    ctx.config = dict(
        config, moe_ffn_hidden_size=config["moe_intermediate_size"],
        moe_num_primary_experts=config["num_experts"])
    try:
        return smallthinker_flops.read_experts_roofline(spec, ctx)
    finally:
        ctx.config = config
