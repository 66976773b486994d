"""Operations and bytes of the ``granite_hybrid`` family's training step,
computed from shapes (the other ``*_flops.py`` files have their families';
this file adds and changes nothing there), the expected first loss under
a tied head, and the readers of the family's ``g4h_*`` metrics.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus causal attention over ``seq / 2`` keys in the
attention layers, plus the chunked scan in the Mamba-2 layers. A token
passes through, in a Mamba layer, ``W_in`` and ``W_out`` at the held
heads' widths; in an attention layer ``W_q``, ``W_k``, ``W_v`` and
``W_o`` at the held heads'; in every layer the router over its whole
width, the shared expert and the held experts' share of the
``num_experts_per_tok`` it chose (uniform routing sends ``held /
published`` of a token's choices here); once, the table as the head. The
embedding lookup, the convolution (4 taps a channel), the norms, gates
and softplus, the sort, the gathers and whatever rematerialization
recomputes are not credited.

**The scan's count** is of the chunked form (``ops/ssd.py``'s module
docstring) as a function of tokens, layers, heads, ``p``, ``n`` and the
chunk alone, so that it reads the same work whichever form ran. A chunk
of ``L`` rows, with ``T = L (L + 1) / 2`` the pairs on and under the
diagonal. Forward: ``C B^T`` once for all heads (``2 n T``); a head's
masked product with ``dt x`` (``2 p T``), its read of the state and the
state's update (``2 L n p`` each). Backward: ``C B^T`` again and the two
products that give ``dB`` and ``dC`` from the heads' summed ``d(C B^T)``
(``3 x 2 n T``); a head's ``dy (dt x)^T`` and ``W^T dy`` (``2 x 2 p T``)
and five whole products with the state or its cotangent (``5 x 2 L n
p``). The exponentials of the masks (``L^2`` a head a chunk, each
direction) are on no roofline here: the reader's log counts them.
"""

import math

from benchmarks.harness.minicpm_sala_flops import _kernel_roofline, _tokens

KINDS = {"mamba": "M", "attention": "A"}


def kinds_of(config: dict):
    """``"M"`` (Mamba-2) or ``"A"`` (attention) of each layer held."""
    return [KINDS[t] for t in config["layer_types"]]


def head_dim(c: dict) -> int:
    """An attention head's width: config.json has no key for it, so it is
    ``hidden_size`` over the published count of heads."""
    return c["hidden_size"] // c.get(
        "published_num_attention_heads", c["num_attention_heads"])


def mamba_sizes(c: dict) -> dict:
    return dict(heads=c["mamba_n_heads"], p=c["mamba_d_head"],
                n=c["mamba_d_state"] * c["mamba_n_groups"],
                chunk=c["mamba_chunk_size"])


def mamba_matmul_params(c: dict) -> int:
    m, d = mamba_sizes(c), c["hidden_size"]
    inner = m["heads"] * m["p"]
    return d * (2 * inner + 2 * m["n"] + m["heads"]) + inner * d


def attention_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kvh * hd


def expert_matmul_params(c: dict) -> float:
    """The expert layer's, a token: the held experts' share of the
    chosen."""
    d = c["hidden_size"]
    published = c.get("published_num_local_experts", c["num_local_experts"])
    return (d * published                                           # router
            + 3 * d * c["shared_intermediate_size"]
            + c["num_experts_per_tok"] * c["num_local_experts"] / published
            * 3 * d * c["intermediate_size"])


def active_matmul_params(c: dict) -> float:
    """Matmul parameters one token passes through on this chip."""
    kinds = kinds_of(c)
    return (kinds.count("M") * mamba_matmul_params(c)
            + kinds.count("A") * attention_matmul_params(c)
            + len(kinds) * expert_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def ssd_chunk_flops(*, tokens: int, heads: int, p: int, n: int, chunk: int
                    ) -> dict:
    """FLOPs one layer's scan needs over ``tokens``: ``fwd`` and ``bwd``
    (the module docstring's count)."""
    pairs = chunk * (chunk + 1) // 2
    whole = 2 * chunk * n * p
    chunks = tokens / chunk
    return {
        "fwd": chunks * (2 * n * pairs + heads * (2 * p * pairs + 2 * whole)),
        "bwd": chunks * (3 * 2 * n * pairs
                         + heads * (2 * 2 * p * pairs + 5 * whole)),
    }


def ssd_chunk_bytes(*, tokens: int, heads: int, p: int, n: int, chunk: int,
                    itemsize: int = 2) -> dict:
    """HBM bytes one layer's scan has to move at the least: the forward
    reads x, B, C (``itemsize`` an element) and dt (float32) and writes y
    and a float32 state a chunk; the backward reads them, y's cotangent
    and the states, and writes a gradient of x, dt, B and C."""
    operands = itemsize * (heads * p + 2 * n) + 4 * heads
    out = itemsize * heads * p
    states = 4 * heads * p * n / chunk
    return {"fwd": tokens * (operands + out + states),
            "bwd": tokens * (operands + out + states + operands)}


def flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: causal
    attention does ``seq / 2`` score and value products a token and head
    at 128 / 128, three times (forward, and twice that backward); the
    scan's count does not grow with ``seq``."""
    c, kinds = config, kinds_of(config)
    attn = 3.0 * kinds.count("A") * c["num_attention_heads"] * seq * (
        2 * head_dim(c))
    scan = ssd_chunk_flops(tokens=1, **mamba_sizes(c))
    return (6.0 * active_matmul_params(c) + attn
            + kinds.count("M") * (scan["fwd"] + scan["bwd"]))


def expected_first_loss(config: dict) -> float:
    """``ln V + var / 2`` **plus the tied term**. At this init the
    branches add next to nothing (``out_proj_std``), so the normed last
    state points along its own token's row ``E_t``: over the other rows
    its logits have variance ``D sigma^2 / l^2`` as under an untied head,
    but its own row's logit is ``sqrt(D) |E_t| / l``, about ``D sigma /
    l`` (5.12 at the published sizes), one large term in every
    position's partition sum: ``ln((V - 1) e^(var / 2) + e^own)``, less
    ``own / V`` for the targets that are the token itself."""
    std = float(config["assumed"]["initializer_range"])
    d, v = config["hidden_size"], config["vocab_size"]
    scaling = float(config["logits_scaling"])
    var = d * (std / scaling) ** 2
    own = d * std / scaling
    return math.log((v - 1) * math.exp(var / 2) + math.exp(own)) - own / v


# ---------------------------------------------------------------------------
# Readers (layer_metrics/g4h_*.py)
# ---------------------------------------------------------------------------

_GAUGES = ("ssm.", "attn.", "layers.", "moe.", "embed.", "fused_ce.",
           "step.hbm_")
_SCOPES = ("ssm_proj", "ssm_conv", "ssm_dt", "ssm_chunk", "ssm_out",
           "attn_proj", "attention_fwd", "attention_bwd", "moe_route",
           "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
           "embed_lookup", "fused_ce_fwd", "fused_ce_bwd", "norm")


def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "granite_hybrid")


def read_ssm_ms(spec, ctx):
    """``g4h_ssm_ms``: the five ``ssm_*`` scopes' device milliseconds a
    step. Also logs what the family's gauges say of the build and the
    step's device milliseconds scope by scope (the operator's; the line
    carries neither)."""
    from benchmarks.harness import hlo_scopes, program_spans

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


def read_ssm_chunk_roofline(spec, ctx):
    """``g4h_ssm_chunk_roofline``: the kernels ``ssd_fwd`` and ``ssd_bwd``
    by name, every traced call (remat's forwards ran, so they count) at
    what one call must do, the larger of its FLOPs over the bf16 peak and
    its bytes over the HBM peak (the log names the side)."""
    if not _is_ours(ctx):
        return None
    sizes = dict(tokens=_tokens(ctx), **mamba_sizes(ctx.config))
    flops, moved = ssd_chunk_flops(**sizes), ssd_chunk_bytes(**sizes)
    ctx.log(f"ssd: {sizes['tokens'] * sizes['chunk'] * sizes['heads'] / 1e6:.0f}"
            " M exponentials of the masks a call, on no roofline")
    return _kernel_roofline(ctx, "ssd", {
        r"^(jvp_)?ssd_fwd[_.\d]*$": (flops["fwd"], moved["fwd"]),
        r"^ssd_bwd[_.\d]*$": (flops["bwd"], moved["bwd"])})


def read_experts_roofline(spec, ctx):
    """``g4h_moe_experts_roofline``: ``st_moe_experts_roofline``'s reader
    (the traced grouped-product calls x what one call must do over the
    counted live rows), which reads an expert's width and the held count
    under smallthinker's keys: given this family's under those names for
    the length of the call."""
    from benchmarks.harness import smallthinker_flops

    if not _is_ours(ctx):
        return None
    config = ctx.config
    ctx.config = dict(
        config, moe_ffn_hidden_size=config["intermediate_size"],
        moe_num_primary_experts=config["num_local_experts"])
    try:
        return smallthinker_flops.read_experts_roofline(spec, ctx)
    finally:
        ctx.config = config
