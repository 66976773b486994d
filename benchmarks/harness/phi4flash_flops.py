"""Operations and bytes of the ``phi4flash`` family's training step,
computed from shapes (the other ``*_flops.py`` files have their families';
this file adds and changes nothing there), the expected first loss under
a tied head behind a LayerNorm, and the readers of the family's ``p4f_*``
metrics.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through, plus attention at each layer's own count of (query,
key) pairs, plus the selective scan. A token passes through, in a Mamba
layer (M), ``W_in``, ``W_x``, ``W_dt`` and ``W_out``; in a window or full
layer (S, F) ``W_qkv`` and ``W_o``; in a gated memory unit (G) ``W_1`` and
``W_2``; in a cross layer (C) ``W_q`` and ``W_o``; in every layer the
SwiGLU; once, the held slice of the table as the head. Attention: band
pairs on S, causal pairs on F and on C (a C layer computes scores and
values over another layer's keys; it has no key projection). The
embedding lookup, the convolution (4 taps a channel), the norms, gates
and softplus and whatever rematerialization recomputes are not credited.

**The scan's count** is of the recurrence itself, ``c n`` state updates a
token: forward five FLOPs an update (the decay times the state, the input
times ``B``, their sum, times ``C``, the sum over the states); backward
fourteen (the state's cotangent gains ``dy C``; ``dC``, ``dB``, ``dx`` /
``ddt`` through ``B``, the decay's own gradient into ``ddt`` and ``dA``,
the cotangent times the decay), the backward's recomputation of the
states not credited. An update's exponential (one forward, one again
backward) is on no roofline here: the reader's log counts them.
"""

import math

from benchmarks.harness.minicpm_sala_flops import _kernel_roofline, _tokens
from benchmarks.harness.smallthinker_flops import (
    attention_flops_per_call,
    band_pairs,
)

SCAN_FLOPS_AN_UPDATE = {"fwd": 5, "bwd": 14}


def sizes_of(config: dict) -> dict:
    """What the functions below read of a configuration's file."""
    mamba = config["assumed"]["mamba"]
    d = config["hidden_size"]
    return dict(
        kinds=config["layer_kinds"], dim=d,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=d // config["num_attention_heads"],
        ffn_dim=config["intermediate_size"],
        channels=mamba["expand"] * d, state=mamba["d_state"],
        dt_rank=-(-d // 16), window=config["sliding_window"],
        vocab_size=config["vocab_size"],
    )


def mixer_matmul_params(kind: str, *, dim, n_heads, n_kv_heads, head_dim,
                        channels, state, dt_rank, **_) -> int:
    """Matmul parameters of one layer's mixer, by kind."""
    if kind == "M":
        return (dim * 2 * channels + channels * (dt_rank + 2 * state)
                + dt_rank * channels + channels * dim)
    if kind in "SF":
        return dim * (n_heads + 2 * n_kv_heads) * head_dim + (
            n_heads * head_dim * dim)
    if kind == "G":
        return 2 * dim * channels
    return 2 * dim * n_heads * head_dim


def active_matmul_params(*, kinds, dim, ffn_dim, vocab_size, **sizes) -> int:
    """Matmul parameters one token passes through on this chip."""
    return (sum(mixer_matmul_params(k, dim=dim, **sizes) for k in kinds)
            + len(kinds) * 3 * dim * ffn_dim + dim * vocab_size)


def pairs_of(kind: str, seq: int, window: int) -> int:
    return band_pairs(seq, window if kind == "S" else None)


def attention_flops_per_token(*, seq, kinds, n_heads, head_dim, window, **_
                              ) -> float:
    """A layer's attention does ``pairs / seq`` score and value products
    a token and head, 4 x ``head_dim`` FLOPs each, three times (forward,
    and twice that backward)."""
    return sum(3.0 * 4 * head_dim * n_heads * pairs_of(k, seq, window) / seq
               for k in kinds if k in "SFC")


def scan_flops_per_token(*, kinds, channels, state, **_) -> float:
    return kinds.count("M") * channels * state * sum(
        SCAN_FLOPS_AN_UPDATE.values())


def flops_per_token(*, seq: int, **sizes) -> float:
    """Model FLOPs per trained token at sequence length ``seq``."""
    return (6.0 * active_matmul_params(**sizes)
            + attention_flops_per_token(seq=seq, **sizes)
            + scan_flops_per_token(**sizes))


def sscan_flops_bytes_per_call(*, tokens: int, channels: int, state: int,
                               chunk: int, itemsize: int = 2) -> dict:
    """``{"fwd": (FLOPs, bytes), "bwd": (FLOPs, bytes)}`` one layer's scan
    needs over ``tokens``. Bytes, at the least: the forward reads ``x``,
    ``B``, ``C`` (``itemsize`` an element) and ``dt`` (float32) and writes
    ``y`` and a float32 state a chunk; the backward reads them, ``y``'s
    cotangent and the states, and writes the gradients of ``x``, ``dt``,
    ``B`` and ``C`` (``dA`` and ``dD`` are a state's size)."""
    updates = tokens * channels * state
    operands = itemsize * (channels + 2 * state) + 4 * channels
    out = itemsize * channels
    states = 4 * channels * state / chunk
    return {
        "fwd": (SCAN_FLOPS_AN_UPDATE["fwd"] * updates,
                tokens * (operands + out + states)),
        "bwd": (SCAN_FLOPS_AN_UPDATE["bwd"] * updates,
                tokens * (operands + out + states + operands)),
    }


def _mean_silu_squared(var: float) -> float:
    """``E[silu(a)^2]`` for ``a ~ N(0, var)``, by Gauss-Hermite."""
    import numpy as np

    nodes, weights = np.polynomial.hermite.hermgauss(96)
    a = math.sqrt(2.0 * var) * nodes
    return float(np.sum(weights * (a / (1.0 + np.exp(-a))) ** 2)
                 / math.sqrt(math.pi))


def residual_variance(config: dict) -> float:
    """The variance of an element of the last residual at the seeded
    init: the table's ``sigma^2 (1 - 1 / d)`` plus what the branches add,
    layer by layer. A projection of ``LN(x)`` has variance ``pre = d
    sigma^2 v / (v + eps)`` where ``x``'s is ``v``; a SwiGLU adds ``F
    E[silu(a)^2] pre sigma_o^2``; a Mamba mixer or a memory unit ``c
    E[t^2] E[silu(z)^2] sigma_o^2`` with ``t = silu`` of the
    convolution's output (variance ``pre / 3`` from four taps uniform
    within 1/2, and 1/12 from its bias) standing for ``m`` (``D`` is 1 and
    the state's term a per cent of it); attention, an average over
    hundreds of keys, adds under a thousandth of a SwiGLU and is left
    out."""
    a = config["assumed"]
    sigma = float(a["initializer_range"])
    sigma_o = float(a.get("out_proj_std", sigma))
    d, ffn = config["hidden_size"], config["intermediate_size"]
    channels = a["mamba"]["expand"] * d
    eps = float(config["layer_norm_eps"])
    v = sigma * sigma * (1 - 1 / d)

    def pre():
        return d * sigma * sigma * v / (v + eps)

    for kind in config["layer_kinds"]:
        if kind in "MG":
            v += (channels * _mean_silu_squared(pre() / 3 + 1 / 12)
                  * _mean_silu_squared(pre()) * sigma_o ** 2)
        v += ffn * _mean_silu_squared(pre()) * pre() * sigma_o ** 2
    return v


def expected_first_loss(config: dict) -> float:
    """What seeded weights give, **which is not ln V**. The head is the
    lookup's table behind a LayerNorm, with no divisor of the logits: at
    this init the branches add little to the residual (``out_proj_std``),
    so ``LN(x_L)`` is the token's own row ``e`` standardised by the last
    residual's variance ``v_L`` (`residual_variance`), and its own id's
    logit is ``d var(e) / sqrt(v_L + eps)``: 50.3 at the published width
    (``d sigma`` is 51.2; ``eps`` of 1e-5 beside a variance of 4e-4 takes
    0.6 and the branches 0.25). Every other id's logit has variance
    ``|LN(x_L)|^2 sigma^2``, the target (the *next* token) among them
    with mean 0: the loss is ``ln(e^own + (V - 1) e^(var / 2))``, less
    ``own / V`` for the targets that are the token itself."""
    sigma = float(config["assumed"]["initializer_range"])
    d, v = config["hidden_size"], config["vocab_size"]
    eps = float(config["layer_norm_eps"])
    var_e, var_x = sigma * sigma * (1 - 1 / d), residual_variance(config)
    own = d * var_e / math.sqrt(var_x + eps)
    var = d * var_x / (var_x + eps) * sigma * sigma
    high = max(own, var / 2)
    return (high + math.log(math.exp(own - high)
                            + (v - 1) * math.exp(var / 2 - high)) - own / v)


# ---------------------------------------------------------------------------
# Readers (layer_metrics/p4f_*.py). Each returns None off the TPU, for
# another family's configuration and where nothing of its kind ran (the
# parent: the line then leaves the metric out).
# ---------------------------------------------------------------------------

_GAUGES = ("mamba.", "attn.", "layers.", "embed.", "fused_ce.", "step.hbm_")
_SCOPES = ("mamba_proj", "mamba_conv", "mamba_xdt", "mamba_scan",
           "mamba_gate", "gmu", "attn_proj", "cross_proj", "dense_mlp",
           "attention_fwd", "attention_bwd", "embed_lookup", "fused_ce_fwd",
           "fused_ce_bwd", "norm")


def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "phi4flash")


def read_mamba_ms(spec, ctx):
    """``p4f_mamba_ms``: the five ``mamba_*`` scopes' device milliseconds
    a step. Also logs what the family's gauges say of the build and the
    step's device milliseconds scope by scope (the operator's; the line
    carries neither; ``dense_mlp``, ``attn_proj`` and the flash kernels'
    readings of this cell are there)."""
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


def read_sscan_roofline(spec, ctx):
    """``p4f_sscan_roofline``: the kernels ``sscan_fwd`` and ``sscan_bwd``
    by name, every traced call at what one call must do, the larger of
    its FLOPs over the bf16 peak and its bytes over the HBM peak (the log
    names the side, and counts the state updates and exponentials)."""
    if not _is_ours(ctx):
        return None
    s = sizes_of(ctx.config)
    tokens = _tokens(ctx)
    per_call = sscan_flops_bytes_per_call(
        tokens=tokens, channels=s["channels"], state=s["state"],
        chunk=int(ctx.config["assumed"]["mamba"]["chunk"]))
    updates = tokens * s["channels"] * s["state"]
    ctx.log(f"sscan: {updates / 1e6:.0f} M state updates a call, each an "
            f"exponential forward ({updates / 1e6:.0f} M) and again "
            f"backward ({2 * updates / 1e6:.0f} M: the recomputed states' "
            "and the walk back's), on the vector and transcendental units "
            "and on no roofline")
    return _kernel_roofline(ctx, "sscan", {
        r"^(jvp_)?sscan_fwd[_.\d]*$": per_call["fwd"],
        r"^sscan_bwd[_.\d]*$": per_call["bwd"]})


def read_flash_roofline(spec, ctx):
    """``p4f_full_flash_roofline`` / ``p4f_swa_flash_roofline``: the
    traced calls of one mask's three flash kernels x the FLOPs of the
    pairs under the mask itself at 40 heads of 64, over their device
    seconds x the bf16 peak. Logs the kernels' own shares."""
    import re

    from benchmarks.harness import peaks
    from benchmarks.harness.laguna_flops import _flash_calls

    found = _flash_calls(spec, ctx) if _is_ours(ctx) else None
    if found is None:
        return None
    calls, seconds, patterns, devices = found
    window = spec["kind"] == "window"
    s, params = sizes_of(ctx.config), ctx.cell["params"]
    pairs = pairs_of("S" if window else "F", int(params["seq"]), s["window"])
    flops = attention_flops_per_call(
        batch=int(params["batch"]) // len(ctx.devices),
        n_heads=s["n_heads"], head_dim=s["head_dim"], pairs=pairs)
    peak = peaks.peaks_for(ctx.devices[0].device_kind)["bf16_flops_per_s"]
    needed, own = 0.0, []
    for k, pattern in patterns.items():
        mine = [o for o in calls if re.search(pattern, o[2])]
        needed += flops[k] * len(mine) / devices
        busy = sum(o[1] - o[0] for o in mine) / devices / 1e9
        if busy:
            share = 100.0 * flops[k] * len(mine) / devices / peak / busy
            own.append(f"{k} {len(mine) // devices} calls {share:.1f} %")
    ctx.log(f"phi4flash {spec['kind']} flash kernels ({s['n_heads']} heads "
            f"of {s['head_dim']}, {pairs} pairs a head): " + "; ".join(own))
    return 100.0 * needed / peak / seconds
