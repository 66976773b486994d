"""Operations and bytes of the ``minicpm_sala`` family's training step,
computed from shapes (the other ``*_flops.py`` have their families'; this
file adds and changes nothing there), and the readers of the family's
roofline metrics.

Per trained token, forward and backward: 6 x the matmul parameters a
token passes through (a ``minicpm4`` layer's ``W_q``, ``W_k``, ``W_v``,
its gate's ``W_g`` and ``W_o``; a ``lightning-attn`` layer's five square
projections; every layer's SwiGLU; once, the head over the held slice of
the vocabulary), plus the sparse layers' attention over the pairs their
queries *select*, plus the lightning rule at its chunked count. The
embedding lookup, the norms, rotary, the gates, the choice of blocks (it
has no gradient and is the selection's own cost, `score_flops`) and what
rematerialization recomputes are not credited.

**The selected pairs** do not depend on the weights: past ``dense_len`` a
query with more than ``topk`` blocks behind it sees ``topk - 1`` whole
blocks and its own up to itself, any other every key at or before it. At
16384 positions, blocks of 64 and 64 a query: 58,335,232 of the
134,225,920 causal pairs a head (43.46 %).

**The lightning rule's count** is of the chunked form (``ops/
lightning.py``) as a function of tokens, layers, heads, the head's width
and the chunk alone, so that it reads the same work whether XLA's ops or
the kernels ran it. A chunk of ``C`` rows of a head of ``d``, forward:
the ``(C, C)`` scores and their product with v (``2 C d`` multiply-adds a
row), the row against the ``(d, d)`` state and its own ``k^T v`` into it
(``2 d^2``). The backward is credited at twice the forward.
"""

import math

from benchmarks.harness.dots3_flops import (
    _share_of_peak, attention_flops_per_call, causal_pairs)

KINDS = {"minicpm4": "S", "lightning-attn": "L"}


def kinds_of(config: dict):
    """``"S"`` (``minicpm4``) or ``"L"`` (``lightning-attn``) of each
    layer held, first to last."""
    return [KINDS[m] for m in config["mixer_types"]]


def sparse_config(config: dict) -> dict:
    return config["assumed"]["sparse_config"]


def selected_pairs(seq: int, config: dict) -> int:
    """(query, key) pairs one head of a ``minicpm4`` layer attends over
    at ``seq`` positions (the module docstring's count)."""
    sc = sparse_config(config)
    if seq <= sc["dense_len"]:
        return causal_pairs(seq)
    block, topk = sc["block_size"], sc["topk"]
    pairs = 0
    for first in range(0, seq, block):
        rows = min(block, seq - first)
        chosen = min(first // block + 1, topk)
        pairs += rows * (chosen - 1) * block + rows * (rows + 1) // 2
    return pairs


def sparse_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    return 3 * d * h * hd + 2 * d * kvh * hd          # q, gate, o; k, v


def lightning_matmul_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["lightning_head_dim"]
    return (3 * d * c["lightning_nh"] * hd            # q, gate, o
            + 2 * d * c["lightning_nkv"] * hd)        # k, v


def active_matmul_params(c: dict) -> int:
    """Matmul parameters one token passes through on this chip."""
    kinds = kinds_of(c)
    return (kinds.count("S") * sparse_matmul_params(c)
            + kinds.count("L") * lightning_matmul_params(c)
            + len(kinds) * 3 * c["hidden_size"] * c["intermediate_size"]
            + c["hidden_size"] * c["vocab_size"])


def lightning_chunk_flops(*, tokens: int, heads: int, d: int, chunk: int
                          ) -> dict:
    """FLOPs one layer's chunked rule does over ``tokens``, a kernel: the
    forward's four products, the backward's nine (the scores again, d
    scores, three through each, the state's two and its cotangent's
    two); ``step``: what a step is credited, three forwards."""
    fwd = float(tokens * heads * (4 * chunk * d + 4 * d * d))
    bwd = float(tokens * heads * (10 * chunk * d + 8 * d * d))
    return {"fwd": fwd, "bwd": bwd, "step": 3.0 * fwd}


def lightning_chunk_bytes(*, tokens: int, heads: int, d: int, chunk: int,
                          itemsize: int = 2) -> dict:
    """HBM bytes one layer's rule has to move at the least, a kernel: the
    forward reads q, k, v and writes o (and a float32 state a chunk where
    a backward follows); the backward reads q, k, v, o's cotangent and
    the states and writes three gradients."""
    row = itemsize * heads * d
    states = 4.0 * heads * d * d * tokens / chunk
    return {"fwd": 4.0 * tokens * row + states,
            "bwd": 7.0 * tokens * row + states}


def score_flops(*, seq: int, heads: int, d: int, pooled: int) -> float:
    """The scoring kernel's one product a call: every query head against
    every pooled key it is given (the causal edge is masked, not
    skipped)."""
    return 2.0 * seq * heads * pooled * d


def score_bytes(*, seq: int, heads: int, kv_heads: int, d: int,
                pooled: int, blocks: int, itemsize: int = 2) -> float:
    """q once, the pooled keys once a group, the float32 block scores."""
    return float(itemsize * (seq * heads * d + kv_heads * pooled * d)
                 + 4 * kv_heads * seq * blocks)


def flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs per trained token at sequence length ``seq``: the
    products, the selected pairs a ``minicpm4`` layer (14 x 128 FLOPs a
    pair and head: the forward's two products, the backward's four and
    the one pass over the scores a flash backward cannot do without;
    the kernels' second such pass is recomputation) and the lightning
    rule at three forwards of its chunked count."""
    c = config
    kinds = kinds_of(c)
    pair = 14.0 * c["head_dim"] * c["num_attention_heads"] * selected_pairs(
        seq, c)
    rule = lightning_chunk_flops(
        tokens=1, heads=c["lightning_nh"], d=c["lightning_head_dim"],
        chunk=min(int(c["assumed"]["la_chunk"]), seq))["step"]
    return (6.0 * active_matmul_params(c)
            + kinds.count("S") * pair / seq + kinds.count("L") * rule)


# ---------------------------------------------------------------------------
# Readers (layer_metrics/sala_*.py)
# ---------------------------------------------------------------------------

def _is_ours(ctx) -> bool:
    return (ctx.devices[0].platform == "tpu"
            and ctx.config.get("family") == "minicpm_sala")


def _tokens(ctx) -> int:
    params = ctx.cell["params"]
    return int(params["seq"]) * int(params["batch"]) // len(ctx.devices)


def _kernel_roofline(ctx, what: str, kernels: dict):
    """The traced calls of ``kernels`` (``{name pattern: (FLOPs, bytes) a
    call}``): the least time the chip could take for them (each call the
    larger of its FLOPs over the bf16 peak and its bytes over the HBM
    peak; the log names the side that binds) over their device seconds,
    in per cent. None where none ran."""
    import re

    from benchmarks.harness import hlo_scopes, peaks

    per_device = hlo_scopes.matching_ops(ctx, (), list(kernels))
    if per_device is None:
        return None
    lo, hi = ctx.trace.window_ns
    calls = [o for _, ops in per_device for o in ops
             if o[1] > lo and o[0] < hi]
    seconds = hlo_scopes.seconds_in_window(ctx, per_device)
    if not calls or seconds <= 0:
        return None
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    least = 0.0
    for pattern, (flops, moved) in kernels.items():
        n = sum(1 for o in calls if re.search(pattern, o[2])) / len(per_device)
        by_flops = flops / peak["bf16_flops_per_s"]
        by_bytes = moved / peak["hbm_bytes_per_s"]
        ctx.log(f"{what} {pattern}: {n:g} calls traced, each "
                f"{flops / 1e9:.1f} GFLOP = {by_flops * 1e3:.3f} ms at the "
                f"bf16 peak, {moved / 1e6:.1f} MB = {by_bytes * 1e3:.3f} ms "
                f"at the HBM peak: "
                f"{'bytes' if by_bytes > by_flops else 'FLOPs'} bind")
        least += n * max(by_flops, by_bytes)
    return 100.0 * least / seconds


def read_lightning_chunk_roofline(spec, ctx):
    """``sala_lightning_chunk_roofline``: the kernels ``lightning_fwd`` and
    ``lightning_bwd`` by name, every traced call (remat's forwards ran,
    so they count) at what one call must do."""
    if not _is_ours(ctx):
        return None
    c = ctx.config
    sizes = dict(tokens=_tokens(ctx), heads=c["lightning_nh"],
                 d=c["lightning_head_dim"],
                 chunk=int(c["assumed"]["la_chunk"]))
    flops, moved = lightning_chunk_flops(**sizes), lightning_chunk_bytes(
        **sizes)
    return _kernel_roofline(ctx, "lightning", {
        r"^lightning_fwd(\.\d+)?$": (flops["fwd"], moved["fwd"]),
        r"^lightning_bwd(\.\d+)?$": (flops["bwd"], moved["bwd"])})


def read_score_roofline(spec, ctx):
    """``sala_blk_score_roofline``: the scoring kernel ``blk_score`` by
    name. Its one product and its bytes are what the roofline knows; its
    sixteen softmaxes over a group's pooled keys (an exponential a score:
    seq x heads x pooled of them) ride on the VPU and EUP beside the MXU
    and are what binds in truth, so a small share is expected and the
    log says how many exponentials a call takes."""
    if not _is_ours(ctx):
        return None
    c, sc, seq = ctx.config, sparse_config(ctx.config), _tokens(ctx)
    pooled = (seq - sc["kernel_size"]) // sc["kernel_stride"] + 1
    sizes = dict(seq=seq, heads=c["num_attention_heads"], d=c["head_dim"],
                 pooled=pooled)
    ctx.log(f"blk_score: {seq * sizes['heads'] * pooled / 1e6:.1f} M "
            "exponentials a call, not in the roofline")
    return _kernel_roofline(ctx, "blk_score", {
        r"^blk_score(\.\d+)?$": (score_flops(**sizes), score_bytes(
            **sizes, kv_heads=c["num_key_value_heads"],
            blocks=seq // sc["block_size"]))})


def flash_patterns() -> dict:
    return {k: "^" + name + r"_blk(\.\d+)?$" for k, name in (
        ("fwd", "attention_fwd"), ("dq", "attention_bwd_dq"),
        ("dkv", "attention_bwd_dkv"))}


def read_flash_roofline(spec, ctx):
    """``sala_blk_flash_roofline``: the traced calls of the three ``_blk``
    flash kernels x the FLOPs of the *selected* pairs, over their device
    seconds x the bf16 peak (FLOPs bind at these shapes). The kernels
    walk every causal tile, so this is at most the selected share of what
    the causal kernels reach."""
    if not _is_ours(ctx):
        return None
    params, c = ctx.cell["params"], ctx.config
    return _share_of_peak(
        ctx, flash_patterns(), attention_flops_per_call(
            batch=int(params["batch"]) // len(ctx.devices),
            n_heads=c["num_attention_heads"], qk_dim=c["head_dim"],
            v_dim=c["head_dim"],
            pairs=selected_pairs(int(params["seq"]), c)))


def expected_first_loss(config: dict) -> float:
    """``ln V + d sigma^2 / (2 m^2)``: random weights at sigma give the
    head's logits, of a normed hidden state over ``m = hidden /
    dim_model_base``, a variance of ``hidden sigma^2 / m^2``."""
    std = float(config["assumed"]["initializer_range"])
    m = config["hidden_size"] / config["dim_model_base"]
    return math.log(config["vocab_size"]) + config["hidden_size"] * (
        std / m) ** 2 / 2
