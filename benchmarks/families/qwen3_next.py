"""The qwen3_next family (``model_type: qwen3_next``,
Qwen3-Next-80B-A3B), as ``dlrover_tpu.models.qwen3_next`` computes it and
as this file's plain reference computes it again.

Layer equations, from the model's config.json and the public
implementation, hidden width ``d``; every block is ``h += Mixer(Norm(h));
h += MoE(Norm(h))``, ``x`` the normed input, ``Norm(x) = x rsqrt(mean x^2
+ eps) (1 + w)``; layer ``i`` is gated attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet:

- Gated DeltaNet, ``hk`` key heads, ``hv`` value heads, value head ``j``
  on key head ``j // (hv / hk)``::

      [q~ | k~ | v~ | z] = x W_qkvz;   [b | a] = x W_ba
      [q^ | k^ | v^] = SiLU(Conv([q~ | k~ | v~]))   depthwise, causal
      q_t = L2norm(q^_t) dk^-1/2;  k_t = L2norm(k^_t);  v_t = v^_t
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
      S' = e^(g_t) S_(t-1);  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t                            S in R^(dk x dv), S_0 = 0
      y = W_o concat_j[RMSNorm_dv(o_t^j) w_n SiLU(z_t^j)]

- gated attention, ``h`` query heads on ``kvh`` key heads of ``hd``:
  ``[q_h | gate_h] = x W_q`` a head; ``k, v = x W_k, x W_v``; ``Norm_hd``
  on q and k a head; rotary (theta, a half against the other) on the
  first ``partial_rotary_factor x hd`` channels; causal softmax at
  ``hd^-1/2``; ``y = W_o concat_h[o_h sigmoid(gate_h)]``.
- expert layer: ``p = softmax(x W_r)`` over ``num_experts`` (published:
  512); the ``k`` largest; ``w = p[chosen] / sum p[chosen]``; ``sum_j w_j
  SwiGLU_j(x) + sigmoid(x w_s) SwiGLU_s(x)``. This chip holds experts
  ``first_expert .. + num_experts - 1`` of ``published_num_experts``: a
  pair that chose another adds nothing.
- loss: mean next-token cross-entropy over ``Norm(h) W_head`` +
  ``router_aux_loss_coef`` x the layers' mean of ``E sum_i f_i P_i``.

What config.json does not say is under ``assumed`` in the configuration.

The reference is float32 at matmul precision "highest": the delta rule
**token by token** (a ``lax.scan`` over time, in rematerialised blocks so
that a vjp at 16384 tokens keeps a state a block), attention by explicit
scores and mask in blocks of queries, the expert layer a loop over the
held experts, each on all tokens. It imports nothing of ``dlrover_tpu``;
what the references share is ``families/xing4.py``'s (norm, SwiGLU,
casts), ``families/kimi_linear.py``'s (the convolution, the L2 norm) and
``families/smallthinker.py``'s (the blocked attention and CE).
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.kimi_linear import _l2_norm, _ref_conv
from benchmarks.families.smallthinker import (
    _ref_attention_core, _ref_ce, _round_trip)
from benchmarks.families.xing4 import (
    _f32, _rms_norm, _row_rel, _shifted, _swiglu)
from benchmarks.harness import qwen3_next_flops
from benchmarks.harness.qwen3_next_flops import kinds_of

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

T_BLOCK = 128      # tokens a rematerialised block of the recurrence


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("model_type", "qwen3_next"), ("rope_scaling", None),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False)):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/qwen3_next.py computes ({want!r})")
    return dict(
        n_layers=config["num_hidden_layers"], dim=config["hidden_size"],
        full_attention_interval=config["full_attention_interval"],
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_size=config["linear_conv_kernel_dim"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        expert_ffn_dim=config["moe_intermediate_size"],
        shared_ffn_dim=config["shared_expert_intermediate_size"],
        n_experts=config.get("published_num_experts", config["num_experts"]),
        experts_held=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        vocab_size=config["vocab_size"],
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import qwen3_next
    from dlrover_tpu.parallel import named_shardings

    sizes = _sizes(config)
    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/qwen3_next.py remats a block or nothing")
    std = float(assumed["initializer_range"])
    cfg = qwen3_next.Qwen3NextConfig(
        **sizes,
        gdn_chunk=int(assumed["gdn_chunk"]),
        first_expert=int(config.get("first_expert", 0)),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        router_aux_coef=float(assumed["router_aux_loss_coef"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        init_std=std,
        out_proj_std=(float(assumed["out_proj_std"])
                      if "out_proj_std" in assumed else None),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = qwen3_next.param_specs(cfg)
    init = jax.jit(
        lambda key: qwen3_next.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs))

    def reference(params, tokens):
        want = reference_pieces(params, tokens, config)
        ok = _compare(cfg, mesh, params, tokens, config, want)
        return want["loss"] if ok else float("nan")

    return types.SimpleNamespace(
        cfg=cfg,
        param_specs=specs,
        init_params=init,
        # jobs/finetune_loop.py: the optimizer the configuration states
        # (arguments of TrainConfig) and the expert layers' live rows
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(
            lambda p, t: qwen3_next.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: qwen3_next.loss_fn(p, t, cfg, mesh),
        param_count=qwen3_next.param_count(cfg),
        flops_per_token=lambda seq: qwen3_next_flops.flops_per_token(
            config, seq),
        # random weights at sigma give logits of variance dim x sigma^2;
        # a balanced router's load-balancing loss is 1
        expected_first_loss=(
            math.log(sizes["vocab_size"]) + sizes["dim"] * std * std / 2
            + cfg.router_aux_coef),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _norm(x, w, eps):
    """The family's norm: the weight is stored as its offset from one."""
    return _rms_norm(x, 1.0 + w, eps)


def ref_delta_rule(q, k, v, g, beta):
    """The recurrence as written, a token a step: ``q, k (b, s, hk, dk)``,
    ``v (b, s, hv, dv)``, ``g, beta (b, s, hv)`` -> ``o (b, s, hv, dv)``;
    value head ``j`` reads key head ``j // (hv / hk)``. The scan runs in
    rematerialised blocks of ``T_BLOCK`` tokens: a vjp keeps one state a
    block and a block's own states while it is differentiated."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))

    def step(S, xs):
        q, k, v, g, beta = xs                          # (b, hv, d), (b, hv)
        S = jnp.exp(g)[..., None, None] * S
        err = v - jnp.einsum("bhde,bhd->bhe", S, k)
        S = S + (beta[..., None] * k)[..., None] * err[..., None, :]
        return S, jnp.einsum("bhde,bhd->bhe", S, q)

    block = T_BLOCK if s % T_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, *a.shape[:1],
                                             *a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(s, b, hv, dv), 0, 1)


def _ref_gdn_operands(y, lp, config):
    """``y (b, s, d)``, pre-normed -> ``(q, k, v, g, beta)`` of the delta
    rule and the output gate's logits ``z``."""
    b, s, _ = y.shape
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    kw, vw = hk * dk, hv * dv
    qkvz, ba = y @ lp["w_qkvz"], y @ lp["w_ba"]
    mixed = jax.nn.silu(_ref_conv(qkvz[..., :2 * kw + vw], lp["conv"]))
    q = _l2_norm(mixed[..., :kw].reshape(b, s, hk, dk)) * dk ** -0.5
    k = _l2_norm(mixed[..., kw:2 * kw].reshape(b, s, hk, dk))
    v = mixed[..., 2 * kw:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(ba[..., hv:] + lp["dt_bias"])
    return (q, k, v, g, beta), qkvz[..., 2 * kw + vw:].reshape(b, s, hv, dv)


def _ref_gdn(y, lp, config):
    """``y (b, s, d)``, already pre-normed -> the Gated DeltaNet layer's
    output."""
    b, s, _ = y.shape
    operands, z = _ref_gdn_operands(y, lp, config)
    o = _rms_norm(ref_delta_rule(*operands), lp["o_norm"],
                  float(config["rms_norm_eps"])) * jax.nn.silu(z)
    return o.reshape(b, s, -1) @ lp["w_o"]


def _partial_rotary(x, theta, rotary_dim):
    """``x (b, s, heads, d)``: channels ``0 .. rotary_dim - 1`` turned, a
    half against the other, the rest as they are."""
    s, half = x.shape[1], rotary_dim // 2
    inv_freq = theta ** (
        -jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1)


def _ref_gattn_operands(y, lp, config):
    """``y (b, s, d)``, pre-normed -> ``(q, k, v)`` as the softmax reads
    them and the gate's logits ``(b, s, h, hd)``."""
    b, s, _ = y.shape
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    rotary_dim = int(hd * config["partial_rotary_factor"])
    qg = (y @ lp["w_q"]).reshape(b, s, h, 2 * hd)
    q = _norm(qg[..., :hd], lp["q_norm"], eps)
    k = _norm((y @ lp["w_k"]).reshape(b, s, kvh, hd), lp["k_norm"], eps)
    v = (y @ lp["w_v"]).reshape(b, s, kvh, hd)
    return (_partial_rotary(q, theta, rotary_dim),
            _partial_rotary(k, theta, rotary_dim), v), qg[..., hd:]


def _ref_gattn(y, lp, config):
    """``y (b, s, d)``, already pre-normed -> the gated attention
    layer's output."""
    b, s, _ = y.shape
    operands, gate = _ref_gattn_operands(y, lp, config)
    o = _ref_attention_core(*operands, None) * jax.nn.sigmoid(gate)
    return o.reshape(b, s, -1) @ lp["w_o"]


def _ref_mixer(y, lp, config):
    return (_ref_gdn if "a_log" in lp else _ref_gattn)(y, lp, config)


def _ref_router(yt, lp, config):
    """``yt (t, d)`` -> per-expert weight (t, E) over ALL the experts the
    router scores (a token's w for the experts it chose, 0 for the
    others), the chosen experts (t, k) and the load-balancing loss ``E
    sum_i f_i P_i``."""
    p = jax.nn.softmax(yt @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(p, config["num_experts_per_tok"])
    if config["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chose = top_e[:, :, None] == jnp.arange(p.shape[1])[None, None, :]
    share = jnp.sum(chose, axis=(0, 1)) / top_e.size
    aux = p.shape[1] * jnp.sum(share * jnp.mean(p, axis=0))
    return jnp.sum(jnp.where(chose, top_p[:, :, None], 0.0), axis=1), \
        top_e, aux


def _ref_expert_layer(y, lp, config):
    """``y (b, s, d)``, pre-normed -> (held experts' part + gated shared
    expert, chosen experts (t, k), the load-balancing loss)."""
    b, s, d = y.shape
    yt = y.reshape(b * s, d)
    weight, top_e, aux = _ref_router(yt, lp, config)
    first = int(config.get("first_expert", 0))

    def one(i, out):
        return out + weight[:, first + i, None] * _swiglu(
            yt, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])

    # a loop over the held experts (rolled, so that they trace once),
    # every one on every token; an absent expert is not in the loop
    out = jax.lax.fori_loop(0, lp["w_gate"].shape[0], one, jnp.zeros_like(yt))
    out = out + jax.nn.sigmoid(yt @ lp["w_s"]) * _swiglu(
        yt, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out.reshape(b, s, d), top_e, aux


def _ref_block(x, lp, config, cast=lambda a: a):
    """One layer -> (the residual after it, the mixer's output, the
    expert layer's, the chosen experts, the load-balancing loss); which
    mixer it has is read off the leaves it was given. ``cast`` rounds the
    weights and each sublayer's input and output (``second_reading``)."""
    eps = float(config["rms_norm_eps"])
    lp = jax.tree.map(cast, lp)
    mixer = cast(_ref_mixer(cast(_norm(x, lp["attn_norm"], eps)), lp, config))
    x = x + mixer
    expert, top_e, aux = _ref_expert_layer(
        cast(_norm(x, lp["mlp_norm"], eps)), lp, config)
    expert = cast(expert)
    return x + expert, mixer, expert, top_e, aux


def _ref_mixer_grads(x, lp, config, cast):
    """What holds a mixer's *backward* to the definition: the operands of
    its core on the residual ``x`` (the delta rule's q, k, v, g, beta, or
    the softmax's q, k, v), q, k and v rounded to the activation dtype
    (what both sides read), one seeded cotangent of the core's output,
    and the reference core's vjp there in float32 (``cast`` rounds its
    operands and results). ``((operands), cotangent), (gradients)``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    if "a_log" in lp:
        ops, _ = _ref_gdn_operands(y, lp, config)
        core = ref_delta_rule
        shape = ops[2].shape
    else:
        ops, _ = _ref_gattn_operands(y, lp, config)
        core = lambda q, k, v: _ref_attention_core(q, k, v, None)  # noqa: E731
        shape = ops[0].shape
    ops = tuple(a.astype(dt) for a in ops[:3]) + tuple(ops[3:])
    ct = jax.random.normal(jax.random.key(0), shape, jnp.float32).astype(dt)
    _, vjp = jax.vjp(core, *(cast(_f32(a)) for a in ops))
    return (ops, ct), tuple(cast(d) for d in vjp(cast(_f32(ct))))


# the leaves a Gated DeltaNet mixer reads, in the order its vjp is given
GDN_LEAVES = ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "o_norm", "w_o")


def _ref_gdn_vjp(x, lp, config, cast):
    """The *whole* Gated DeltaNet mixer's backward (the core's alone
    passes by the convolution over an un-normed v, the gates, the head
    norm under its SiLU gate and ``W_o``): the pre-normed input on the
    residual ``x`` rounded to the activation dtype, one seeded cotangent
    of the mixer's output, and the reference mixer's vjp there in float32
    against that input and the mixer's leaves. ``(input, cotangent), (dy,
    d GDN_LEAVES...)``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _norm(x, lp["attn_norm"], float(config["rms_norm_eps"])).astype(dt)
    ct = jax.random.normal(jax.random.key(1), y.shape, jnp.float32).astype(dt)
    _, vjp = jax.vjp(lambda p, y: _ref_gdn(y, p, config),
                     {name: cast(lp[name]) for name in GDN_LEAVES},
                     cast(_f32(y)))
    d_lp, d_y = vjp(cast(_f32(ct)))
    return (y, ct), (cast(d_y), *(cast(d_lp[name]) for name in GDN_LEAVES))


def layers_of(params):
    """The layers' parameter trees, first to last: the program stacks
    them a position of the period (``pos0`` holds layers 0, p, 2p, ..),
    so layer ``l`` is row ``l // p`` of position ``l % p``."""
    slabs = [params["layers"][name] for name in sorted(
        params["layers"], key=lambda name: int(name[3:]))]
    for row in range(jax.tree.leaves(slabs[0])[0].shape[0]):
        for slab in slabs:
            yield jax.tree.map(lambda a: a[row], slab)


def plain_loss(params, tokens, config: dict):
    """CE + the aux term of ``tokens`` (b, s) under float32 ``params``:
    the equations of the module docstring composed once, differentiable
    as it stands."""
    x, aux = params["embed"][tokens], []
    for lp in layers_of(params):
        x, _, _, _, a = _ref_block(x, lp, config)
        aux.append(a)
    ce = _ref_ce(x, 1.0 + params["final_norm"], params["lm_head"],
                 _shifted(tokens, 1), float(config["rms_norm_eps"]))
    return ce + float(config["assumed"]["router_aux_loss_coef"]) * jnp.mean(
        jnp.stack(aux))


def reference_pieces(params, tokens, config: dict, cast=None,
                     inputs=None) -> dict:
    """What the comparisons read, from the reference: ``loss`` (``ce`` +
    the aux term), ``hidden``, the residual after the last block; of each
    layer of the first period ``resid[i]`` (the residual before it),
    ``after[i]``, ``mixer[i]`` (the mixer's output), ``top_e[i]``; of the
    first layer ``expert``; of the first layer of each kind
    ``grad_operands[i]`` and ``grads[i]`` (``_ref_mixer_grads``).
    ``params`` is the program's tree in any dtype; one layer is cast to
    float32 at a time so that it fits beside a full device. ``cast``
    (``second_reading``) rounds weights and sublayer inputs and outputs;
    the first period's pieces are then read on ``inputs[i]`` (the float32
    reference's ``resid``), as the program's are, beside the rounded
    chain."""
    eps = float(config["rms_norm_eps"])
    cast = cast or (lambda a: a)
    block = jax.jit(lambda x, lp: _ref_block(x, _f32(lp), config, cast))
    grads = jax.jit(
        lambda x, lp: _ref_mixer_grads(x, _f32(lp), config, cast))
    gdn_vjp = jax.jit(lambda x, lp: _ref_gdn_vjp(x, _f32(lp), config, cast))
    embed = jax.jit(lambda table, t: cast(_f32(table))[t])
    kinds = kinds_of(config)
    period = config["full_attention_interval"]
    grad_layers = {kinds.index(kind) for kind in set(kinds[:period])}
    out = {"resid": [], "after": [], "mixer": [], "top_e": [],
           "grad_operands": {}, "grads": {}}
    aux = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i, lp in enumerate(layers_of(params)):
            if i < period:
                out["resid"].append(x)
            if i in grad_layers:
                at = x if inputs is None else inputs[i]
                out["grad_operands"][i], out["grads"][i] = grads(at, lp)
                if kinds[i] == "G":
                    out["vjp_operands"], out["vjp"] = gdn_vjp(at, lp)
            if i < period and inputs is not None:
                after, mixer, expert, top_e, _ = block(inputs[i], lp)
                x, *_, a = block(x, lp)
            else:
                after, mixer, expert, top_e, a = block(x, lp)
                x = after
            aux.append(a)
            if i < period:
                out["after"].append(after)
                out["mixer"].append(mixer)
                out["top_e"].append(top_e)
            if i == 0:
                out["expert"] = expert
        ce = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, 1.0 + cast(_f32(norm)), cast(_f32(w)), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
    aux = float(jnp.mean(jnp.stack(aux)))
    coef = float(config["assumed"]["router_aux_loss_coef"])
    return dict(out, ce=float(ce), aux=aux, loss=float(ce) + coef * aux,
                hidden=x)


def reference_loss(params, tokens, config: dict) -> float:
    return reference_pieces(params, tokens, config)["loss"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + d sigma^2 / 2
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
# Except for (a), each piece is the program's layer on the *reference's*
# input to that layer (rounded to the activation dtype), so that a
# reading is one layer's error and not the chain's.
#
# Each limit lies between two readings on the chip at the published
# widths and 16384 positions (my chip runs, PR 45; PERF.md section 6):
# the largest the bf16 program gave against the float32 reference over
# the cell's seeds, and what the reference itself gives against float32
# when its weights and each sublayer's input and output are rounded to
# float8_e4m3fn, the nearest precision below the bfloat16 the
# configuration states (``second_reading``). The loss is the exception:
# no precision moves it much (the float8 reference's reads 0.0003 to
# 0.0034 by seed, the program's at most 0.00015), so its limit is held
# by what it is there to catch, a term left out of the loss.
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward (the scan over periods): median over the tokens of
    # |program - reference| / |reference| along the row
    "hidden_rel_median": 0.03,
    # (b) the residual after each layer of the first period, the layer
    # given the reference's input: the largest of the layers' medians
    "resid_rel_median": 0.012,
    # (c) the mixers' outputs (W_o included): the first Gated DeltaNet
    # layer's and the first gated attention layer's
    "gdn_rel_median": 0.03,
    "gattn_rel_median": 0.03,
    # (d) the first layer's expert output (the gated shared expert in it)
    # over the tokens whose choices agree and hold a held expert
    "expert_rel_median": 0.02,
    # (e) share of (token, choice) pairs the routers agree on, the least
    # of the first period's layers: both route in float32, the program
    # from a bf16 pre-norm; near-ties flip
    "router_agree_min": 0.97,
    # (g) the mixers' *backward*: the chunked per-head rule's dq, dk, dv,
    # dg, dbeta against the token-by-token recurrence's vjp, and the
    # flash kernels' dq, dk, dv at 256 / 256 and group 8 against the
    # blocked reference's, on the reference's operands (q, k, v rounded
    # to the activation dtype) and one seeded cotangent: the 99th
    # percentile over the (token, head) rows of |program - reference| /
    # |reference| (dg and dbeta: the whole array's), the largest of the
    # gradients
    "gdn_grad_rel_p99": 0.5,
    "gattn_grad_rel_p99": 0.03,
    # (h) the first Gated DeltaNet mixer's *whole* backward, as the layer
    # calls it (the projections, both calls of the input pass, the gates,
    # the rule, the head norm under its SiLU gate, W_o), against the
    # reference mixer's vjp on the reference's pre-normed input and one
    # seeded cotangent: |program - reference| / |reference| of each whole
    # array (dy and the seven leaves of ``GDN_LEAVES``), the largest:
    # 0.0072-0.0092 over the seeds | 1 for the float8 reference (its d W_o
    # 0.106; the rest lies under e4m3's smallest subnormal at W_o's init)
    "gdn_vjp_rel_max": 0.03,
    # (f) the loss (CE + the aux term at its coefficient, about 0.001)
    # against the reference's: the job's own loss difference, held well
    # under the aux term, so that a loss without it fails
    "loss_abs": 0.0006,
}


def program_pieces(cfg, mesh, params, tokens, inputs, operands,
                   vjp_operands=None) -> dict:
    """The program's side of ``reference_pieces``; ``inputs[i]`` is the
    reference's residual before layer ``i`` of the first period,
    ``operands[i]`` its ``grad_operands``, ``vjp_operands`` the first
    Gated DeltaNet layer's."""
    from dlrover_tpu.models import moe, qwen3_next
    from dlrover_tpu.ops import kda
    from dlrover_tpu.ops.attention import flash_attention

    mcfg = cfg.as_moe()
    b, s = tokens.shape
    loss = jax.jit(lambda p, t: qwen3_next.loss_fn(p, t, cfg, mesh))
    forward = jax.jit(
        lambda p, t: qwen3_next.forward_layers(p, t, cfg, mesh))

    def layer(lp, x, kind):
        x = x.astype(cfg.dtype)
        y = qwen3_next.norm(x, lp["attn_norm"], cfg.norm_eps)
        if kind == "G":
            mixer = qwen3_next.gdn_attention(cfg, lp, y, mesh=mesh)
        else:
            mixer = qwen3_next.gated_attention(cfg, mesh, lp, y)
        u = qwen3_next.norm(x + mixer, lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(mcfg, lp["router"], u.reshape(b * s, -1))
        expert = moe.moe_mlp(mcfg, lp, u, mesh)[0]
        return qwen3_next.block(cfg, mesh, kind, lp, x)[0], mixer, expert, \
            top_e

    def core_grads(kind, ops, ct):
        # the mixer's core alone, as the layer calls it
        if kind == "G":
            core = lambda *a: kda.chunk_gdn(*a, chunk=cfg.gdn_chunk)  # noqa: E731
        else:
            core = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=True, mesh=mesh)
        return jax.vjp(core, *ops)[1](ct)

    @jax.jit
    def gdn_vjp(lp, y, ct):
        # the whole mixer as the layer calls it, against its input and
        # its own leaves
        d_lp, d_y = jax.vjp(
            lambda mine, y: qwen3_next.gdn_attention(
                cfg, {**lp, **mine}, y, mesh=mesh),
            {name: lp[name] for name in GDN_LEAVES}, y)[1](ct)
        return (d_y, *(d_lp[name] for name in GDN_LEAVES))

    layer = jax.jit(layer, static_argnums=2)
    core_grads = jax.jit(core_grads, static_argnums=0)
    hidden, aux = forward(params, tokens)
    out = {"loss": float(loss(params, tokens)), "aux": float(aux),
           "hidden": hidden, "after": [], "mixer": [], "top_e": [],
           "grads": {}}
    for i, x in enumerate(inputs):
        lp = qwen3_next.layer_params(cfg, params, i)
        after, mixer, expert, top_e = layer(lp, x, cfg.kinds[i])
        out["after"].append(after)
        out["mixer"].append(mixer)
        out["top_e"].append(top_e)
        if i == 0:
            out["expert"] = expert
        if i in operands:
            out["grads"][i] = core_grads(cfg.kinds[i], *operands[i])
            if cfg.kinds[i] == "G" and vjp_operands is not None:
                out["vjp"] = gdn_vjp(lp, *vjp_operands)
    return out


def _chosen(top_e, n_experts: int):
    """``top_e (t, k)`` -> (t, n_experts): 1 where the token chose it."""
    return jax.nn.one_hot(top_e, n_experts, dtype=jnp.int32).sum(1)


def readings(got: dict, want: dict, kinds, n_experts: int, held) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's; ``held``: (the first held expert, how many)."""
    k = want["top_e"][0].shape[1]
    first, n_held = held
    period = len(want["mixer"])

    def median(a, b, rows=slice(None)):
        return float(jnp.median(_row_rel(a, b)[rows]))

    def grads_p99(i):
        # a head's vector a row (dq, dk, dv); the scalars a head (dg,
        # dbeta) as one vector: a fast-decaying head's are below 1e-30
        return max(
            float(jnp.percentile(_row_rel(a, b), 99.0)) if b.ndim == 4
            else float(_row_rel(a.reshape(1, -1), b.reshape(1, -1))[0])
            for a, b in zip(got["grads"][i], want["grads"][i]))

    agreed = [jnp.sum(_chosen(got["top_e"][i], n_experts)
                      * _chosen(want["top_e"][i], n_experts), axis=1)
              for i in range(period)]
    out = {
        "hidden_rel_median": median(got["hidden"], want["hidden"]),
        "resid_rel_median": max(
            median(got["after"][i], want["after"][i])
            for i in range(period)),
        # more than half the tokens choose no held expert and read the
        # shared expert alone: the median is over those that chose one
        "expert_rel_median": median(
            got["expert"], want["expert"], (agreed[0] == k) & (jnp.sum(
                _chosen(want["top_e"][0], n_experts)[:, first:first + n_held],
                axis=1) > 0)),
        "router_agree_min": min(
            float(jnp.sum(a)) / (a.shape[0] * k) for a in agreed),
        "loss_abs": abs(got["loss"] - want["loss"]),
    }
    for kind, name in (("G", "gdn"), ("F", "gattn")):
        if kind in kinds[:period]:
            i = kinds.index(kind)
            out[f"{name}_rel_median"] = median(
                got["mixer"][i], want["mixer"][i])
            out[f"{name}_grad_rel_p99"] = grads_p99(i)
    if "vjp" in want:
        out["gdn_vjp_rel"] = {
            name: float(_row_rel(a.reshape(1, -1), b.reshape(1, -1))[0])
            for name, a, b in zip(("y",) + GDN_LEAVES, got["vjp"],
                                  want["vjp"])}
        out["gdn_vjp_rel_max"] = max(out["gdn_vjp_rel"].values())
    return out


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[qwen3_next] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok) + "".join(
            f"; d {name} {value:.4g}"
            for name, value in read.get("gdn_vjp_rel", {}).items()),
        flush=True)
    return all(ok.values())


def _compare(cfg, mesh, params, tokens, config, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each and returns whether all
    hold."""
    got = program_pieces(cfg, mesh, params, tokens, want["resid"],
                         want["grad_operands"], want.get("vjp_operands"))
    held = sum(int(np.asarray(jnp.sum(
        (e >= cfg.first_expert)
        & (e < cfg.first_expert + cfg.as_moe().n_held))))
        for e in got["top_e"])
    return _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}; {held} of "
        f"{len(got['top_e']) * got['top_e'][0].size} pairs of the first "
        f"period chose a held expert; loss {got['loss']:.5f} / "
        f"{want['loss']:.5f} = CE {want['ce']:.5f} + {cfg.router_aux_coef:g}"
        f" x aux {want['aux']:.5f}, the program's aux {got['aux']:.5f})",
        readings(got, want, list(cfg.kinds), cfg.n_experts,
                 (cfg.first_expert, cfg.as_moe().n_held)))


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` (which
    has to fail at least one limit) and to ``bfloat16`` (which has to
    pass them all), each against the reference in float32, on the batch
    and the weights ``jobs/finetune_loop.py`` makes from ``seed``. By
    hand, on the chip::

        python -c "import json
        from benchmarks.families import qwen3_next as f
        f.second_reading(json.load(open(
            'benchmarks/configs/qwen3-next-80b-a3b-ep16-1chip.json')), 3)"
    """
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam = build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    params = fam.init_params(k_params)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    want = reference_pieces(params, tokens, config)
    passed = {}
    for name, dtype in (("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        got = reference_pieces(params, tokens, config, _round_trip(dtype),
                               inputs=want["resid"])
        passed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed} "
            f"(loss {got['loss']:.5f} / {want['loss']:.5f})",
            readings(got, want, kinds_of(config), fam.cfg.n_experts,
                     (fam.cfg.first_expert, fam.cfg.as_moe().n_held)))
        del got     # 3.6 GiB of pieces at 16384: not beside the next side's
    return passed
