"""The granite_hybrid family (``model_type: granitemoehybrid``,
granite-4.0-h-small, 32B-A9B), as ``dlrover_tpu.models.granite_hybrid``
computes it and as this file's plain reference computes it again.

Layer equations, from the model's config.json and its public
implementation; hidden width ``D``; ``Norm(x) = x rsqrt(mean x^2 + eps)
w``; ``e, r, a, l`` are ``embedding_multiplier`` (12),
``residual_multiplier`` (0.22), ``attention_multiplier`` (1 / 128) and
``logits_scaling`` (16); layer ``i`` is what ``layer_types[i]`` says
(published: attention at 5, 15, 25, 35, Mamba-2 elsewhere):

- model: ``x_0 = e E[tokens]``; a layer ``h = x + r Mixer(Norm(x))``,
  ``x' = h + r (MoE(y) + Shared(y))`` with ``y = Norm(h)``; logits
  ``Norm(x_L) E^T / l`` with ``E`` the one table (``tie_word_embeddings``);
  mean next-token cross-entropy over the held slice of the vocabulary.
- Mamba-2 mixer, ``h`` heads of ``p`` = 64, one group (one ``B``, one
  ``C`` for all heads) of state width ``n`` = 128, convolution of 4::

      [z | xBC | dt] = u W_in            (h p | h p + 2 n | h), no bias
      xBC = silu(conv4(xBC) + b)         depthwise, causal, with a bias
      dt = softplus(dt + dt_bias);  A = -exp(A_log)          float32
      S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T,  S_0 = 0   (p x n a head)
      y_t = S_t C_t + D x_t
      out = (RMSNorm_(h p)(y silu(z)) w) W_out      the gate, then the norm

- attention: ``heads`` query heads on ``kv`` key heads of 128, **no
  rotary** (``position_embedding_type: nope``), causal, softmax at ``a``;
  no bias.
- expert layer: router logits over ``num_local_experts`` (published: 72)
  in float32; the ``k`` = 10 largest; weights the softmax over those ten;
  ``sum_j w_j SwiGLU_j(y)`` over experts of width 768, dropless, +
  ``SwiGLU_s(y)``, the shared expert of width 1536, on every token. No
  auxiliary loss (the config has no coefficient).
- **the chip's share.** This chip holds experts ``first_expert .. +
  num_local_experts - 1`` of ``published_num_local_experts`` (a pair that
  chose another adds nothing), Mamba heads ``first_mamba_head .. +
  mamba_n_heads - 1`` of ``published_mamba_n_heads`` (their columns of
  ``W_in``'s ``z``, ``x`` and ``dt`` parts, their rows of ``W_out``, their
  channels of the convolution and of the norm; ``B`` and ``C`` are whole),
  query heads ``first_head .. + num_attention_heads - 1`` with their key
  heads, and ids ``0 .. vocab_size - 1``. **The gated norm's mean square
  is over the held channels**: the rank's own sum of squares over its own
  count; the sum across the head holders (one float a token) is left out,
  here and in the program alike.

What config.json does not say is under ``assumed`` in the configuration.

The reference is float32 under ``jax.default_matmul_precision
("highest")``: the recurrence **token by token** (a ``lax.scan`` over time
in rematerialised blocks), attention by explicit scores and mask in
blocks of queries, the expert layer a loop over the held experts, each on
all tokens, CE in blocks of rows. It imports nothing of ``dlrover_tpu``;
what the references share is ``families/xing4.py``'s (norm, SwiGLU,
casts), ``families/kimi_linear.py``'s convolution and ``families/
smallthinker.py``'s blocked attention.

**The expected first loss** is ``harness/granite_hybrid_flops.py
expected_first_loss``: ``ln V + var / 2`` plus the tied term (the normed
state at init points along its own token's row): 9.4529 at the cell's
sizes against ln 12544 + 0.0032 = 9.4402 untied.
"""

from __future__ import annotations

import functools
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.kimi_linear import _ref_conv
from benchmarks.families.minicpm_sala import _scaled
from benchmarks.families.smallthinker import _ref_attention_core, _round_trip
from benchmarks.families.xing4 import _f32, _rms_norm, _shifted, _swiglu
from benchmarks.harness import granite_hybrid_flops
from benchmarks.harness.granite_hybrid_flops import head_dim, kinds_of

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

T_BLOCK = 128      # tokens a rematerialised block of the recurrence
CE_BLOCK = 1024    # rows a block of the cross-entropy

# the leaves a Mamba mixer reads, in the order its vjp is given
MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip",
                "m_norm", "w_out")


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", True), ("hidden_act", "silu"),
                      ("model_type", "granitemoehybrid"),
                      ("position_embedding_type", "nope"),
                      ("mamba_n_groups", 1), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("normalization_function", "rmsnorm")):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/granite_hybrid.py computes ({want!r})")
    published = {key: config.get("published_" + key, config[key]) for key in (
        "mamba_n_heads", "num_attention_heads", "num_key_value_heads",
        "num_local_experts")}
    if (published["mamba_n_heads"] * config["mamba_d_head"]
            != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("mamba_expand x hidden_size is the published heads' "
                         "inner width")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names a mixer a layer held")
    return dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        mamba_heads=published["mamba_n_heads"],
        mamba_heads_held=config["mamba_n_heads"],
        first_mamba_head=int(config.get("first_mamba_head", 0)),
        mamba_head_dim=config["mamba_d_head"],
        mamba_state=config["mamba_d_state"], conv_size=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        n_heads=published["num_attention_heads"],
        n_kv_heads=published["num_key_value_heads"],
        heads_held=config["num_attention_heads"],
        first_head=int(config.get("first_head", 0)),
        head_dim=head_dim(config),
        expert_ffn_dim=config["intermediate_size"],
        shared_ffn_dim=config["shared_intermediate_size"],
        n_experts=published["num_local_experts"],
        experts_held=config["num_local_experts"],
        first_expert=int(config.get("first_expert", 0)),
        experts_per_token=config["num_experts_per_tok"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]),
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import granite_hybrid
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/granite_hybrid.py remats a block or nothing")
    cfg = granite_hybrid.GraniteHybridConfig(
        **_sizes(config),
        init_std=float(assumed["initializer_range"]),
        out_proj_std=(float(assumed["out_proj_std"])
                      if "out_proj_std" in assumed else None),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = granite_hybrid.param_specs(cfg)
    init = jax.jit(
        lambda key: granite_hybrid.init_params(cfg, key),
        out_shardings=named_shardings(mesh, specs))

    def reference(params, tokens):
        t0 = time.perf_counter()
        want = reference_pieces(params, tokens, config)
        print(f"[granite_hybrid] the reference's pieces took "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
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
            lambda p, t: granite_hybrid.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: granite_hybrid.loss_fn(p, t, cfg, mesh),
        param_count=granite_hybrid.param_count(cfg),
        flops_per_token=lambda seq: granite_hybrid_flops.flops_per_token(
            config, seq),
        expected_first_loss=granite_hybrid_flops.expected_first_loss(config),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def ref_scan(x, dt, A, B, C, D):
    """The recurrence as written, a token a step: ``x (b, s, h, p)``, ``dt
    (b, s, h)``, ``A, D (h,)``, ``B, C (b, s, n)`` -> ``y (b, s, h, p)``.
    The scan runs in rematerialised blocks of ``T_BLOCK`` tokens: a vjp
    keeps one state a block and a block's own states while it is
    differentiated."""
    b, s, h, p = x.shape

    def step(S, xs):
        x, dt, B, C = xs                  # (b, h, p), (b, h), (b, n) x 2
        S = (jnp.exp(dt * A)[..., None, None] * S
             + (dt[..., None] * x)[..., None] * B[:, None, None, :])
        return S, jnp.einsum("bhpn,bn->bhp", S, C) + D[:, None] * x

    block = T_BLOCK if s % T_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, *a.shape[:1],
                                             *a.shape[2:])
               for a in (x, dt, B, C))
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((b, h, p, B.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(y.reshape(s, b, h, p), 0, 1)


def _ref_mamba_operands(y, lp, config):
    """``y (b, s, D)``, pre-normed -> the recurrence's ``(x, dt, A, B, C,
    D)`` and the gate's logits ``z (b, s, h p)``."""
    b, s, _ = y.shape
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    di = h * p
    zxbcdt = y @ lp["w_in"]
    xbc = jax.nn.silu(_ref_conv(zxbcdt[..., di:2 * di + 2 * n], lp["conv_w"])
                      + lp["conv_b"])
    dt = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * n:] + lp["dt_bias"])
    return (xbc[..., :di].reshape(b, s, h, p), dt, -jnp.exp(lp["a_log"]),
            xbc[..., di:di + n], xbc[..., di + n:], lp["d_skip"]), \
        zxbcdt[..., :di]


def _ref_mamba(y, lp, config, scan=ref_scan):
    """``y (b, s, D)``, already pre-normed -> (the Mamba-2 mixer's output,
    the scan's output ``(b, s, h p)``, the gated norm's statistic ``(b, s,
    1)``: the mean square of ``y silu(z)`` over the held channels)."""
    b, s, _ = y.shape
    operands, z = _ref_mamba_operands(y, lp, config)
    o = scan(*operands).reshape(b, s, -1)
    g = o * jax.nn.silu(z)
    stat = jnp.mean(g * g, axis=-1, keepdims=True)
    normed = g * jax.lax.rsqrt(stat + float(config["rms_norm_eps"]))
    return (normed * lp["m_norm"]) @ lp["w_out"], o, stat


def _ref_attention(y, lp, config):
    """``y (b, s, D)``, already pre-normed -> the attention layer's
    output: no rotary, softmax at ``attention_multiplier``."""
    b, s, _ = y.shape
    h, kvh, hd = (config["num_attention_heads"],
                  config["num_key_value_heads"], head_dim(config))
    # the shared core scales by hd^-1/2: hand it q at a / hd^-1/2
    q = (y @ lp["w_q"]).reshape(b, s, h, hd) * (
        float(config["attention_multiplier"]) * hd ** 0.5)
    k = (y @ lp["w_k"]).reshape(b, s, kvh, hd)
    v = (y @ lp["w_v"]).reshape(b, s, kvh, hd)
    return _ref_attention_core(q, k, v, None).reshape(b, s, -1) @ lp["w_o"]


def _ref_router(yt, lp, config):
    """``yt (t, D)`` -> per-expert weight (t, E) over ALL the experts the
    router scores (the softmax over a token's ten largest logits for the
    experts it chose, 0 for the others) and the chosen experts (t, k)."""
    logits = yt @ lp["router"]
    top_l, top_e = jax.lax.top_k(logits, config["num_experts_per_tok"])
    top_w = jax.nn.softmax(top_l, axis=-1)
    chose = top_e[:, :, None] == jnp.arange(logits.shape[1])[None, None, :]
    return jnp.sum(jnp.where(chose, top_w[:, :, None], 0.0), axis=1), top_e


def _ref_routed_term(yt, lp, config, i):
    """Held expert ``i``'s part of the layer on ``yt (t, D)``: its weight
    a token (0 where the token did not choose it) times its SwiGLU."""
    weight, _ = _ref_router(yt, lp, config)
    mine = jax.lax.dynamic_slice_in_dim(
        weight, int(config.get("first_expert", 0)) + i, 1, axis=1)
    return mine * _swiglu(yt, lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i])


def _ref_shared_term(yt, lp):
    return _swiglu(yt, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _ref_expert_layer(y, lp, config):
    """``y (b, s, D)``, pre-normed -> (held experts' part + shared expert,
    chosen experts (t, k))."""
    b, s, d = y.shape
    yt = y.reshape(b * s, d)
    # a loop over the held experts (rolled, so that they trace once),
    # every one on every token; an absent expert is not in the loop
    out = jax.lax.fori_loop(
        0, lp["w_gate"].shape[0],
        lambda i, out: out + _ref_routed_term(yt, lp, config, i),
        _ref_shared_term(yt, lp))
    return out.reshape(b, s, d), _ref_router(yt, lp, config)[1]


def _ref_mixed(x, lp, config, cast=lambda a: a):
    """``x + r Mixer(Norm(x))`` -> (the residual after the mixer, the
    mixer's output, the scan's output and the norm's statistic or None for
    an attention layer); which mixer it has is read off the leaves.
    ``lp`` is already cast."""
    y = cast(_rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"])))
    if "a_log" in lp:
        mixer, scan, stat = _ref_mamba(y, lp, config)
    else:
        mixer, scan, stat = _ref_attention(y, lp, config), None, None
    mixer = cast(mixer)
    return x + float(config["residual_multiplier"]) * mixer, mixer, scan, stat


def _ref_block(x, lp, config, cast=lambda a: a):
    """One layer -> (the residual after it, the mixer's output, the expert
    layer's, the chosen experts, the scan's output and the norm's
    statistic or None for an attention layer, the residual after the
    mixer). ``cast`` rounds the weights and each sublayer's input and
    output (``second_reading``)."""
    eps, r = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    lp = jax.tree.map(cast, lp)
    h, mixer, scan, stat = _ref_mixed(x, lp, config, cast)
    expert, top_e = _ref_expert_layer(
        cast(_rms_norm(h, lp["mlp_norm"], eps)), lp, config)
    expert = cast(expert)
    return h + r * expert, mixer, expert, top_e, scan, stat, h


def block_backward(config: dict, cast=lambda a: a):
    """``(x, h, lp, dx) -> d x``: the cotangent of a layer's input ``x``
    from that of its output (``h``: the residual after its mixer, which
    the forward pass kept), the reference block's own vjp taken **a piece
    at a time** (the mixer's half; the expert layer a held expert a call, then
    the shared one), each piece a program of its own so that none needs
    more than a few GiB beside a full device. A rounding of a sublayer's
    output passes its cotangent on as it is."""
    eps, r = float(config["rms_norm_eps"]), float(config["residual_multiplier"])

    def ready(lp):
        return jax.tree.map(cast, _f32(lp))

    def normed(h, lp):
        return cast(_rms_norm(h, lp["mlp_norm"], eps)).reshape(-1, h.shape[-1])

    mixed_vjp = jax.jit(lambda x, lp, dh: jax.vjp(
        lambda x: _ref_mixed(x, ready(lp), config, cast)[0], x)[1](dh)[0])
    routed_vjp = jax.jit(lambda h, lp, i, d: jax.vjp(
        lambda h: _ref_routed_term(normed(h, ready(lp)), ready(lp), config, i),
        h)[1](d)[0])
    shared_vjp = jax.jit(lambda h, lp, d: jax.vjp(
        lambda h: _ref_shared_term(normed(h, ready(lp)), ready(lp)),
        h)[1](d)[0])

    def back(x, h, lp, dx):
        d = (r * dx).reshape(-1, dx.shape[-1])
        dh = dx + shared_vjp(h, lp, d)
        for i in range(lp["w_gate"].shape[0]):
            dh = dh + routed_vjp(h, lp, i, d)
        return mixed_vjp(x, lp, dh)

    return back


def _ref_mamba_vjp(x, lp, config, cast):
    """The *whole* Mamba mixer's backward: the pre-normed input on the
    residual ``x`` rounded to the activation dtype, one seeded cotangent
    of the mixer's output, and the reference mixer's vjp there in float32
    against that input and the mixer's leaves. ``(input, cotangent), (dy,
    d MAMBA_LEAVES...)``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"])).astype(dt)
    ct = jax.random.normal(jax.random.key(1), y.shape, jnp.float32).astype(dt)
    _, vjp = jax.vjp(lambda p, y: _ref_mamba(y, p, config)[0],
                     {name: cast(lp[name]) for name in MAMBA_LEAVES},
                     cast(_f32(y)))
    d_lp, d_y = vjp(cast(_f32(ct)))
    return (y, ct), (cast(d_y), *(cast(d_lp[name]) for name in MAMBA_LEAVES))


def _ref_scan_vjp(x, lp, config, cast):
    """The scan *alone*, forward and backward, where a chunk hands its
    state to the next: the operands of the first Mamba layer on the
    residual ``x`` (``x``, ``B`` and ``C`` rounded to the activation dtype
    as the kernels read them; the step, ``A`` and ``D`` float32), one
    seeded cotangent, and the token-by-token recurrence's output and vjp
    on them. Both sides read the same operands, so a reading is the
    scan's error and nothing else's. ``(operands, cotangent), (y, d x, d
    dt, d A, d B, d C, d D)``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"])).astype(dt)
    (xs, step, A, B, C, D), _ = _ref_mamba_operands(_f32(y), lp, config)
    operands = (xs.astype(dt), step, A, B.astype(dt), C.astype(dt), D)
    ct = jax.random.normal(jax.random.key(2), xs.shape, jnp.float32).astype(dt)
    out, vjp = jax.vjp(ref_scan, *(cast(_f32(a)) for a in operands))
    return (operands, ct), tuple(
        cast(a) for a in (out, *vjp(cast(_f32(ct)))))


def _ref_ce(x, norm, table, targets, eps, scaling):
    """Mean CE of ``Norm(x) E^T / l`` against ``targets (b, s)``, -1 =
    none; the logits a rematerialised block of rows at a time."""
    d = x.shape[-1]
    rows = (_rms_norm(x, norm, eps) / scaling).reshape(-1, d)
    targets = targets.reshape(-1)
    block = CE_BLOCK if rows.shape[0] % CE_BLOCK == 0 else rows.shape[0]

    @jax.checkpoint
    def one(total, args):
        r, t = args
        logp = jax.nn.log_softmax(r @ table.T, axis=-1)
        gold = jnp.take_along_axis(
            logp, jnp.maximum(t, 0)[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(t >= 0, gold, 0.0)), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (
        rows.reshape(-1, block, d), targets.reshape(-1, block)))
    return -total / jnp.sum(targets >= 0)


def layers_of(params):
    """The layers' parameter trees, first to last: the program stacks
    them a position of the period (``pos0`` holds layers 0, p, 2p, ..),
    so layer ``l`` is row ``l // p`` of position ``l % p``."""
    slabs = [params["layers"][name] for name in sorted(
        params["layers"], key=lambda name: int(name[3:]))]
    for row in range(jax.tree.leaves(slabs[0])[0].shape[0]):
        for slab in slabs:
            yield jax.tree.map(lambda a: a[row], slab)


def period_of(config: dict) -> int:
    """The shortest period of the layer pattern that divides the depth."""
    kinds = kinds_of(config)
    return next(p for p in range(1, len(kinds) + 1) if not len(kinds) % p
                and all(k == kinds[i % p] for i, k in enumerate(kinds)))


def plain_loss(params, tokens, config: dict):
    """The CE of ``tokens`` (b, s) under float32 ``params``: the equations
    of the module docstring composed once, differentiable as it stands
    (the table's gradient is the sum of its two uses because it is one
    array here)."""
    x = float(config["embedding_multiplier"]) * params["embed"][tokens]
    for lp in layers_of(params):
        x = _ref_block(x, lp, config)[0]
    return _ref_ce(x, params["final_norm"], params["embed"],
                   _shifted(tokens, 1), float(config["rms_norm_eps"]),
                   float(config["logits_scaling"]))


def reference_pieces(params, tokens, config: dict, cast=None,
                     inputs=None) -> dict:
    """What the comparisons read, from the reference: ``loss``;
    ``hidden``, the residual after the last block; of each layer of the
    first period ``resid[i]`` (the residual before it), ``after[i]``,
    ``mixer[i]`` (the mixer's output), ``top_e[i]``; of the first layer
    ``expert``; of the first Mamba layer ``scan`` and ``stat``
    (``_ref_mamba``), ``vjp_operands``, ``vjp`` (``_ref_mamba_vjp``) and
    ``scan_operands``, ``scan_vjp`` (``_ref_scan_vjp``);
    ``table_grad``, the loss's gradient in the table, the lookup's part
    plus the head's, by the reference's own backward pass a layer at a
    time. ``params`` is the program's tree in any dtype; one layer is cast
    to float32 at a time and the residuals wait on the host, so that it
    fits beside a full device. ``cast`` (``second_reading``) rounds
    weights and sublayer inputs and outputs; the first period's pieces are
    then read on ``inputs[i]`` (the float32 reference's ``resid``), as the
    program's are, beside the rounded chain."""
    eps = float(config["rms_norm_eps"])
    e_mult = float(config["embedding_multiplier"])
    scaling = float(config["logits_scaling"])
    cast = cast or (lambda a: a)
    block = jax.jit(lambda x, lp: _ref_block(x, _f32(lp), config, cast))
    mamba_vjp = jax.jit(
        lambda x, lp: _ref_mamba_vjp(x, _f32(lp), config, cast))
    scan_vjp = jax.jit(
        lambda x, lp: _ref_scan_vjp(x, _f32(lp), config, cast))
    embed = jax.jit(lambda table, t: e_mult * cast(_f32(table))[t])
    kinds = kinds_of(config)
    period = period_of(config)
    first_mamba = kinds.index("M")
    out = {"resid": [], "after": [], "mixer": [], "top_e": []}
    chain = []      # every layer's input and its residual after the
                    # mixer, on the host
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i, lp in enumerate(layers_of(params)):
            before = jax.device_get(x)
            if i < period:
                out["resid"].append(before)
            at = x if inputs is None or i >= period else jnp.asarray(inputs[i])
            if i == first_mamba:
                out["vjp_operands"], vjp = mamba_vjp(at, lp)
                out["vjp"] = jax.device_get(vjp)
                out["scan_operands"], vjp = scan_vjp(at, lp)
                out["scan_vjp"] = jax.device_get(vjp)
                del vjp
            after, mixer, expert, top_e, scan, stat, h = block(at, lp)
            if at is x:
                x = after
            else:
                x, *_, h = block(x, lp)
            chain.append((before, jax.device_get(h)))
            if i < period:
                out["after"].append(jax.device_get(after))
                out["mixer"].append(jax.device_get(mixer))
                out["top_e"].append(top_e)
            if i == 0:
                out["expert"] = jax.device_get(expert)
            if i == first_mamba:
                out["scan"], out["stat"] = jax.device_get((scan, stat))
            del after, mixer, expert, scan, stat, h
        targets = _shifted(tokens, 1)
        head = jax.jit(lambda table, x, norm: jax.value_and_grad(
            lambda table, x: _ref_ce(x, cast(_f32(norm)), table, targets,
                                     eps, scaling), argnums=(0, 1))(
                cast(_f32(table)), x))
        loss, (d_table, dx) = head(params["embed"], x, params["final_norm"])
        back = block_backward(config, cast)
        for i, lp in reversed(list(enumerate(layers_of(params)))):
            dx = back(*(jnp.asarray(a) for a in chain[i]), lp, dx)
        d_table = jax.jit(lambda d, t, dx: d.at[t].add(e_mult * dx))(
            d_table, tokens, dx)
    return dict(out, loss=float(loss), hidden=jax.device_get(x),
                table_grad=jax.device_get(d_table), period=period,
                first_mamba=first_mamba)


def reference_loss(params, tokens, config: dict) -> float:
    return reference_pieces(params, tokens, config)["loss"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + a constant
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
# Except for (a) and (i), each piece is the program's layer on the
# *reference's* input to that layer (rounded to the activation dtype), so
# that a reading is one layer's error and not the chain's.
#
# Each limit lies between two readings on the chip at the published widths
# and 16384 positions (my chip runs, PR 52; PERF.md section 6): the largest
# the bf16 program gave against the float32 reference over the cell's
# seeds, and what the reference itself gives against float32 when its
# weights and each sublayer's input and output are rounded to
# float8_e4m3fn at a scale a tensor, the nearest precision below the
# bfloat16 the configuration states (``second_reading``: it fails eleven
# of the thirteen; (b) and (i) say why they are the two it does not).
# ---------------------------------------------------------------------------

LIMITS = {
    # each limit's comment ends with its two readings at 16384 positions
    # (my chip runs, PR 52): the program's largest over seeds 0-3 | the
    # float8 reference's on seed 3 (the bfloat16 reference's passes all)
    #
    # (a) the residual after the last block, through the program's own
    # forward (the scan over the period): median over the tokens of
    # |program - reference| / |reference| along the row. 0.00663 | 0.0265
    "hidden_rel_median": 0.013,
    # (b) the residual after each layer of the period, the layer given the
    # reference's input: the largest of the layers' medians. The one limit
    # no rounding of the reference reaches: the reading is the residual
    # stream's own bfloat16 (the reference's stays float32, whatever its
    # sublayers are rounded to), so the limit is held from the program's
    # side alone, against a block wired wrongly (a multiplier, a residual:
    # benchmarks/tests/test_granite_hybrid_reference.py). 0.00226 | 0.0004
    "resid_rel_median": 0.005,
    # (c) the first Mamba layer: the scan's output against the
    # token-by-token recurrence on the reference's own operands chain
    # (W_in, the convolution, softplus), the mixer's output (the gated
    # norm and W_out included), and the gated norm's statistic, one float
    # a token over the held channels. With the cumulative decay rounded
    # to bfloat16 before the kernels read it (by hand, seed 3) they read
    # 0.0771, 0.0694 and 0.0140. 0.00491 | 0.0654; 0.00530 | 0.0762;
    # 0.00548 | 0.0753
    "scan_rel_median": 0.015,
    "ssm_rel_median": 0.015,
    "norm_stat_rel_median": 0.01,
    # (d) the attention layer's output (W_o included). 0.00376 | 0.0540
    "attn_rel_median": 0.012,
    # (e) the first layer's expert output (the shared expert in it) over
    # the tokens whose choices agree and hold a held expert.
    # 0.00489 | 0.0658
    "expert_rel_median": 0.015,
    # (f) share of (token, choice) pairs the routers agree on, the least
    # of the period's layers: both route in float32, the program from a
    # bf16 pre-norm; near-ties flip. 0.9977 | 0.9687
    "router_agree_min": 0.985,
    # (g) the first Mamba mixer's *whole* backward, as the layer calls it
    # (W_in, the convolution with its bias, softplus, the scan's backward
    # kernel, the gate and the norm, W_out), against the reference mixer's
    # vjp on the reference's pre-normed input and one seeded cotangent:
    # |program - reference| / |reference| of each whole array (dy and the
    # eight leaves of ``MAMBA_LEAVES``), the largest (the convolution's
    # taps and bias: 0.0212; d A_log 0.0140, d dt_bias 0.0084). The
    # bfloat16 decay reads 17.7 here (d A_log). 0.0212 | 0.3055
    "ssm_vjp_rel_max": 0.06,
    # (h) the table's gradient through the whole program (the lookup's
    # part plus the head's, summed in the one leaf) against the
    # reference's own backward pass: |program - reference| / |reference|
    # of the whole array. 0.00782 | 0.0360
    "table_grad_rel": 0.017,
    # (i) the loss against the reference's. No precision moves it at init
    # (3.8e-6 | 5.7e-6), so it is held by what it is there to catch, a
    # term of the tail left out: the tied term is 0.0127 of it, the
    # logits' scaling moves it by more
    # (benchmarks/tests/test_granite_hybrid_reference.py)
    "loss_abs": 0.0006,
    # (j) the state a chunk hands to the next. At the stated init (A = -1
    # .. -32, dt about 1.3) a head forgets a token in 1 to 5 more, so the
    # carried state reaches only the ``CARRY`` tokens beside a chunk's
    # edge, in the slowest few heads: about 1 % of what (c) and (g) take
    # their medians and norms over. With the state set to zero at every
    # chunk, forward and backward (by hand on the chip through the real
    # kernels, every chunk a sequence of its own, seed 3), ten of the
    # eleven limits above still pass; (g) alone fails, by a little (d
    # A_log 0.089 against 0.06). These two read the scan *alone*
    # (``ssd.ssd`` and its vjp against the token-by-token recurrence's,
    # both on the reference's operands, so that no other rounding is in
    # them) where the carry is: the scan's output at the first ``CARRY``
    # tokens of every chunk and the cotangent of ``x`` at the last, which
    # the next chunk's state cotangent feeds; a head a row, the median
    # over the chunks, the largest over offsets and heads (``_carry_rel``;
    # the largest single row reads 3.1 and 0.72 on a right program, a row
    # of small norm: no limit can sit on it). The second readings: the
    # fault itself (0.2955 forward, 0.1497 backward, each from its own
    # pass alone as from both) and the float8 reference (0.0563, 0.0517);
    # the bfloat16 reference reads 0.0022 and 0.0028.
    # ``scan_da_head_rel_max``, the scan's d A a head and not as one norm
    # over the heads, is logged without a limit: rounding alone moves it
    # (0.029 to 0.29 the program over seeds 0-5, 0.0402 the bfloat16
    # reference, a head whose sum over the tokens nearly cancels; the
    # fault reads 0.80, float8 0.377). 0.00261 | 0.0563; 0.00278 | 0.0517
    "carry_fwd_rel_max": 0.012,
    "carry_bwd_rel_max": 0.012,
}

#: tokens beside a chunk's edge that (j) reads
CARRY = 8


def programs(cfg, mesh, b: int, s: int) -> dict:
    """The program's side of the comparisons as five jitted programs, by
    name. ``whole`` is the only one that differentiates the model: the
    loss through the program's own ``loss_fn`` pieces, the residual after
    the last block beside it and the loss's gradient in the table."""
    from dlrover_tpu.models import granite_hybrid, moe
    from dlrover_tpu.ops import rms_norm, ssd

    mcfg = cfg.as_moe()

    def whole(p, t):
        def loss(table):
            tied = {**p, "embed": table}
            x = granite_hybrid.forward_layers(tied, t, cfg, mesh)
            return granite_hybrid.head_loss(tied, x, t, cfg, mesh), x

        (value, hidden), table_grad = jax.value_and_grad(
            loss, has_aux=True)(p["embed"])
        return value, hidden, table_grad

    def layer(kind, lp, x):
        x = x.astype(cfg.dtype)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        pieces = {}
        if kind == "M":
            mixer = granite_hybrid.mamba_mixer(cfg, lp, y, mesh=mesh)
            operands, z = granite_hybrid.mamba_operands(cfg, lp, y)
            scan = ssd.ssd(*operands, chunk=cfg.mamba_chunk, mesh=mesh
                           ).reshape(b, s, -1)
            pieces = {"scan": scan, "stat": granite_hybrid.gated(scan, z)[1]}
        else:
            mixer = granite_hybrid.attention_mixer(cfg, lp, y, mesh=mesh)
        u = rms_norm(x + (cfg.residual_multiplier * mixer).astype(x.dtype),
                     lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(mcfg, lp["router"], u.reshape(b * s, -1))
        expert = moe.moe_mlp(mcfg, lp, u, mesh)[0]
        return granite_hybrid.block(cfg, mesh, kind, lp, x), mixer, expert, \
            top_e, pieces

    def mamba_vjp(lp, y, ct):
        # the whole mixer as the layer calls it, against its input and
        # its own leaves
        d_lp, d_y = jax.vjp(
            lambda mine, y: granite_hybrid.mamba_mixer(
                cfg, {**lp, **mine}, y, mesh=mesh),
            {name: lp[name] for name in MAMBA_LEAVES}, y)[1](ct)
        return (d_y, *(d_lp[name] for name in MAMBA_LEAVES))

    def scan_vjp(operands, ct):
        out, vjp = jax.vjp(lambda *o: ssd.ssd(
            *o, chunk=cfg.mamba_chunk, mesh=mesh), *operands)
        return (out, *vjp(ct))

    return {"whole": jax.jit(whole), "mamba_vjp": jax.jit(mamba_vjp),
            "scan_vjp": jax.jit(scan_vjp),
            **{"layer_" + kind: jax.jit(functools.partial(layer, kind))
               for kind in sorted(set(cfg.kinds))}}


def program_pieces(cfg, mesh, params, tokens, want: dict) -> dict:
    """The program's side of ``reference_pieces``' ``want``: each layer of
    the period on the reference's residual before it, the two vjps on the
    reference's operands. Logs the seconds each program's first call took
    (its compile and one run)."""
    from dlrover_tpu.models import granite_hybrid

    run = programs(cfg, mesh, *tokens.shape)
    took = {}

    def first(name, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(run[name](*args))
        took.setdefault(name, time.perf_counter() - t0)
        return out

    loss, hidden, table_grad = first("whole", params, tokens)
    out = {"loss": float(loss), "table_grad": jax.device_get(table_grad),
           "hidden": jax.device_get(hidden),
           "after": [], "mixer": [], "top_e": []}
    del hidden, table_grad
    for i, x in enumerate(want["resid"]):
        lp = granite_hybrid.layer_params(cfg, params, i)
        after, mixer, expert, top_e, pieces = first(
            "layer_" + cfg.kinds[i], lp, jnp.asarray(x))
        out["after"].append(jax.device_get(after))
        out["mixer"].append(jax.device_get(mixer))
        out["top_e"].append(top_e)
        if i == 0:
            out["expert"] = jax.device_get(expert)
        if i == want["first_mamba"]:
            out.update(jax.device_get(pieces))
            out["vjp"] = first("mamba_vjp", lp, *want["vjp_operands"])
            out["scan_vjp"] = first("scan_vjp", *want["scan_operands"])
    print("[granite_hybrid] the program's pieces, seconds to a program's "
          "first result: " + " ".join(
              f"{name}={seconds:.1f}" for name, seconds in took.items()),
          flush=True)
    return out


def _rows_rel(got, want, rows: int = 256):
    """|got - want| / |want| along the last axis, float32. In numpy on
    the host, as all of the comparison: the reference's pieces wait there
    already, and on the device each of its few dozen small operations is
    a program to compile (89 s of a cold set-up, my chip run, PR 52);
    ``rows`` at a time, so that the temporaries stay in the cache (a
    (16384, 4096) pair takes 0.3 s so, 3.9 s whole)."""
    got, want = np.asarray(got), np.asarray(want)
    lead, width = want.shape[:-1], want.shape[-1]
    got, want = got.reshape(-1, width), want.reshape(-1, width)
    out = np.empty(len(want), np.float32)
    for i in range(0, len(want), rows):
        g, w = (a[i:i + rows].astype(np.float32) for a in (got, want))
        out[i:i + rows] = np.linalg.norm(g - w, axis=-1) / np.maximum(
            np.linalg.norm(w, axis=-1), 1e-30)
    return out.reshape(lead)


def _chosen(top_e, n_experts: int):
    """``top_e (t, k)`` -> (t, n_experts): 1 where the token chose it."""
    return (np.asarray(top_e)[:, :, None] == np.arange(n_experts)).sum(1)


def _whole(got, want, block: int = 1 << 20) -> float:
    """|got - want| / |want| of the whole arrays, a block at a time."""
    got, want = np.asarray(got).reshape(-1), np.asarray(want).reshape(-1)
    off = of = 0.0
    for i in range(0, len(want), block):
        g, w = (a[i:i + block].astype(np.float32) for a in (got, want))
        off, of = off + float(np.dot(g - w, g - w)), of + float(np.dot(w, w))
    return (off / max(of, 1e-60)) ** 0.5


def _carry_rel(got, want, chunk: int, at) -> float:
    """``got, want (b, s, h, p)``: |got - want| / |want| along ``p``, a
    token a head, at the offsets ``at`` of every chunk: the median over
    the chunks, then the largest over the offsets and the heads."""
    b, s, h, p = want.shape
    rel = _rows_rel(got, want)[:, :s // chunk * chunk]
    return float(np.median(rel.reshape(-1, chunk, h)[:, at], axis=0).max())


def readings(got: dict, want: dict, kinds, n_experts: int, held,
             chunk: int) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's; ``held``: (the first held expert, how many);
    ``chunk``: the scan's."""
    k = want["top_e"][0].shape[1]
    first, n_held = held
    period = len(want["mixer"])

    def median(a, b, rows=slice(None)):
        return float(np.median(_rows_rel(a, b).reshape(-1)[rows]))

    agreed = [np.sum(_chosen(got["top_e"][i], n_experts)
                     * _chosen(want["top_e"][i], n_experts), axis=1)
              for i in range(period)]
    m = want["first_mamba"]
    stat = np.asarray(want["stat"])
    out = {
        "hidden_rel_median": median(got["hidden"], want["hidden"]),
        "resid_rel_median": max(
            median(got["after"][i], want["after"][i])
            for i in range(period)),
        "scan_rel_median": median(got["scan"], want["scan"]),
        "ssm_rel_median": median(got["mixer"][m], want["mixer"][m]),
        "norm_stat_rel_median": float(np.median(
            np.abs(np.asarray(got["stat"]) - stat) / stat)),
        # most tokens choose no held expert and read the shared expert
        # alone: the median is over those that chose one
        "expert_rel_median": median(
            got["expert"], want["expert"], (agreed[0] == k) & (np.sum(
                _chosen(want["top_e"][0], n_experts)[:, first:first + n_held],
                axis=1) > 0)),
        "router_agree_min": min(
            float(np.sum(a)) / (a.shape[0] * k) for a in agreed),
        "table_grad_rel": _whole(got["table_grad"], want["table_grad"]),
        "loss_abs": abs(got["loss"] - want["loss"]),
    }
    if "A" in kinds[:period]:
        i = kinds.index("A")
        out["attn_rel_median"] = median(got["mixer"][i], want["mixer"][i])
    out["ssm_vjp_rel"] = {
        name: _whole(a, b) for name, a, b in zip(
            ("y",) + MAMBA_LEAVES, got["vjp"], want["vjp"])}
    out["ssm_vjp_rel_max"] = max(out["ssm_vjp_rel"].values())
    # the scan alone on the reference's operands: (y, d x, d dt, d A, ..)
    (y, dx, _, dA, *_), (y_w, dx_w, _, dA_w, *_) = (
        got["scan_vjp"], want["scan_vjp"])
    edge = min(CARRY, chunk)
    out["carry_fwd_rel_max"] = _carry_rel(y, y_w, chunk, slice(0, edge))
    out["carry_bwd_rel_max"] = _carry_rel(
        dx, dx_w, chunk, slice(chunk - edge, chunk))
    dA, dA_w = np.asarray(dA), np.asarray(dA_w)
    out["scan_da_head_rel_max"] = float(np.max(np.abs(dA - dA_w) / np.abs(dA_w)))
    return out


def _report(what: str, read: dict) -> bool:
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[granite_hybrid] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok) + "".join(
            f"; d {name} {value:.4g}"
            for name, value in read.get("ssm_vjp_rel", {}).items())
        + f"; scan_da_head_rel_max {read['scan_da_head_rel_max']:.4g}",
        flush=True)
    return all(ok.values())


def _compare(cfg, mesh, params, tokens, config, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each, and the seconds the
    program's pieces and the comparison took, and returns whether all
    hold."""
    t0 = time.perf_counter()
    got = program_pieces(cfg, mesh, params, tokens, want)
    t1 = time.perf_counter()
    held = sum(int(np.sum(
        (np.asarray(e) >= cfg.first_expert)
        & (np.asarray(e) < cfg.first_expert + cfg.as_moe().n_held)))
        for e in got["top_e"])
    ok = _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}; {held} of "
        f"{len(got['top_e']) * got['top_e'][0].size} pairs of the period "
        f"chose a held expert; loss {got['loss']:.5f} / "
        f"{want['loss']:.5f})",
        readings(got, want, list(cfg.kinds), cfg.n_experts,
                 (cfg.first_expert, cfg.as_moe().n_held), cfg.mamba_chunk))
    print(f"[granite_hybrid] the program's pieces took {t1 - t0:.1f} s, the "
          f"comparison {time.perf_counter() - t1:.1f} s", flush=True)
    return ok


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` at a
    scale a tensor (``families/minicpm_sala.py _scaled``; it has to fail
    at least one limit) and to ``bfloat16`` (which has to pass them all),
    each against the reference in float32, on the batch and the weights
    ``jobs/finetune_loop.py`` makes from ``seed``. By hand, on the chip::

        python -c "import json
        from benchmarks.families import granite_hybrid as f
        f.second_reading(json.load(open(
            'benchmarks/configs/granite-4.0-h-small-ep8-1chip.json')), 3)"
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
    for name, cast in (("float8_e4m3fn", _scaled(jnp.float8_e4m3fn)),
                       ("bfloat16", _round_trip(jnp.bfloat16))):
        got = reference_pieces(params, tokens, config, cast,
                               inputs=want["resid"])
        passed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed} "
            f"(loss {got['loss']:.5f} / {want['loss']:.5f})",
            readings(got, want, kinds_of(config), fam.cfg.n_experts,
                     (fam.cfg.first_expert, fam.cfg.as_moe().n_held),
                     fam.cfg.mamba_chunk))
        del got
    return passed
