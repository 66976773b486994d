"""The minicpm_sala family (``model_type: minicpm_sala``, MiniCPM-SALA
9B), as ``dlrover_tpu.models.minicpm_sala`` computes it and as this file's
plain reference computes it again.

Layer equations, from the model's config.json, MiniCPM4's published
``sparse_config`` and the lightning-attention family's public
implementations; hidden width ``D``, ``d`` = 128 a head, ``x`` a layer's
input after its RMSNorm (``x rsqrt(mean x^2 + eps) w``), ``r =
scale_depth / sqrt(32)`` by the *published* depth:

- model: ``h_0 = scale_emb E[token]``; a layer ``h <- h + r Mixer(Norm
  (h))``, ``h <- h + r W_down(silu(W_gate x') W_up x')``; logits ``W_head
  (Norm(h_L) / (D / dim_model_base))``; mean next-token cross-entropy
  over the held slice of the vocabulary.
- ``lightning-attn``: ``q, k, v = x W_q, x W_k, x W_v`` (32 heads);
  ``RMSNorm_d`` on q and k (a weight of ``d`` each); rotary on the whole
  head (theta 1e4, a half against the other); ``q / sqrt(d)``; ``S_t =
  exp(-s) S_(t-1) + k_t^T v_t`` from 0, ``o_t = q_t S_t``, the slope
  ``s`` a stated constant of (layer, head); ``y = (RMSNorm_d(o) w_n
  sigmoid(x W_g)) W_o``.
- ``minicpm4``: 32 query heads on 2 key heads, ``RMSNorm_d`` on q and k,
  no rotary. Up to ``dense_len`` positions causal softmax attention at
  ``d^-1/2``. Past it, for key head ``g`` and query ``i``: ``c_j = mean
  (k_g[16 j : 16 j + 32])``; ``c_j`` visible iff ``16 j + 31 <= i``;
  ``p_(h,i,.)`` the softmax of ``q_(h,i) . c_j d^-1/2`` over the visible
  (zero where none); ``a_(i,j)`` its sum over the group's 16 heads;
  ``B_(i,b) = max a_(i,j)`` over the windows that meet block ``[64 b, 64
  b + 64)``; forced: block 0 and every block holding one of ``[i - 2047,
  i]``; chosen: the forced and, of the others with ``64 b <= i``, the
  best-scored until 64 in all, ties to the lower ``b``; softmax
  attention over the chosen blocks' keys ``t <= i``; ``y = (o sigmoid(x
  W_g)) W_o``. No gradient through the choice.

What config.json does not say is under ``assumed`` in the configuration.

The reference is float32 under ``jax.default_matmul_precision
("highest")``: the lightning recurrence **token by token** (a ``lax.scan``
over time in rematerialised blocks), the choice **by a sort**, attention
by explicit scores and mask in blocks of queries, CE in blocks of rows.
It imports nothing of ``dlrover_tpu``; what the references share is
``families/xing4.py``'s (norm, SwiGLU, casts), ``families/
smallthinker.py``'s (the blocked causal attention and CE) and
``families/qwen3_next.py``'s rotary.

**The expected first loss** is ``ln V + D sigma^2 / (2 m^2)`` with ``m =
D / dim_model_base``: the head reads a normed state over ``m``, so a
logit's variance at init is ``D sigma^2 / m^2`` (0.0064 at the published
sizes: ln 18362 + 0.0032 = 9.8212).
"""

from __future__ import annotations

import dataclasses
import itertools
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.qwen3_next import _partial_rotary
from benchmarks.families.smallthinker import (
    _ref_attention_core, _ref_ce, _round_trip)
from benchmarks.families.xing4 import (
    _f32, _rms_norm, _row_rel, _shifted, _swiglu)
from benchmarks.harness import minicpm_sala_flops
from benchmarks.harness.minicpm_sala_flops import kinds_of, sparse_config

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

T_BLOCK = 128      # tokens a rematerialised block of the recurrence
Q_ROWS = 128       # query rows a block of the choice and of the attention


def _sizes(config: dict) -> dict:
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("model_type", "minicpm_sala"), ("qk_norm", True),
                      ("attn_use_rope", False), ("lightning_use_rope", True),
                      ("use_output_gate", True), ("use_output_norm", True),
                      ("attn_use_output_gate", True),
                      ("attention_bias", False),
                      ("lightning_scale", "1/sqrt(d)")):
        if config.get(key, want) != want:
            raise ValueError(
                f"{config['name']}: {key}={config[key]!r} is not what "
                f"models/minicpm_sala.py computes ({want!r})")
    if len(config["mixer_types"]) != config["num_hidden_layers"]:
        raise ValueError("mixer_types names a mixer a layer held")
    if config["lightning_nkv"] != config["lightning_nh"]:
        raise ValueError("a lightning layer's k and v have its q's heads")
    sc = sparse_config(config)
    return dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        ffn_dim=config["intermediate_size"],
        mixer_types=tuple(config["mixer_types"]),
        published_layers=config.get("published_num_hidden_layers",
                                    config["num_hidden_layers"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        blk_kernel=sc["kernel_size"], blk_stride=sc["kernel_stride"],
        blk_size=sc["block_size"], blk_topk=sc["topk"],
        blk_init=sc["init_blocks"], blk_window=sc["window_size"],
        dense_len=sc["dense_len"],
        la_heads=config["lightning_nh"],
        la_head_dim=config["lightning_head_dim"],
        la_slopes=tuple(tuple(row) for row in
                        config["assumed"]["lightning_slopes"]),
        rope_theta=float(config["rope_theta"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=config["dim_model_base"],
        norm_eps=float(config["rms_norm_eps"]),
    )


def build(config: dict, mesh):
    """What ``jobs/`` need of this family for ``config`` on ``mesh``."""
    from dlrover_tpu.models import minicpm_sala
    from dlrover_tpu.parallel import named_shardings

    assumed = config["assumed"]
    if assumed["remat"] not in ("all", "off"):
        raise ValueError("models/minicpm_sala.py remats a block or nothing")
    cfg = minicpm_sala.MiniCPMSalaConfig(
        **_sizes(config),
        la_chunk=int(assumed["la_chunk"]),
        init_std=float(assumed["initializer_range"]),
        out_proj_std=(float(assumed["out_proj_std"])
                      if "out_proj_std" in assumed else None),
        dtype=_DTYPES[assumed["activation_dtype"]],
        param_dtype=_DTYPES[assumed["param_dtype"]],
        remat=assumed["remat"] != "off",
    )
    specs = minicpm_sala.param_specs(cfg)
    init = jax.jit(
        lambda key: minicpm_sala.init_params(cfg, key),
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
        # (arguments of TrainConfig) and the sparse layers' live tiles
        train_config=dict(assumed.get("train_config", {})),
        live_rows=jax.jit(
            lambda p, t: minicpm_sala.live_rows(p, t, cfg, mesh)),
        loss_fn=lambda p, t: minicpm_sala.loss_fn(p, t, cfg, mesh),
        param_count=minicpm_sala.param_count(cfg),
        flops_per_token=lambda seq: minicpm_sala_flops.flops_per_token(
            config, seq),
        expected_first_loss=minicpm_sala_flops.expected_first_loss(config),
        reference_loss=reference,
    )


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def ref_lightning_rule(q, k, v, slopes):
    """The recurrence as written, a token a step: ``q, k, v (b, s, h, d)``,
    ``slopes (h,)`` -> ``o (b, s, h, d)``. The scan runs in rematerialised
    blocks of ``T_BLOCK`` tokens: a vjp keeps one state a block and a
    block's own states while it is differentiated."""
    b, s, h, d = q.shape
    decay = jnp.exp(-slopes)[None, :, None, None]

    def step(S, xs):
        q, k, v = xs                                       # (b, h, d)
        S = decay * S + k[..., :, None] * v[..., None, :]
        return S, jnp.einsum("bhd,bhde->bhe", q, S)

    block = T_BLOCK if s % T_BLOCK == 0 else s
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // block, block, b, h, d)
               for a in (q, k, v))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda S, x: jax.lax.scan(step, S, x)),
        jnp.zeros((b, h, d, d), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(s, b, h, d), 0, 1)


def _heads(y, lp, h, kvh, d, eps):
    """q, k normed a head, v, and the gate's logits."""
    b, s, _ = y.shape
    q = _rms_norm((y @ lp["w_q"]).reshape(b, s, h, d), lp["q_norm"], eps)
    k = _rms_norm((y @ lp["w_k"]).reshape(b, s, kvh, d), lp["k_norm"], eps)
    return q, k, (y @ lp["w_v"]).reshape(b, s, kvh, d), (
        y @ lp["w_g"]).reshape(b, s, h, d)


def _ref_la_operands(y, lp, config):
    """``y (b, s, D)``, pre-normed -> ``(q, k, v)`` as the rule reads them
    and the gate's logits."""
    h, d = config["lightning_nh"], config["lightning_head_dim"]
    q, k, v, gate = _heads(y, lp, h, h, d, float(config["rms_norm_eps"]))
    theta = float(config["rope_theta"])
    return (_partial_rotary(q, theta, d) * d ** -0.5,
            _partial_rotary(k, theta, d), v), gate


def _ref_lightning(y, lp, config, rounded=lambda a: a):
    """``y``, pre-normed -> the lightning mixer's output; ``lp["slopes"]``
    the layer's. ``rounded`` rounds q, k and v as the rule reads them
    (``near_nothing_witness``)."""
    b, s, _ = y.shape
    operands, gate = _ref_la_operands(y, lp, config)
    operands = tuple(rounded(a) for a in operands)
    o = _rms_norm(ref_lightning_rule(*operands, lp["slopes"]), lp["o_norm"],
                  float(config["rms_norm_eps"])) * jax.nn.sigmoid(gate)
    return o.reshape(b, s, -1) @ lp["w_o"]


def _ref_pooled(k, sc):
    """``k (b, s, g, d)`` -> ``c (b, n, g, d)``: window ``j`` the mean of
    keys ``stride j .. stride j + kernel - 1``, gathered."""
    s = k.shape[1]
    n = (s - sc["kernel_size"]) // sc["kernel_stride"] + 1
    at = (sc["kernel_stride"] * jnp.arange(n)[:, None]
          + jnp.arange(sc["kernel_size"])[None, :])
    return jnp.mean(k[:, at], axis=2)


def ref_block_scores(q, k, config):
    """``q (b, s, h, d)``, ``k (b, s, g, d)`` -> ``B (b, g, s, s / block)``
    (the module docstring's), a block of query rows at a time."""
    sc = sparse_config(config)
    b, s, h, d = q.shape
    g = k.shape[2]
    c = _ref_pooled(k, sc)
    n, nb = c.shape[1], s // sc["block_size"]
    start = sc["kernel_stride"] * np.arange(n)
    first = sc["block_size"] * np.arange(nb)
    meets = jnp.asarray(                                   # (n, nb)
        (start[:, None] < first[None, :] + sc["block_size"])
        & (start[:, None] + sc["kernel_size"] > first[None, :]))
    rows = Q_ROWS if s % Q_ROWS == 0 else s

    def one(args):
        qb, i0 = args                                      # (b, rows, h, d)
        i = i0 + jnp.arange(rows)
        last = jnp.asarray(start + sc["kernel_size"] - 1)
        seen = last[None, :] <= i[:, None]
        logits = jnp.einsum("bqgrd,bkgd->bgrqk",
                            qb.reshape(b, rows, g, h // g, d), c) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
        a = jnp.sum(jnp.where(seen, p, 0.0), axis=2)       # (b, g, rows, n)
        return jnp.max(jnp.where(meets, a[..., None], 0.0), axis=-2)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // rows, rows, h, d), 1, 0),
        jnp.arange(0, s, rows)))
    return jnp.moveaxis(out, 0, 2).reshape(b, g, s, nb)


def ref_choice(scores, config):
    """``B (b, g, s, nb)`` -> bool of that shape: the forced blocks and
    the best-scored others until ``topk`` in all, **by a sort** (stable,
    descending: ties to the lower block)."""
    sc = sparse_config(config)
    s, nb = scores.shape[-2:]
    i = jnp.arange(s)[:, None]
    first = sc["block_size"] * jnp.arange(nb)[None, :]
    eligible = first <= i
    forced = eligible & ((first < sc["block_size"] * sc["init_blocks"]) | (
        first + sc["block_size"] - 1 >= i - (sc["window_size"] - 1)))
    key = jnp.where(forced, jnp.inf, jnp.where(eligible, scores, -jnp.inf))
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < sc["topk"]) & eligible


def ref_sparse_core(q, k, v, chosen, block: int):
    """Softmax attention of ``q (b, s, h, d)`` over the keys ``t <= i`` of
    the blocks ``chosen (b, g, s, s / block)`` names, by explicit scores,
    a block of queries at a time (recomputed in a backward pass)."""
    b, s, h, d = q.shape
    g = k.shape[2]
    rows = Q_ROWS if s % Q_ROWS == 0 else s
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        # (b, rows, g, r, d), (b, g, rows, nb)
        qb, cb, i0 = args
        qpos = i0 + jnp.arange(rows)
        seen = jnp.repeat(cb, block, axis=-1) & (
            kpos[None, :] <= qpos[:, None])                # (b, g, rows, s)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * d ** -0.5
        scores = jnp.where(seen[:, :, None], scores, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, (
        jnp.moveaxis(q.reshape(b, s // rows, rows, g, h // g, d), 1, 0),
        jnp.moveaxis(chosen.reshape(b, g, s // rows, rows, -1), 2, 0),
        jnp.arange(0, s, rows)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _ref_sparse_operands(y, lp, config):
    return _heads(y, lp, config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"],
                  float(config["rms_norm_eps"]))


def _ref_sparse(y, lp, config, chosen=None):
    """``y``, pre-normed -> ``(the minicpm4 mixer's output, B, chosen)``;
    ``chosen`` given: attention over that choice (B is not formed); a
    sequence within ``dense_len``: causal attention, B and chosen None."""
    b, s, _ = y.shape
    q, k, v, gate = _ref_sparse_operands(y, lp, config)
    scores = None
    if s <= sparse_config(config)["dense_len"]:
        o = _ref_attention_core(q, k, v, None)
    else:
        if chosen is None:
            scores = ref_block_scores(q, k, config)
            chosen = ref_choice(scores, config)
        o = ref_sparse_core(q, k, v, chosen,
                            sparse_config(config)["block_size"])
    o = o * jax.nn.sigmoid(gate)
    return o.reshape(b, s, -1) @ lp["w_o"], scores, chosen


def _scale(config) -> float:
    return float(config["scale_depth"]) / float(config.get(
        "published_num_hidden_layers", config["num_hidden_layers"])) ** 0.5


def _ref_block(x, lp, config, cast=lambda a: a, chosen=None):
    """One layer -> (the residual after it, the mixer's output, B, the
    choice); which mixer it has is read off the leaves it was given.
    ``cast`` rounds the weights and each sublayer's input and output
    (``second_reading``)."""
    eps, r = float(config["rms_norm_eps"]), _scale(config)
    lp = {name: leaf if name == "slopes" else cast(leaf)
          for name, leaf in lp.items()}
    y = cast(_rms_norm(x, lp["attn_norm"], eps))
    if "slopes" in lp:
        mixer, scores, chosen = _ref_lightning(y, lp, config), None, None
    else:
        mixer, scores, chosen = _ref_sparse(y, lp, config, chosen)
    mixer = cast(mixer)
    x = x + r * mixer
    ffn = cast(_swiglu(cast(_rms_norm(x, lp["mlp_norm"], eps)), lp["w_gate"],
                       lp["w_up"], lp["w_down"]))
    return x + r * ffn, mixer, scores, chosen


def _ct(key: int, shape, dt):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32
                             ).astype(dt)


def _ref_la_grads(x, lp, config, cast):
    """What holds the lightning rule's *backward* to the definition: its
    q, k, v on the residual ``x``, rounded to the activation dtype (what
    both sides read), one seeded cotangent, and the recurrence's vjp
    there in float32. ``((q, k, v), cotangent), (dq, dk, dv)``."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    ops = tuple(a.astype(dt) for a in _ref_la_operands(y, lp, config)[0])
    ct = _ct(0, ops[2].shape, dt)
    _, vjp = jax.vjp(lambda *a: ref_lightning_rule(*a, lp["slopes"]),
                     *(cast(_f32(a)) for a in ops))
    return (ops, ct), tuple(cast(d) for d in vjp(cast(_f32(ct))))


# the leaves a lightning mixer reads, in the order its vjp is given
LA_LEAVES = ("w_q", "w_k", "w_v", "w_g", "q_norm", "k_norm", "o_norm", "w_o")


#: a head's output under this share of its median norm over the sequence
#: is "nearly nothing" (``_ref_la_vjp``)
NEAR_NOTHING = 0.1


def _ref_la_vjp(x, lp, config, cast, mask: bool = True,
                rounded=lambda a: a):
    """The *whole* lightning mixer's backward (projections, norms, rotary,
    the rule, the head norm under its gate, ``W_o``): the pre-normed input
    rounded to the activation dtype, one seeded cotangent, and the
    reference mixer's vjp against that input and the mixer's leaves.

    **The cotangent is zero on the tokens where some head's output is
    nearly nothing** (its norm under ``NEAR_NOTHING`` of that head's
    median: 1-3 % of the tokens, the fast-decaying heads'). There
    ``RMSNorm_d(o)``'s backward divides by that norm, so one (token,
    head) whose two or three live scores ``q . k`` happen to cancel takes
    a gradient hundreds of times the others', its direction set by the
    rounding of q and k, and it lands in *every* row of ``d W_q`` and ``d
    W_k``: a whole-array reading then measures that token and not the
    path (my chip runs, PR 48: 0.010-0.011 on six seeds and 0.135 on a
    seventh, 2147483701, the heads that read ``W_v``, ``W_g`` and ``W_o``
    at 0.006 on all seven). **Two witnesses side with the reference**
    (`near_nothing_witness` on that seed, the cotangent whole): the
    program again 0.1353, twice; this file's reference with the rule's q,
    k and v rounded to bf16 and no code of the program 0.6155 (``d W_v``,
    ``d W_g``, ``d W_o`` 0.003); the program's own layer and kernels at
    float32 activations 4.75e-5. So the reading is bf16's rounding of q
    and k through that token and no fault of the kernels. The per-row
    readings of the other pieces are medians and percentiles, which one
    token does not move. ``mask`` False leaves
    the cotangent whole and ``rounded`` rounds the rule's q, k and v:
    ``near_nothing_witness`` reads both."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    eps = float(config["rms_norm_eps"])
    y = _rms_norm(x, lp["attn_norm"], eps).astype(dt)
    o = ref_lightning_rule(*_ref_la_operands(_f32(y), lp, config)[0],
                           lp["slopes"])
    size = jnp.linalg.norm(o, axis=-1)                     # (b, s, h)
    sound = jnp.all(
        size >= NEAR_NOTHING * jnp.median(size, axis=1, keepdims=True), -1)
    ct = (_ct(1, y.shape, jnp.float32) * (sound[..., None] | (not mask))
          ).astype(dt)
    _, vjp = jax.vjp(
        lambda p, y: _ref_lightning(y, {**p, "slopes": lp["slopes"]}, config,
                                    rounded),
        {name: cast(lp[name]) for name in LA_LEAVES}, cast(_f32(y)))
    d_lp, d_y = vjp(cast(_f32(ct)))
    return (y, ct), (cast(d_y), *(cast(d_lp[name]) for name in LA_LEAVES))


def _ref_sparse_grad_operands(x, lp, config):
    """The sparse core's q, k, v on the residual ``x`` rounded to the
    activation dtype, and one seeded cotangent."""
    dt = _DTYPES[config["assumed"]["activation_dtype"]]
    y = _rms_norm(x, lp["attn_norm"], float(config["rms_norm_eps"]))
    ops = tuple(a.astype(dt) for a in _ref_sparse_operands(y, lp, config)[:3])
    return ops, _ct(2, ops[0].shape, dt)


def layers_of(params, config):
    """The layers' parameter trees, first to last (the program stacks a
    run of like layers), a lightning layer's with its ``slopes``."""
    slopes = iter(config["assumed"]["lightning_slopes"])
    for name in sorted(params["runs"]):
        slab = params["runs"][name]
        for row in range(jax.tree.leaves(slab)[0].shape[0]):
            lp = jax.tree.map(lambda a: a[row], slab)
            if "o_norm" in lp:
                lp["slopes"] = jnp.asarray(next(slopes), jnp.float32)
            yield lp


def plain_loss(params, tokens, config: dict):
    """The loss of ``tokens`` (b, s) under float32 ``params``: the
    equations of the module docstring composed once, differentiable as it
    stands."""
    x = float(config["scale_emb"]) * params["embed"][tokens]
    for lp in layers_of(params, config):
        x = _ref_block(x, lp, config)[0]
    m = config["hidden_size"] / config["dim_model_base"]
    return _ref_ce(x, params["final_norm"] / m, params["lm_head"],
                   _shifted(tokens, 1), float(config["rms_norm_eps"]))


def reference_pieces(params, tokens, config: dict, cast=None,
                     inputs=None) -> dict:
    """What the comparisons read, from the reference: ``loss``;
    ``hidden``, the residual after the last block; of each layer
    ``resid[i]`` (the residual before it), ``after[i]``, ``mixer[i]``; of
    the first ``minicpm4`` layer ``scores``, ``chosen`` and
    ``sparse_operands``; of the first lightning layer ``la_operands`` /
    ``la_grads`` and ``vjp_operands`` / ``vjp``. ``params`` is the
    program's tree in any dtype; one layer is cast to float32 at a time.
    ``cast`` (``second_reading``) rounds weights and sublayer inputs and
    outputs; the pieces are then read on ``inputs[i]`` (the float32
    reference's ``resid``), as the program's are, beside the rounded
    chain. The pieces a layer (256 MiB each at 16384 positions) wait on
    the host."""
    eps = float(config["rms_norm_eps"])
    cast = cast or (lambda a: a)
    block = jax.jit(lambda x, lp: _ref_block(x, _f32(lp), config, cast))
    la_grads = jax.jit(lambda x, lp: _ref_la_grads(x, _f32(lp), config, cast))
    la_vjp = jax.jit(lambda x, lp: _ref_la_vjp(x, _f32(lp), config, cast))
    sparse_ops = jax.jit(
        lambda x, lp: _ref_sparse_grad_operands(x, _f32(lp), config))
    embed = jax.jit(lambda table, t: float(config["scale_emb"]) * cast(
        _f32(table))[t])
    kinds = kinds_of(config)
    first = {kind: kinds.index(kind) for kind in set(kinds)}
    out = {"resid": [], "after": [], "mixer": []}
    m = config["hidden_size"] / config["dim_model_base"]
    with jax.default_matmul_precision("highest"):
        x = embed(params["embed"], tokens)
        for i, lp in enumerate(layers_of(params, config)):
            out["resid"].append(jax.device_get(x))
            at = x if inputs is None else jnp.asarray(inputs[i])
            if i == first.get("L"):
                out["la_operands"], out["la_grads"] = la_grads(at, lp)
                out["vjp_operands"], out["vjp"] = la_vjp(at, lp)
            if i == first.get("S"):
                out["sparse_operands"] = sparse_ops(at, lp)
            after, mixer, scores, chosen = block(at, lp)
            x = after if inputs is None else block(x, lp)[0]
            out["after"].append(jax.device_get(after))
            out["mixer"].append(jax.device_get(mixer))
            del after, mixer
            if i == first.get("S"):
                out["scores"], out["chosen"] = scores, chosen
        loss = jax.jit(lambda x, norm, w, t: _ref_ce(
            x, cast(_f32(norm)) / m, cast(_f32(w)), t, eps))(
                x, params["final_norm"], params["lm_head"],
                _shifted(tokens, 1))
    return dict(out, loss=float(loss), hidden=jax.device_get(x), first=first)


def reference_given(params, config, x, chosen, operands, cast=None) -> dict:
    """The first ``minicpm4`` layer **given a choice of blocks** (the
    program's): ``sattn``, its mixer's output on the residual ``x``, and
    ``grads``, the core's vjp on ``operands`` (``(q, k, v), cotangent``)."""
    cast = cast or (lambda a: a)
    i = kinds_of(config).index("S")
    # (one layer's slices, not every layer's: they do not fit beside a
    # full device)
    lp = _f32(next(itertools.islice(layers_of(params, config), i, None)))
    block_size = sparse_config(config)["block_size"]
    (q, k, v), ct = operands

    @jax.jit
    def given(x, lp, chosen, q, k, v, ct):
        sattn = _ref_block(x, lp, config, cast, chosen)[1]
        _, vjp = jax.vjp(
            lambda q, k, v: ref_sparse_core(q, k, v, chosen, block_size),
            *(cast(_f32(a)) for a in (q, k, v)))
        return sattn, tuple(cast(d) for d in vjp(cast(_f32(ct))))

    with jax.default_matmul_precision("highest"):
        sattn, grads = given(x, lp, chosen != 0, q, k, v, ct)
    return {"sattn": sattn, "grads": grads}


def reference_loss(params, tokens, config: dict) -> float:
    return reference_pieces(params, tokens, config)["loss"]


# ---------------------------------------------------------------------------
# What a loss cannot show. At random init the CE is ln V + a constant
# whatever the body computes, so the loss check alone would pass a wrong
# layer: the program's pieces against the reference's on the seeded batch
# (logged outside the timed window; one failure makes the cell incorrect).
# Except for (a), each piece is the program's layer on the *reference's*
# input to that layer (rounded to the activation dtype), so that a reading
# is one layer's error and not the chain's.
#
# Each limit lies between two readings on the chip at the published widths
# and 16384 positions (my chip runs, PR 48; PERF.md section 6): the largest
# the bf16 program gave against the float32 reference over the cell's
# fourteen seeds, and what the reference itself gives against float32 when
# its weights and each sublayer's input and output are rounded to
# float8_e4m3fn **at a scale a tensor**, the nearest precision below the
# bfloat16 the configuration states, kept inside its range (`_scaled`;
# ``second_reading``, seeds 3, 2147483701, 2147483777). Unscaled, e4m3
# flushes both closing projections (1e-4) to zero and a lightning state
# passes its 448: four of these read exactly 1 or NaN there, which is a
# lost branch and no rounding. Two limits have another upper reading, said
# at each.
# ---------------------------------------------------------------------------

LIMITS = {
    # (a) the residual after the last block, through the program's own
    # forward (the scans over the runs): median over the tokens of
    # |program - reference| / |reference| along the row
    # 0.00426 on every seed | 0.0265
    "hidden_rel_median": 0.011,
    # (b) the residual after each of the four layers, the layer given the
    # reference's input: the largest of the layers' medians. What the
    # program reads here is bf16's rounding of the residual itself; the two
    # branches are 1.4 % of it, so a rounded *branch* moves this by 0.0009
    # (the scaled reading) and a *lost* one by 0.0139 (e4m3 unscaled: both
    # closing projections flushed to zero), which is what this limit is for
    # 0.00281 | 0.0139 the branches lost
    "resid_rel_median": 0.006,
    # (c) the first lightning mixer's output (W_o included)
    # 0.00693-0.00695 | 0.0792-0.0793
    "la_rel_median": 0.02,
    # (d) the first minicpm4 layer's block scores B, a (query, group)'s
    # row of blocks: the median
    # 0.000149-0.000150 | 0.00235
    "blk_score_rel_median": 0.0006,
    # (e) of the blocks the reference chooses, the share the program
    # chooses too: both sort float32 scores, the program's from bf16 q and
    # pooled keys; near-ties at the cut flip
    # 0.9988-0.9989 | 0.9850-0.9851
    "blk_agree_min": 0.993,
    # (f) the minicpm4 mixer's output *given the program's choice* (the
    # reference attends over the blocks the program chose)
    # 0.00602-0.00612 | 0.0737-0.0747
    "sattn_rel_median": 0.02,
    # (g) the mixers' *backward*: the `_blk` kernels' dq, dk, dv against
    # the blocked reference's vjp under the program's choice, and the
    # lightning kernels' against the token-by-token recurrence's, on the
    # reference's operands (rounded to the activation dtype) and one
    # seeded cotangent: the 99th percentile over the (token, head) rows
    # of |program - reference| / |reference|, the largest of the three
    # 0.00279-0.00281 | 0.0804-0.0807; 0.00819-0.00826 | 0.1258-0.1267
    "blk_grad_rel_p99": 0.015,
    "la_grad_rel_p99": 0.03,
    # (h) the first lightning mixer's *whole* backward, as the layer calls
    # it, against the reference mixer's vjp on the reference's pre-normed
    # input and one seeded cotangent: |program - reference| / |reference|
    # of each whole array (dy and the eight leaves of ``LA_LEAVES``), the
    # largest; the cotangent zero where a head's output is nearly nothing
    # (``_ref_la_vjp``): 0.0095-0.0117 over fourteen seeds | 0.1313-0.1379
    "la_vjp_rel_max": 0.04,
    # (i) the loss against the reference's. No rounding moves it at init
    # (the CE is ln V + a constant whatever the body computes: e4m3 at a
    # scale reads 3.8e-6 to 8.6e-6, as the program does), so its upper
    # reading is a lost or wrong piece: the mean over half the positions
    # (`_loss_faults`) 4.2e-5, 1.0e-3, 1.1e-3, both closing projections
    # flushed (e4m3 unscaled) 3.4e-5 and 4.3e-5 on two seeds of three, the
    # head's 1 / 16 dropped 0.80-0.83
    # at most 8.6e-6 over fourteen seeds (rms 4.0e-6, the mean of 16383
    # tokens' CE errors) | 3.4e-5
    "loss_abs": 2e-5,
}


def _program_la_vjp(cfg, mesh):
    """The program's lightning mixer's vjp, as the layer calls it: ``(lp,
    y, cotangent)`` -> ``(dy, *the leaves' of LA_LEAVES)``."""
    from dlrover_tpu.models import minicpm_sala

    @jax.jit
    def la_vjp(lp, y, ct):
        d_lp, d_y = jax.vjp(
            lambda mine, y: minicpm_sala.lightning_layer(
                cfg, {**lp, **mine}, y, mesh=mesh),
            {name: lp[name] for name in LA_LEAVES}, y)[1](ct)
        return (d_y, *(d_lp[name] for name in LA_LEAVES))

    return la_vjp


def program_pieces(cfg, mesh, params, tokens, want: dict) -> dict:
    """The program's side of ``reference_pieces``: each layer on the
    reference's residual before it, the cores' gradients on the
    reference's operands."""
    from dlrover_tpu.models import minicpm_sala
    from dlrover_tpu.ops import lightning
    from dlrover_tpu.ops.attention import flash_attention
    from dlrover_tpu.ops.norms import rms_norm

    s = tokens.shape[1]
    loss = jax.jit(lambda p, t: minicpm_sala.loss_fn(p, t, cfg, mesh))
    forward = jax.jit(
        lambda p, t: minicpm_sala.forward_layers(p, t, cfg, mesh))

    def layer(lp, x, kind):
        x = x.astype(cfg.dtype)
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        mixer = (minicpm_sala.lightning_layer if kind == "L"
                 else minicpm_sala.sparse_layer)(cfg, lp, y, mesh=mesh)
        return minicpm_sala.block(cfg, mesh, kind, lp, x), mixer

    @jax.jit
    def choice(lp, x):
        # the scores and the choice as the layer forms them
        q, k, _, _ = minicpm_sala.sparse_operands(cfg, lp, rms_norm(
            x.astype(cfg.dtype), lp["attn_norm"], cfg.norm_eps))
        return (minicpm_sala.score_blocks(cfg, q, k, mesh),
                minicpm_sala.choose_blocks(cfg, q, k, mesh))

    @jax.jit
    def blk_grads(chosen, q, k, v, ct):
        return jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, mesh=mesh, select=chosen,
            select_block=cfg.blk_size), q, k, v)[1](ct)

    @jax.jit
    def la_grads(slopes, q, k, v, ct):
        return jax.vjp(lambda q, k, v: lightning.lightning_attention(
            q, k, v, slopes, chunk=min(cfg.la_chunk, s), mesh=mesh),
            q, k, v)[1](ct)

    la_vjp = _program_la_vjp(cfg, mesh)
    layer = jax.jit(layer, static_argnums=2)
    out = {"loss": float(loss(params, tokens)),
           "hidden": jax.device_get(forward(params, tokens)), "after": [],
           "mixer": []}
    first = want["first"]
    for i, x in enumerate(want["resid"]):
        lp = minicpm_sala.layer_params(cfg, params, i)
        after, mixer = jax.device_get(layer(lp, x, cfg.kinds[i]))
        out["after"].append(after)
        out["mixer"].append(mixer)
        if i == first.get("S") and cfg.sparse_at(s):
            out["scores"], out["chosen"] = choice(lp, x)
            (q, k, v), ct = want["sparse_operands"]
            out["blk_grads"] = blk_grads(out["chosen"], q, k, v, ct)
        if i == first.get("L"):
            (q, k, v), ct = want["la_operands"]
            out["la_grads"] = la_grads(lp["slopes"], q, k, v, ct)
            out["vjp"] = la_vjp(lp, *want["vjp_operands"])
    return out


def _whole_rel(got, want) -> dict:
    """|got - want| / |want| of each whole array of a lightning mixer's
    vjp, by name."""
    return {name: float(_row_rel(a.reshape(1, -1), b.reshape(1, -1))[0])
            for name, a, b in zip(("y",) + LA_LEAVES, got, want)}


def readings(got: dict, want: dict, given) -> dict:
    """The numbers ``LIMITS`` bounds, of one side's pieces against the
    float32 reference's; ``given``: ``reference_given`` under that side's
    choice (None where the sequence takes the dense branch)."""
    first = want["first"]

    def median(a, b):
        return float(jnp.median(_row_rel(a, b)))

    def grads_p99(a, b):
        return max(float(jnp.percentile(_row_rel(x, y), 99.0))
                   for x, y in zip(a, b))

    out = {
        "hidden_rel_median": median(got["hidden"], want["hidden"]),
        "resid_rel_median": max(
            median(a, b) for a, b in zip(got["after"], want["after"])),
        "loss_abs": abs(got["loss"] - want["loss"]),
    }
    if "L" in first:
        out["la_rel_median"] = median(
            got["mixer"][first["L"]], want["mixer"][first["L"]])
        out["la_grad_rel_p99"] = grads_p99(got["la_grads"], want["la_grads"])
        out["la_vjp_rel"] = _whole_rel(got["vjp"], want["vjp"])
        out["la_vjp_rel_max"] = max(out["la_vjp_rel"].values())
    if given is not None:
        chose, wanted = got["chosen"] != 0, want["chosen"] != 0
        out["blk_score_rel_median"] = median(got["scores"], want["scores"])
        out["blk_agree_min"] = float(
            jnp.sum(chose & wanted) / jnp.sum(wanted))
        out["sattn_rel_median"] = median(
            got["mixer"][first["S"]], given["sattn"])
        out["blk_grad_rel_p99"] = grads_p99(got["blk_grads"], given["grads"])
    return out


def _report(what: str, read: dict) -> tuple:
    """Logs each reading beside its limit; the names of those that
    failed."""
    ok = {
        name: (read[name] >= limit if name.endswith("_min")
               else read[name] <= limit)
        for name, limit in LIMITS.items() if name in read
    }
    print(f"[minicpm_sala] {what}: " + "; ".join(
        f"{name} {read[name]:.4g} (limit {LIMITS[name]:g}, "
        f"{'ok' if ok[name] else 'FAILED'})" for name in ok) + "".join(
            f"; d {name} {value:.4g}"
            for name, value in read.get("la_vjp_rel", {}).items()),
        flush=True)
    return tuple(name for name in ok if not ok[name])


def _compare(cfg, mesh, params, tokens, config, want: dict) -> bool:
    """The comparisons of ``LIMITS``; logs each and returns whether all
    hold."""
    got = program_pieces(cfg, mesh, params, tokens, want)
    given = None
    if "chosen" in got:
        given = reference_given(
            params, config, want["resid"][want["first"]["S"]], got["chosen"],
            want["sparse_operands"])
        chosen = np.asarray(jnp.sum(got["chosen"] != 0, axis=-1))
        what = (f"; a query's group chose {chosen.min()} to {chosen.max()} "
                f"blocks of {cfg.blk_size}, {int(chosen.sum())} in all")
    else:
        what = "; the dense branch"
    return not _report(
        f"program against reference on the seeded batch ({tokens.size} "
        f"tokens, pattern {cfg.pattern_string}{what}; loss "
        f"{got['loss']:.5f} / {want['loss']:.5f})",
        readings(got, want, given))


def _scaled(dtype):
    """Round to ``dtype`` and back **at a scale a tensor** (its largest
    magnitude lands on the format's largest finite number), what a path in
    ``dtype`` does so that nothing leaves the format's range: a control
    that reads the *rounding*. Unscaled, e4m3 flushes the output
    projections (1e-4) to zero and a lightning state passes its 448, and
    the mixers' pieces read 1 and NaN whatever their limits are."""
    top = float(jnp.finfo(dtype).max)
    narrow = _round_trip(dtype)

    def cast(a):
        scale = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top)
        return narrow(a / scale) * scale

    return cast


def _seeded(config: dict, seed: int, seq: int):
    """The family on one device, and the weights and the batch
    ``jobs/finetune_loop.py`` makes from ``seed``."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam = build(config, mesh)
    k_params, k_ref, _ = jax.random.split(jax.random.key(seed), 3)
    tokens = jax.random.randint(
        k_ref, (1, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    return fam, mesh, fam.init_params(k_params), tokens


def _loss_faults(params, tokens, config: dict, want: dict) -> dict:
    """``loss_abs``'s upper reading. No rounding moves the loss at init
    (the CE is ln V + a constant whatever the body computes), so it is
    held against two *named faults* of the tail, each the reference's own
    loss with that fault against its loss without: the normed state not
    divided by ``D / dim_model_base`` before the head, and the mean taken
    over the first half of the positions."""
    m = config["hidden_size"] / config["dim_model_base"]
    eps = float(config["rms_norm_eps"])
    x, targets = jnp.asarray(want["hidden"]), _shifted(tokens, 1)
    half = tokens.shape[1] // 2
    ce = jax.jit(lambda x, norm, w, t: _ref_ce(x, _f32(norm), _f32(w), t,
                                               eps))
    with jax.default_matmul_precision("highest"):
        unscaled = ce(x, params["final_norm"], params["lm_head"], targets)
        halved = ce(x[:, :half], params["final_norm"] / m,
                    params["lm_head"], targets[:, :half])
    return {"no_head_scale": abs(float(unscaled) - want["loss"]),
            "half_the_positions": abs(float(halved) - want["loss"])}


def second_reading(config: dict, seed: int, seq: int = 16384) -> dict:
    """The limits' second reading: the reference with its weights and
    each sublayer's input and output rounded to ``float8_e4m3fn`` at a
    scale a tensor (`_scaled`; it has to fail at least one limit) and to
    ``bfloat16`` (which has to pass them all), each against the reference
    in float32, on the batch and the weights ``jobs/finetune_loop.py``
    makes from ``seed``; and the loss under `_loss_faults`. Returns the
    limits each side failed. By hand, on the chip::

        python -c "import json
        from benchmarks.families import minicpm_sala as f
        f.second_reading(json.load(open(
            'benchmarks/configs/minicpm-sala-9b-d4-1chip.json')), 3)"
    """
    _, _, params, tokens = _seeded(config, seed, seq)
    want = reference_pieces(params, tokens, config)
    print(f"[minicpm_sala] the loss under a named fault, seed {seed}: "
          + "; ".join(f"{name} {value:.4g}" for name, value in _loss_faults(
              params, tokens, config, want).items()), flush=True)
    sparse = "chosen" in want and want["chosen"] is not None
    failed = {}
    for name, cast in (("float8_e4m3fn", _scaled(jnp.float8_e4m3fn)),
                       ("bfloat16", _round_trip(jnp.bfloat16))):
        got = reference_pieces(params, tokens, config, cast,
                               inputs=want["resid"])
        given = None
        if sparse:
            x = want["resid"][want["first"]["S"]]
            given = reference_given(params, config, x, got["chosen"],
                                    want["sparse_operands"])
            got["blk_grads"] = reference_given(
                params, config, x, got["chosen"], want["sparse_operands"],
                cast)["grads"]
        failed[name] = _report(
            f"reference rounded to {name} against float32, seed {seed} "
            f"(loss {got['loss']:.5f} / {want['loss']:.5f})",
            readings(got, want, given))
        del got, given
    return failed


def near_nothing_witness(config: dict, seed: int, seq: int = 16384) -> dict:
    """What sides with `_ref_la_vjp`'s mask: the first lightning mixer's
    whole backward **with the cotangent left whole**, on the batch and the
    weights of ``seed``, each side against the float32 reference's vjp on
    the same operands (``la_vjp_rel_max``'s reading, unmasked):

    - ``program``: the program as the cell runs it (and
      ``program_masked``, what `_compare` reads);
    - ``rounded_reference``: the reference with the rule's q, k and v
      rounded to the activation dtype, where the program rounds them and
      nowhere else; it runs no code of the program, so a reading as large
      as the program's is the rounding's and not a kernel's;
    - ``program_float32``: the program's layer at float32 activations and
      ``highest`` products (the same kernels): a reading as small as the
      masked one says the same from the program's side.

    By hand, on the chip, as `second_reading`."""
    from dlrover_tpu.models import minicpm_sala

    fam, mesh, params, tokens = _seeded(config, seed, seq)
    cfg = fam.cfg
    i = kinds_of(config).index("L")
    want = reference_pieces(params, tokens, config)
    x = jnp.asarray(want["resid"][i])
    lp = minicpm_sala.layer_params(cfg, params, i)
    ref_lp = next(itertools.islice(layers_of(params, config), i, None))
    whole = jax.jit(lambda x, lp, rounded: _ref_la_vjp(
        x, _f32(lp), config, lambda a: a, mask=False,
        rounded=_round_trip(cfg.dtype) if rounded else lambda a: a),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        (y, ct), ref = whole(x, ref_lp, False)
        out = {"rounded_reference": _whole_rel(whole(x, ref_lp, True)[1],
                                               ref)}
    out["program"] = _whole_rel(_program_la_vjp(cfg, mesh)(lp, y, ct), ref)
    with jax.default_matmul_precision("highest"):
        out["program_float32"] = _whole_rel(_program_la_vjp(
            dataclasses.replace(cfg, dtype=jnp.float32), mesh)(
                _f32(lp), _f32(y), _f32(ct)), ref)
    out["program_masked"] = _whole_rel(_program_la_vjp(cfg, mesh)(
        lp, *want["vjp_operands"]), want["vjp"])
    for name, read in out.items():
        print(f"[minicpm_sala] witness, seed {seed}, {name}: largest "
              f"{max(read.values()):.4g}; " + "; ".join(
                  f"d {leaf} {value:.4g}" for leaf, value in read.items()),
              flush=True)
    return out
