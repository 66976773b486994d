"""The ``xing4_0`` decoder family (Xing4.0-29B-A4B): latent attention with
two head widths, ``hc_mult`` residual streams mixed per token through a
Sinkhorn-normalised matrix, sigmoid routing with a choice bias and a
shared expert, leading dense layers, and a multi-token head.

Every piece that another family has is that family's: the expert path is
``models/moe.py``'s (``route``, sorted dispatch, grouped matmul, combine,
the shared expert; this file only hands it a ``MoeConfig`` view), the
dense feed-forward is ``llama.swiglu``, attention is
``ops.flash_attention`` (q/k heads of ``qk_nope + qk_rope``, v heads of
``v_head_dim``, the softmax scale yarn states), the embedding and the
fused cross-entropy are the shared ops, called twice a step here.

What is this family's own:

- **residual streams** (manifold-constrained hyper-connections, arXiv
  2512.24880). The residual is ``X (n, b, s, d)``, stream-major so that
  a stream is a whole ``(b, s, d)`` slab (a ``(.., n, d)`` layout would
  pad its 4 rows to a 16-row bf16 tile). A sublayer ``F`` is wrapped::

      u      = vec(X) / rms(vec(X))            over a token's n*d values
      H~     = alpha * (u phi) + b             pre (n), post (n), res (n, n)
      H_pre  = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
      H_res  = Sinkhorn(exp(clip(H~_res)))     columns then rows, 20 times
      y      = sum_i H_pre[i] X[i]
      X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(RMSNorm(y))

  Coefficients are formed in float32 (``hc_coeff``); the streams stay in
  the activation dtype and are mixed in float32 (``hc_mix``). ``u phi``
  is ``(vec(X) phi) / rms``: the product takes the streams as they are
  stored and accumulates in float32, so nothing is rounded twice. On
  the TPU, where a stream's channels are whole lanes, the four places
  where a sublayer touches the streams are ``ops/hc_mix.py``'s Pallas
  passes (each slab read once a direction); anywhere else the ``jnp``
  below, which is also their oracle (``hc_sublayer`` chooses).
- **latent attention** (as ``deepseek_v3``): q through a rank
  ``q_lora_rank`` bottleneck with its own norm; k and v through one of
  rank ``kv_lora_rank``; ``qk_rope_dim`` rotary dims per head, the key's
  shared by all heads; yarn frequencies (``ops.yarn_frequencies``).
- **the forward**: embedding -> n copies -> the dense blocks -> the
  expert blocks (each a ``lax.scan`` over its slab, remat a block) ->
  sum of the streams -> final norm -> loss; then the multi-token module
  through the same block function: ``[RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(x_i)] W_eh`` copied to n streams, one expert block, summed,
  its own norm, the shared head against ``t_{i+2}``;
  ``loss = CE_main + mtp_loss_weight * CE_mtp``. No aux loss: the choice
  bias ``router_bias`` balances the load in the published recipe, by an
  update outside the gradient that this program does not make (top-k's
  indices give it no gradient, so the optimizer leaves it where it is).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama, moe, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    attention,
    embed_lookup,
    flash_attention,
    hc_mix,
    rms_norm,
    yarn_frequencies,
    yarn_mscale,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """XingChen-AGI/Xing4.0-29B-A4B's config.json by default."""
    vocab_size: int = 131072
    dim: int = 3584
    n_dense_layers: int = 2          # first_k_dense_replace
    n_moe_layers: int = 38
    n_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    dense_ffn_dim: int = 9216
    expert_ffn_dim: int = 1024
    n_experts: int = 64              # the router's width
    experts_per_token: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling: float = 2.0
    scoring: str = "sigmoid"
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    mtp_depth: int = 1               # num_nextn_predict_layers
    mtp_loss_weight: float = 0.3
    rope_theta: float = 10000.0
    yarn_factor: float = 64.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if self.mtp_depth not in (0, 1):
            raise ValueError(
                f"mtp_depth={self.mtp_depth}: one multi-token module or none"
            )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-0.5 * m^2``, ``m`` yarn's temperature over all
        dims (``deepseek_v3`` folds it into the scale)."""
        m = yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def rope_magnitude(self) -> float:
        """What cos and sin are multiplied by: 1 where ``mscale`` and
        ``mscale_all_dim`` agree, as published."""
        return (yarn_mscale(self.yarn_factor, self.yarn_mscale)
                / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim))

    @property
    def hc_width(self) -> int:
        """Coefficients a sublayer forms a token: pre, post, res."""
        return self.hc_mult * (self.hc_mult + 2)

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_moe_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_heads, ffn_dim=self.expert_ffn_dim,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, scoring=self.scoring,
            routed_scaling=self.routed_scaling,
            experts_held=self.experts_held, first_expert=self.first_expert,
            router_aux_coef=0.0, norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=self.remat,
        )

    @staticmethod
    def tiny(**kw) -> "Xing4Config":
        base = dict(
            vocab_size=256, dim=64, n_dense_layers=1, n_moe_layers=2,
            n_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, dense_ffn_dim=96,
            expert_ffn_dim=32, n_experts=8, experts_per_token=2,
            hc_mult=4, yarn_factor=4.0, yarn_original_max=32,
            max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return Xing4Config(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: Xing4Config, dense: bool) -> Dict[str, Tuple]:
    """``{name: (shape, init)}`` of one block; ``init`` is "normal",
    "ones", "zeros" or an ``hc_*`` rule."""
    D, h, n = cfg.dim, cfg.n_heads, cfg.hc_mult
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    shapes = {
        "attn_norm": ((D,), "ones"),
        "w_qa": ((D, rq), "normal"),
        "q_a_norm": ((rq,), "ones"),
        "w_qb": ((rq, h * (dn + dr)), "normal"),
        "w_kva": ((D, rkv + dr), "normal"),
        "kv_a_norm": ((rkv,), "ones"),
        "w_kvb": ((rkv, h * (dn + dv)), "normal"),
        "w_o": ((h * dv, D), "normal"),
        "mlp_norm": ((D,), "ones"),
    }
    for sub in ("hc_attn", "hc_mlp"):
        shapes[f"{sub}_phi"] = ((n, D, cfg.hc_width), "normal")
        shapes[f"{sub}_alpha"] = ((3,), "hc_alpha")
        shapes[f"{sub}_bias"] = ((cfg.hc_width,), "hc_bias")
    if dense:
        F = cfg.dense_ffn_dim
        shapes.update({
            "w_gate": ((D, F), "normal"), "w_up": ((D, F), "normal"),
            "w_down": ((F, D), "normal"),
        })
        return shapes
    E, F = cfg.as_moe().n_held, cfg.expert_ffn_dim
    shapes.update({
        "router": ((D, cfg.n_experts), "normal"),
        "router_bias": ((cfg.n_experts,), "zeros"),
        "w_gate": ((E, D, F), "normal"), "w_up": ((E, D, F), "normal"),
        "w_down": ((E, F, D), "normal"),
    })
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        shapes.update({
            "ws_gate": ((D, Fs), "normal"), "ws_up": ((D, Fs), "normal"),
            "ws_down": ((Fs, D), "normal"),
        })
    return shapes


def hc_bias_init(n: int) -> jnp.ndarray:
    """pre: ``logit(1/n)`` (the streams' mean), post: 0 (``H_post`` 1),
    res: ``6 I`` (near the identity after Sinkhorn)."""
    pre = jnp.full((n,), math.log(1.0 / (n - 1)) if n > 1 else 0.0)
    return jnp.concatenate(
        [pre, jnp.zeros((n,)), (6.0 * jnp.eye(n)).reshape(-1)]
    ).astype(jnp.float32)


def _init_slab(cfg: Xing4Config, key, layers: int, dense: bool) -> Params:
    std, pd = 0.02, cfg.param_dtype
    out = {}
    shapes = _block_shapes(cfg, dense)
    for k, (name, (shape, rule)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        full = (layers,) + shape
        if rule == "normal":
            leaf = jax.random.normal(k, full, jnp.float32) * std
        elif rule == "ones":
            leaf = jnp.ones(full, jnp.float32)
        elif rule == "zeros":
            leaf = jnp.zeros(full, jnp.float32)
        elif rule == "hc_alpha":
            leaf = jnp.full(full, 0.01, jnp.float32)
        else:
            leaf = jnp.broadcast_to(hc_bias_init(cfg.hc_mult), full)
        out[name] = leaf.astype(pd)
    return out


def init_params(cfg: Xing4Config, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_dense, k_moe, k_head, k_mtp, k_eh = jax.random.split(rng, 6)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(pd)

    params = {
        "embed": normal(k_embed, (V, D)),
        "dense": _init_slab(cfg, k_dense, cfg.n_dense_layers, dense=True),
        "layers": _init_slab(cfg, k_moe, cfg.n_moe_layers, dense=False),
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }
    if cfg.mtp_depth:
        params["mtp"] = {
            "enorm": jnp.ones((D,), pd),
            "hnorm": jnp.ones((D,), pd),
            "w_eh": normal(k_eh, (2 * D, D)),
            "block": _init_slab(cfg, k_mtp, cfg.mtp_depth, dense=False),
            "norm": jnp.ones((D,), pd),
        }
    return params


def _slab_specs(cfg: Xing4Config, dense: bool) -> Params:
    """A matrix shards its model-width side over fsdp (the side it
    projects back to, for ``w_o`` and the down projections), an expert
    layer's stack of experts over ep; norms, biases and the stream
    coefficients are replicated. The leading axis is the layer."""
    specs = {}
    for name, (shape, init) in _block_shapes(cfg, dense).items():
        matrix = (FSDP, None) if "down" not in name and name != "w_o" else (
            None, FSDP)
        if init != "normal" or name.startswith("hc_"):
            specs[name] = P(*([None] * (len(shape) + 1)))
        elif len(shape) == 3:
            specs[name] = P(None, EP, *matrix)
        else:
            specs[name] = P(None, *matrix)
    return specs


def param_specs(cfg: Xing4Config) -> Params:
    """Data and expert parallelism: matrices shard their model-width
    side over fsdp, the held experts over ep; no tp, sp or pp (latent
    attention has no head-sharded or sequence-sharded form here)."""
    specs = {
        "embed": P(None, FSDP),
        "dense": _slab_specs(cfg, dense=True),
        "layers": _slab_specs(cfg, dense=False),
        "final_norm": P(None),
        "lm_head": P(FSDP, None),
    }
    if cfg.mtp_depth:
        specs["mtp"] = {
            "enorm": P(None), "hnorm": P(None), "w_eh": P(FSDP, None),
            "block": _slab_specs(cfg, dense=False), "norm": P(None),
        }
    return specs


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def validate_for_mesh(cfg: Xing4Config, mesh: Mesh, batch: int = 0) -> None:
    shape = dict(mesh.shape)
    for axis in (TP, SP, PP):
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"xing4: mesh {axis}={shape[axis]}: latent attention runs "
                "whole heads and whole sequences on a device (dp, fsdp "
                "and ep only)"
            )
    shards = math.prod(shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        raise ValueError(
            f"batch={batch} does not divide over the mesh's {shards} data "
            "shards (dp x fsdp x ep)"
        )
    held, ep = cfg.as_moe().n_held, shape.get(EP, 1)
    if held % ep:
        raise ValueError(
            f"the {held} experts held are not divisible by mesh ep={ep}"
        )


# ---------------------------------------------------------------------------
# Residual streams
# ---------------------------------------------------------------------------

def hc_coefficients(cfg: Xing4Config, phi, alpha, bias, X):
    """``X (n, b, s, d)`` -> ``H_pre (n, b, s)``, ``H_post (n, b, s)``,
    ``H_res (n, n, b, s)``, float32, tokens minor (a ``(.., n, n)``
    layout would leave 124 of a register's 128 lanes empty through the
    Sinkhorn's 40 normalisations)."""
    with trace.scope("hc_coeff"):
        x32 = X.astype(jnp.float32)
        inv_rms = lax.rsqrt(jnp.mean(x32 * x32, axis=(0, 3)) + cfg.norm_eps)
        raw = jnp.einsum(
            "nbsd,ndk->kbs", X, phi.astype(X.dtype),
            preferred_element_type=jnp.float32,
        ) * inv_rms
        return hc_mix.coefficients(
            raw, alpha, bias, cfg.hc_mult, cfg.hc_clamp,
            cfg.hc_sinkhorn_iters, cfg.hc_eps)


def hc_pre_mix(h_pre, X):
    """``y = sum_i H_pre[i] X[i]``: (b, s, d) in the streams' dtype."""
    with trace.scope("hc_mix"):
        y = sum(h_pre[i][..., None] * X[i].astype(jnp.float32)
                for i in range(X.shape[0]))
        return y.astype(X.dtype)


def hc_post_mix(h_post, h_res, X, z):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] z``."""
    n = X.shape[0]
    with trace.scope("hc_mix"):
        x32 = [X[j].astype(jnp.float32) for j in range(n)]
        z32 = z.astype(jnp.float32)
        return jnp.stack([
            (sum(h_res[i, j][..., None] * x32[j] for j in range(n))
             + h_post[i][..., None] * z32).astype(X.dtype)
            for i in range(n)
        ])


def hc_sublayer(cfg: Xing4Config, lp: Params, name: str, X, fn, *,
                mesh: Optional[Mesh] = None, interpret: bool = False):
    """One sublayer ``fn`` (b, s, d) -> (b, s, d) between its pre-mix
    and its post + res-mix, with the coefficients ``lp[name_*]``: on the
    TPU with streams of whole lanes (or with ``interpret``)
    ``ops/hc_mix.py``'s four passes, anywhere else the ``jnp`` form
    above; the gauge ``layers.hc_fused`` says which."""
    phi, alpha, bias = (lp[f"{name}_{k}"] for k in ("phi", "alpha", "bias"))
    if hc_mix.fused(interpret, X.shape[-1]):
        return hc_mix.sublayer(
            X, phi, alpha, bias, fn, norm_eps=cfg.norm_eps,
            clamp=cfg.hc_clamp, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            interpret=interpret, mesh=mesh)
    h_pre, h_post, h_res = hc_coefficients(cfg, phi, alpha, bias, X)
    return hc_post_mix(h_post, h_res, X, fn(hc_pre_mix(h_pre, X)))


# ---------------------------------------------------------------------------
# Latent attention, the block, the forward
# ---------------------------------------------------------------------------

def latent_attention(cfg, mesh, positions, inv_freq, lp, y, *,
                     window: Optional[int] = None, attend=None):
    """Latent attention of ``y (b, s, d)``, already pre-normed: q heads
    of ``qk_nope_dim + qk_rope_dim``, k and v through one bottleneck of
    rank ``kv_lora_rank``, the last ``qk_rope_dim`` of a key one vector
    for all heads. The one function of its kind: ``cfg`` is any config
    (or a layer kind's view of one) with those widths, ``n_heads``,
    ``dtype``, ``norm_eps``, ``softmax_scale`` and, where there is
    rotary, ``rope_magnitude`` (this family's,
    ``models/kimi_linear.py``'s, each layer kind of
    ``models/dots3.py``'s, whose ranks, head counts, widths and theta
    differ a call). q
    goes through a rank bottleneck with its own norm where ``lp`` has
    ``w_qa`` / ``w_qb`` and through one matrix ``w_q`` where not;
    ``inv_freq`` None leaves the ``qk_rope_dim`` part without rotary.
    Where ``cfg`` has ``latent_rescale = (a_q, a_kv)`` the two normed
    latents are multiplied by them. ``window``: the flash kernels'
    (a query sees its own position and the ``window - 1`` before it).
    ``attend(q, k, v, c_q) -> (b, s, h, v_head_dim)`` stands in for the
    causal flash call where the caller's attention is its own (a learned
    selection: it is handed the normed q latent, which an indexer
    reads). Where ``lp`` has ``w_g`` the heads' outputs are gated,
    ``sigmoid(y w_g)`` a head, before ``w_o``."""
    dt, eps = cfg.dtype, cfg.norm_eps
    b, s, _ = y.shape
    h, rkv = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    a_q, a_kv = getattr(cfg, "latent_rescale", (1.0, 1.0))
    c_q = None
    with trace.scope("mla_proj"):
        if "w_qa" in lp:
            c_q = rms_norm(y @ lp["w_qa"].astype(dt), lp["q_a_norm"], eps)
            if a_q != 1.0:
                c_q = c_q * a_q
            q = c_q @ lp["w_qb"].astype(dt)
        else:
            q = y @ lp["w_q"].astype(dt)
        q = q.reshape(b, s, h, dn + dr)
        kva = y @ lp["w_kva"].astype(dt)
        c_kv = rms_norm(kva[..., :rkv], lp["kv_a_norm"], eps)
        if a_kv != 1.0:
            c_kv = c_kv * a_kv
        kv = (c_kv @ lp["w_kvb"].astype(dt)).reshape(b, s, h, dn + dv)
        k_rope = kva[:, :, None, rkv:]
        if inv_freq is not None:
            q_rope = apply_rope(q[..., dn:], positions, inv_freq)
            k_rope = apply_rope(k_rope, positions, inv_freq)
            if cfg.rope_magnitude != 1.0:
                q_rope = q_rope * cfg.rope_magnitude
                k_rope = k_rope * cfg.rope_magnitude
            q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        # the rotary part of a key is one vector for all heads
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
        v = kv[..., dn:]
    if attend is not None:
        out = attend(q, k, v, c_q)
    else:
        out = flash_attention(q, k, v, causal=True, mesh=mesh,
                              scale=cfg.softmax_scale, window=window)
    if "w_g" in lp:
        with trace.scope("attn_gate"):
            gate = jax.nn.sigmoid(y @ lp["w_g"].astype(dt))
            out = out * gate[..., None]
    with trace.scope("mla_proj"):
        return out.reshape(b, s, h * dv) @ lp["w_o"].astype(dt)


def block(cfg: Xing4Config, mesh, positions, inv_freq, lp: Params, X):
    """Attention and feed-forward, each wrapped in its stream mixing.
    The feed-forward is the expert layer where ``lp`` has a router, the
    dense SwiGLU otherwise."""
    eps = cfg.norm_eps

    def attention(y):
        with trace.scope("norm"):
            y = rms_norm(y, lp["attn_norm"], eps)
        return latent_attention(cfg, mesh, positions, inv_freq, lp, y)

    def feed_forward(y):
        with trace.scope("norm"):
            y = rms_norm(y, lp["mlp_norm"], eps)
        if "router" in lp:
            return moe.moe_mlp(cfg.as_moe(), lp, y, mesh)[0]
        with trace.scope("dense_mlp"):
            return llama.swiglu(
                y, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)

    X = hc_sublayer(cfg, lp, "hc_attn", X, attention, mesh=mesh)
    X = hc_sublayer(cfg, lp, "hc_mlp", X, feed_forward, mesh=mesh)
    if mesh is not None:
        X = lax.with_sharding_constraint(
            X, NamedSharding(mesh, P(None, BATCH_AXES, None, None)))
    return X


def _report_shapes(cfg: Xing4Config):
    """The gauges that say what this build's blocks are (set while the
    step is traced, as ``attn.block_q`` is)."""
    trace.gauge("mla.qk_head_dim", cfg.qk_head_dim)
    trace.gauge("mla.v_head_dim", cfg.v_head_dim)
    trace.gauge("mla.q_lora_rank", cfg.q_lora_rank)
    trace.gauge("mla.kv_lora_rank", cfg.kv_lora_rank)
    trace.gauge("attn.scale", cfg.softmax_scale)
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one (`_block_fn`)
    trace.gauge("hc.streams", cfg.hc_mult)
    trace.gauge("hc.sinkhorn_iters", cfg.hc_sinkhorn_iters)
    trace.gauge("mtp.depth", cfg.mtp_depth)
    trace.gauge("mtp.loss_weight", cfg.mtp_loss_weight)


def rotary_tables(cfg: Xing4Config, tokens):
    """``(positions (b, s), inv_freq)`` of a batch's rotary."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return positions, yarn_frequencies(
        cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max, cfg.yarn_beta_fast, cfg.yarn_beta_slow)


def _block_fn(cfg: Xing4Config, mesh, tokens):
    """A block is recomputed whole in the backward pass, but for the
    flash forward's output and ``lse``, its backward's residuals (65 MiB
    a layer at 2 x 4096 tokens): the kernel runs once a step."""
    return stack.recompute(
        functools.partial(block, cfg, mesh, *rotary_tables(cfg, tokens)),
        cfg.remat, attention.KEPT, attention.report_kept)


def _streams(cfg: Xing4Config, x):
    return jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape)


def _sum_streams(X):
    return jnp.sum(X, axis=0, dtype=jnp.float32).astype(X.dtype)


def forward_streams(
    params: Params, tokens: jnp.ndarray, cfg: Xing4Config,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The summed streams after the last block, before the final norm:
    (b, s, dim)."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    block_fn = _block_fn(cfg, mesh, tokens)
    X = _streams(cfg, embed_lookup(params["embed"], tokens, mesh, cfg.dtype))

    def body(X, lp):
        return block_fn(lp, X), None

    X, _ = lax.scan(body, X, params["dense"])
    X, _ = lax.scan(body, X, params["layers"])
    return _sum_streams(X)


def _mtp_hidden(params: Params, tokens, targets, h, cfg: Xing4Config, mesh):
    """The multi-token module's states (b, s, dim) before the head:
    position ``i`` holds what predicts ``t_{i+2}``. ``targets`` is
    ``t_{i+1}`` (-1 past the end, where 0 stands in: causal attention
    keeps what follows out of every position that has a target)."""
    mp, eps = params["mtp"], cfg.norm_eps
    with trace.scope("mtp"):
        e = embed_lookup(
            params["embed"], jnp.maximum(targets, 0), mesh, cfg.dtype)
        with trace.scope("norm"):
            both = jnp.concatenate(
                [rms_norm(e, mp["enorm"], eps),
                 rms_norm(h, mp["hnorm"], eps)], axis=-1)
        X = _streams(cfg, both @ mp["w_eh"].astype(cfg.dtype))
        lp = jax.tree.map(lambda a: a[0], mp["block"])
        X = _block_fn(cfg, mesh, tokens)(lp, X)
        h = _sum_streams(X)
        with trace.scope("norm"):
            return rms_norm(h, mp["norm"], eps)


def loss_terms(
    params: Params, tokens: jnp.ndarray, cfg: Xing4Config,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(CE_main, CE_mtp, hidden)``: mean next-token and next-but-one
    cross-entropy through the one head (pad tokens < 0 ignored), and the
    summed streams both heads start from."""
    h = forward_streams(params, tokens, cfg, mesh)
    ce = functools.partial(
        stack.next_token_loss, chunk_size=cfg.ce_chunk_size, mesh=mesh)
    with trace.scope("norm"):
        x = rms_norm(h, params["final_norm"], cfg.norm_eps)
    main = ce(x, params["lm_head"], tokens)
    if not cfg.mtp_depth:
        return main, jnp.zeros((), jnp.float32), h
    targets = stack.shift_targets(tokens)   # the second head's tokens
    x = _mtp_hidden(params, tokens, targets, h, cfg, mesh)
    with trace.scope("mtp"):
        return main, ce(x, params["lm_head"], targets), h


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: Xing4Config,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    main, mtp, _ = loss_terms(params, tokens, cfg, mesh)
    return main + cfg.mtp_loss_weight * mtp
