"""Mixtral-family sparse-MoE decoder with expert parallelism, TPU-first.

Expert parallelism is green-field relative to the reference (it is only
checkpoint-aware of Megatron EP ranks, ``megatron_dist_ckpt.py:247``); here
it is a real compute path:

- **dense one-hot dispatch** (GShard/Switch style): routing builds
  ``dispatch``/``combine`` tensors and the token->expert shuffle is two
  einsums — everything stays MXU-shaped matmuls, and with expert weights
  sharded ``P(EP, ...)`` and tokens sharded over the batch axes the XLA
  SPMD partitioner inserts the all-to-alls over ICI itself. No per-token
  gather/scatter, no dynamic shapes.
- **capacity factor** bounds per-expert work so shapes are static under
  jit; overflow tokens fall through the residual (standard Switch
  behavior).
- **aux load-balance loss** (Switch Transformers eq. 4) keeps routing
  uniform; it is accumulated through the layer scan.
- attention/rope/norm reuse the Llama blocks (ring attention over sp when
  the mesh has it).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama
from dlrover_tpu.ops import (
    apply_rope,
    chunked_ce_enabled,
    cross_entropy_sums,
    embed_lookup,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, SP, TP

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    max_seq_len: int = 8192
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "auto"
    # chunked fused cross-entropy (ops/chunked_ce.py): vocab columns per
    # loss scan step; DLROVER_TPU_CHUNKED_CE=0 restores dense logits
    ce_chunk_size: int = 2048

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def as_llama(self) -> llama.LlamaConfig:
        """The attention-relevant view (reused Llama blocks)."""
        return llama.LlamaConfig(
            vocab_size=self.vocab_size,
            dim=self.dim,
            n_layers=self.n_layers,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            ffn_dim=self.ffn_dim,
            max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            remat=self.remat,
            attn_impl=self.attn_impl,
            ce_chunk_size=self.ce_chunk_size,
        )

    # ---- presets -------------------------------------------------------
    @staticmethod
    def mixtral_8x7b() -> "MoeConfig":
        return MoeConfig()

    @staticmethod
    def tiny(**kw) -> "MoeConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, n_experts=4, experts_per_token=2,
            max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return MoeConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg: MoeConfig, rng: jax.Array) -> Params:
    pd = cfg.param_dtype
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    std = 0.02
    L, D, E, F = cfg.n_layers, cfg.dim, cfg.n_experts, cfg.ffn_dim
    H = cfg.n_heads * cfg.head_dim
    KV = cfg.n_kv_heads * cfg.head_dim

    def norm_init(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    ks = jax.random.split(k_layers, 8)
    out_scale = std / (2 * cfg.n_layers) ** 0.5
    layers = {
        "attn_norm": jnp.ones((L, D), pd),
        "wq": norm_init(ks[0], (L, D, H), std),
        "wk": norm_init(ks[1], (L, D, KV), std),
        "wv": norm_init(ks[2], (L, D, KV), std),
        "wo": norm_init(ks[3], (L, H, D), out_scale),
        "mlp_norm": jnp.ones((L, D), pd),
        "router": norm_init(ks[4], (L, D, E), std),
        "w_gate": norm_init(ks[5], (L, E, D, F), std),
        "w_up": norm_init(ks[6], (L, E, D, F), std),
        "w_down": norm_init(ks[7], (L, E, F, D), out_scale),
    }
    return {
        "embed": norm_init(k_embed, (cfg.vocab_size, D), std),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": norm_init(k_head, (D, cfg.vocab_size), std),
    }


def param_specs(cfg: MoeConfig) -> Params:
    """Expert weights shard over EP on the expert axis; within an expert
    the ffn shards like the dense model (fsdp x tp)."""
    return {
        "embed": P(TP, FSDP),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, FSDP, TP),
            "wk": P(None, FSDP, TP),
            "wv": P(None, FSDP, TP),
            "wo": P(None, TP, FSDP),
            "mlp_norm": P(None, None),
            "router": P(None, FSDP, None),
            "w_gate": P(None, EP, FSDP, TP),
            "w_up": P(None, EP, FSDP, TP),
            "w_down": P(None, EP, TP, FSDP),
        },
        "final_norm": P(None),
        "lm_head": P(FSDP, TP),
    }


def abstract_params(cfg: MoeConfig) -> Params:
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def param_count(cfg: MoeConfig) -> int:
    import math

    return sum(
        math.prod(l.shape) for l in jax.tree.leaves(abstract_params(cfg))
    )


def active_param_count(cfg: MoeConfig) -> int:
    """Params touched per token (the 'x7B' in 8x7B marketing math)."""
    total = param_count(cfg)
    expert = 3 * cfg.dim * cfg.ffn_dim * cfg.n_layers
    return total - expert * (cfg.n_experts - cfg.experts_per_token)


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------

def _capacity(tokens: int, cfg: MoeConfig) -> int:
    cap = int(
        cfg.capacity_factor * tokens * cfg.experts_per_token / cfg.n_experts
    )
    return max(cap, cfg.experts_per_token)


def moe_mlp(
    cfg: MoeConfig, lp: Params, y: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    dt = cfg.dtype
    b, s, d = y.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(t, cfg)
    yt = y.reshape(t, d)

    router_logits = (yt @ lp["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)  # (t, e)
    top_p, top_e = lax.top_k(probs, k)  # (t, k)
    # renormalize the chosen experts' weights (mixtral convention)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) in its expert's capacity buffer
    choice_mask = jax.nn.one_hot(top_e, e, dtype=jnp.float32)  # (t, k, e)
    # order: all k=0 choices first, then k=1 — priority to primary experts
    flat_mask = choice_mask.transpose(1, 0, 2).reshape(k * t, e)
    pos_in_expert = (jnp.cumsum(flat_mask, axis=0) - 1.0) * flat_mask
    pos_in_expert = pos_in_expert.reshape(k, t, e).transpose(1, 0, 2)
    within_cap = (pos_in_expert < cap).astype(jnp.float32) * choice_mask

    # dispatch (t, e, cap) one-hot; combine carries router weights
    # (positions where the mask is 0 one-hot to slot 0 but are zeroed by
    # the within_cap factor in the einsums below)
    pos_oh = jax.nn.one_hot(
        pos_in_expert.astype(jnp.int32), cap, dtype=jnp.float32
    )
    dispatch = jnp.einsum("tke,tkec->tec", within_cap, pos_oh)
    combine = jnp.einsum(
        "tke,tkec->tec", within_cap * top_p[..., None], pos_oh
    )

    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), yt)
    gate = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", expert_in, lp["w_gate"].astype(dt))
    )
    up = jnp.einsum("ecd,edf->ecf", expert_in, lp["w_up"].astype(dt))
    expert_out = jnp.einsum(
        "ecf,efd->ecd", gate * up, lp["w_down"].astype(dt)
    )
    out = jnp.einsum("tec,ecd->td", combine.astype(dt), expert_out)

    # Switch aux loss: E * sum_e(fraction_dispatched_e * mean_prob_e)
    fraction = jnp.einsum("tke->e", choice_mask) / (t * k)
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(fraction * mean_prob)
    return out.reshape(b, s, d), aux


def _decoder_layer(cfg: MoeConfig, mesh, inv_freq, positions, lp, x):
    dt = cfg.dtype
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (y @ lp["wq"].astype(dt)).reshape(b, s, h, hd)
    k = (y @ lp["wk"].astype(dt)).reshape(b, s, kvh, hd)
    v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    attn = llama._attention(cfg.as_llama(), mesh, q, k, v).reshape(b, s, h * hd)
    x = x + attn @ lp["wo"].astype(dt)

    y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    moe_out, aux = moe_mlp(cfg, lp, y)
    x = x + moe_out

    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, SP, None))
        )
    return x, aux


def validate_for_mesh(cfg: MoeConfig, mesh: Mesh, seq_len: int = 0) -> None:
    llama.validate_for_mesh(cfg.as_llama(), mesh, seq_len)
    ep = dict(mesh.shape).get(EP, 1)
    if cfg.n_experts % max(1, ep):
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by mesh ep={ep}"
        )


def forward_hidden(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoeConfig,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(final-norm hidden states (b, s, dim), aux_loss scalar) — the
    pre-unembed factorization the chunked-CE loss fuses the lm-head into
    (same split as models/llama.py forward_hidden)."""
    b, s = tokens.shape
    if mesh is not None:
        validate_for_mesh(cfg, mesh, seq_len=s)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    layer_fn = functools.partial(_decoder_layer, cfg, mesh, inv_freq, positions)
    if cfg.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable
        )

    def scan_body(carry, lp):
        x, aux_sum = carry
        x, aux = layer_fn(lp, x)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_sum / cfg.n_layers


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoeConfig,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(logits (b, s, vocab) float32, aux_loss scalar)."""
    x, aux = forward_hidden(params, tokens, cfg, mesh)
    logits = x.astype(jnp.float32) @ params["lm_head"].astype(jnp.float32)
    return logits, aux


def loss_fn(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoeConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Next-token CE + router aux loss (pad tokens < 0 ignored)."""
    if chunked_ce_enabled():
        x, aux = forward_hidden(params, tokens, cfg, mesh)
        # f32 operands, matching this model's dense unembed contract
        # (x.astype(f32) @ lm_head.astype(f32)) — the op casts w to x's
        # dtype, so promoting x keeps chunked-vs-dense numerics identical
        # rather than silently moving MoE to bf16-operand logits
        nll_sum, n_valid = cross_entropy_sums(
            x.astype(jnp.float32), params["lm_head"],
            llama._shift_targets(tokens),
            chunk_size=cfg.ce_chunk_size, mesh=mesh,
        )
        ce = nll_sum / jnp.maximum(n_valid, 1.0)
        return ce + cfg.router_aux_coef * aux
    logits, aux = forward(params, tokens, cfg, mesh)
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    valid = (targets >= 0).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    nll = (logz - gold) * valid
    ce = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)
    return ce + cfg.router_aux_coef * aux
