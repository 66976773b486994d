"""Vision Transformer (ViT) family — the CV model line.

The reference trains CV workloads through its examples (mnist / resnet
under ``examples/pytorch``); this is the TPU-native counterpart built on
the same primitives as the LM families: scan-over-layers encoder blocks,
the Pallas flash kernel (non-causal), rms-norm, and the dp/fsdp/tp mesh
axes — so the elastic trainer, flash checkpoint, and the dryrun treat a
vision model exactly like a language model.

Architecture: patchify via a strided conv expressed as an unfold+matmul
(MXU-friendly, no conv lowering edge cases), learned position embeddings,
pre-norm encoder blocks with gelu MLP, mean-pool head.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dlrover_tpu.models import stack
from dlrover_tpu.ops.attention import (
    flash_attention,
    flash_tiles,
    mha_reference,
)
from dlrover_tpu.ops.fused_ce import cross_entropy_sums
from dlrover_tpu.ops.norms import rms_norm
from dlrover_tpu.parallel.mesh import BATCH_AXES, FSDP, TP

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    n_classes: int = 1000
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "flash"  # flash | reference

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.channels * self.patch_size * self.patch_size

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError("image_size must be a multiple of patch_size")
        if self.dim % self.n_heads:
            raise ValueError("dim must divide by n_heads")

    @staticmethod
    def tiny(**kw) -> "ViTConfig":
        base = dict(
            image_size=32, patch_size=8, channels=3, n_classes=10,
            dim=64, n_layers=2, n_heads=4, mlp_dim=128,
            dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return ViTConfig(**base)

    @staticmethod
    def base_16() -> "ViTConfig":
        """ViT-B/16."""
        return ViTConfig()


def init_params(cfg: ViTConfig, rng: jax.Array) -> Params:
    pd = cfg.param_dtype
    D, L = cfg.dim, cfg.n_layers
    k_patch, k_pos, k_layers, k_head = jax.random.split(rng, 4)

    def init(key, shape, fan_in):
        return (jax.random.normal(key, shape, pd)
                * (1.0 / math.sqrt(fan_in)))

    def layer_leaf(key, shape, fan_in):
        keys = jax.random.split(key, L)
        return jnp.stack([init(k, shape, fan_in) for k in keys])

    ks = jax.random.split(k_layers, 4)
    return {
        "patch_embed": init(k_patch, (cfg.patch_dim, D), cfg.patch_dim),
        "pos_embed": jax.random.normal(k_pos, (cfg.n_patches, D), pd) * 0.02,
        "layers": {
            "attn_norm": jnp.ones((L, D), pd),
            "wqkv": layer_leaf(ks[0], (D, 3 * D), D),
            "wo": layer_leaf(ks[1], (D, D), D),
            "mlp_norm": jnp.ones((L, D), pd),
            "w_up": layer_leaf(ks[2], (D, cfg.mlp_dim), D),
            "w_down": layer_leaf(ks[3], (cfg.mlp_dim, D), cfg.mlp_dim),
        },
        "final_norm": jnp.ones((D,), pd),
        "head": init(k_head, (D, cfg.n_classes), D),
    }


def param_specs(cfg: ViTConfig) -> Params:
    return {
        "patch_embed": P(None, FSDP),
        "pos_embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wqkv": P(None, FSDP, TP),
            "wo": P(None, TP, FSDP),
            "mlp_norm": P(None, None),
            "w_up": P(None, FSDP, TP),
            "w_down": P(None, TP, FSDP),
        },
        "final_norm": P(None),
        "head": P(FSDP, TP),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def patchify(cfg: ViTConfig, images: jnp.ndarray) -> jnp.ndarray:
    """(b, H, W, C) -> (b, n_patches, patch_dim) by unfold — the strided
    patch conv as one reshape+matmul-ready layout (keeps XLA on the MXU
    instead of conv paths for a kernel the size of the stride)."""
    b, hgt, wid, c = images.shape
    p = cfg.patch_size
    gh, gw = hgt // p, wid // p
    x = images.reshape(b, gh, p, gw, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # b, gh, gw, p, p, c
    return x.reshape(b, gh * gw, p * p * c)


def _encoder_layer(cfg: ViTConfig, mesh, lp, x):
    dt = cfg.dtype
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim

    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    qkv = (y @ lp["wqkv"].astype(dt)).reshape(b, s, 3, h, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    # patch counts are rarely powers of two (ViT-B/16: 196, with the
    # class token 197, a prime): the kernel's chooser takes such a
    # sequence as one block, which is always a legal tile. Only one too
    # long for that has no tile, and takes full attention.
    if (cfg.attn_impl == "reference"
            or flash_tiles(s, s, hd, 1, q.dtype) is None):
        attn = mha_reference(q, k, v, causal=False)
    else:
        attn = flash_attention(q, k, v, causal=False, mesh=mesh)
    x = x + attn.reshape(b, s, d) @ lp["wo"].astype(dt)

    y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + jax.nn.gelu(y @ lp["w_up"].astype(dt)) @ lp["w_down"].astype(dt)
    return x


def forward_pooled(params: Params, images: jnp.ndarray, cfg: ViTConfig,
                   mesh=None) -> jnp.ndarray:
    """(b, H, W, C) float images -> (b, dim) mean-pooled features (the
    pre-head factorization shared with the LM families' forward_hidden,
    so the loss can fuse the classifier matmul into the CE)."""
    dt = cfg.dtype
    x = patchify(cfg, images.astype(dt)) @ params["patch_embed"].astype(dt)
    x = x + params["pos_embed"].astype(dt)[None]

    layer_fn = stack.recompute(
        functools.partial(_encoder_layer, cfg, mesh), cfg.remat)
    x, _ = lax.scan(
        lambda x, lp: (layer_fn(lp, x), None), x, params["layers"])
    if mesh is not None:
        from jax.sharding import NamedSharding

        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None))
        )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x.mean(axis=1)


def forward(params: Params, images: jnp.ndarray, cfg: ViTConfig,
            mesh=None) -> jnp.ndarray:
    """(b, H, W, C) float images -> (b, n_classes) logits."""
    pooled = forward_pooled(params, images, cfg, mesh)
    return (pooled @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def loss_fn(params: Params, batch, cfg: ViTConfig, mesh=None) -> jnp.ndarray:
    """Softmax cross entropy; ``batch`` = (images, int labels). Labels
    < 0 are the pad sentinel (``pad_batch_to`` after an elastic resize)
    and contribute nothing."""
    images, labels = batch
    # same fused head-matmul + masked-CE path as the LM families —
    # n_classes is small so one chunk covers it (the op clips), but
    # sharing the op keeps the CE semantics (pad < 0, f32 MXU
    # accumulation) defined in exactly one place
    pooled = forward_pooled(params, images, cfg, mesh)
    nll_sum, n_valid = cross_entropy_sums(
        pooled, params["head"], labels, mesh=mesh
    )
    return nll_sum / jnp.maximum(n_valid, 1.0)
