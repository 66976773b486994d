"""The ``kimi_linear`` decoder family (Kimi-Linear-48B-A3B, arXiv
2510.26692): layers of two attention kinds in one **layer pattern**,
Kimi Delta Attention (a gated delta rule with a decay per channel,
``ops/kda.py``) and latent attention without rotary, over a leading
dense feed-forward and then sigmoid-routed experts with a shared expert.

Every piece that another family has is that family's: latent attention
is ``xing4.latent_attention`` (here with no q rank and no rotary), the
expert path is ``models/moe.py``'s (this file hands it a ``MoeConfig``
view), the dense feed-forward is ``llama.swiglu``, the embedding and the
fused cross-entropy are the shared ops.

What is this family's own:

- **the layer pattern** (``KimiLinearConfig.pattern``): the one place
  that says, layer by layer, which attention (``"kda"`` or ``"mla"``,
  from the config's ``kda_layers`` / ``full_attn_layers``, numbered from
  1 as published) and which feed-forward (``"dense"`` for the first
  ``n_dense_layers``, ``"moe"`` after). Consecutive layers of one kind
  are a *run*: the parameters are stacked a run (``params["runs"]``)
  and ``stack.walk`` scans each run over the one ``block`` function, so
  the published 27 layers compile as 15 loops over three bodies, not as
  27 inlined blocks, and no slab is ever sliced.
- **the KDA layer** (``kda_attention``), ``h`` heads of ``d`` = 128::

      q~, k~, v~ = SiLU(Conv4(x W_q)), SiLU(Conv4(x W_k)), SiLU(Conv4(x W_v))
      q = L2norm(q~) d^-1/2;  k = L2norm(k~);  v = v~          a head each
      g = -exp(A_log) softplus(x W_f1 W_f2 + dt_bias)   log-decay a channel
      b = sigmoid(x w_b)                                    step size a head
      o = the gated delta rule over (q, k, v, g, b)          ops/kda.py
      y = W_o concat_h[RMSNorm_d(o) * sigmoid(x W_g1 W_g2 + b_g2)]

  under the named scopes ``kda_proj``, ``kda_conv`` (the second line
  too: ``kda.conv_silu_norm``), ``kda_gate`` (``g`` and ``b``),
  ``kda_chunk``, ``kda_out`` (``kda.norm_gate`` and ``W_o``).
- every block is ``h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h))``; no aux
  loss (the choice bias balances the load in the published recipe, by an
  update this program does not make, as in ``models/xing4.py``).
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

from dlrover_tpu.models import llama, moe, stack, xing4
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention, embed_lookup, kda, rms_norm
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]

_PUBLISHED_KDA = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                  22, 23, 25, 26)
_PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """moonshotai/Kimi-Linear-48B-A3B-Instruct's config.json by default."""
    vocab_size: int = 163840
    dim: int = 2304
    n_layers: int = 27
    kda_layers: Tuple[int, ...] = _PUBLISHED_KDA     # numbered from 1
    full_attn_layers: Tuple[int, ...] = _PUBLISHED_FULL
    n_dense_layers: int = 1          # first_k_dense_replace
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4               # short_conv_kernel_size
    kda_chunk: int = 64
    n_heads: int = 32                # latent attention's
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64            # no rotary on it (mla_use_nope)
    v_head_dim: int = 128
    dense_ffn_dim: int = 9216
    expert_ffn_dim: int = 1024
    n_experts: int = 256             # the router's width
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling: float = 2.446
    scoring: str = "sigmoid"
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        layers = sorted(self.kda_layers + self.full_attn_layers)
        if layers != list(range(1, self.n_layers + 1)):
            raise ValueError(
                f"kda_layers {self.kda_layers} and full_attn_layers "
                f"{self.full_attn_layers} do not name each of the layers "
                f"1..{self.n_layers} once"
            )

    @property
    def pattern(self) -> Tuple[Tuple[str, str], ...]:
        """``(attention, feed-forward)`` of each layer, first to last."""
        return tuple(
            ("kda" if i in self.kda_layers else "mla",
             "dense" if i <= self.n_dense_layers else "moe")
            for i in range(1, self.n_layers + 1)
        )

    @property
    def pattern_string(self) -> str:
        """A letter a layer: K for KDA, L for latent attention."""
        return "".join("K" if a == "kda" else "L" for a, _ in self.pattern)

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """The pattern as runs of like layers, each a stacked part."""
        return stack.runs(self.pattern)

    @property
    def runs(self) -> Tuple[Tuple[str, str, int], ...]:
        """``(attention, feed-forward, how many)`` of each run."""
        return tuple((*part.kinds[0], part.repeats) for part in self.layout)

    # what xing4.latent_attention reads of a config
    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_layers, n_heads=self.n_heads,
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
    def tiny(**kw) -> "KimiLinearConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=5, kda_layers=(1, 2, 3, 5),
            full_attn_layers=(4,), kda_heads=4, kda_head_dim=16,
            kda_chunk=16, n_heads=4, kv_lora_rank=16, qk_nope_dim=16,
            qk_rope_dim=8, v_head_dim=16, dense_ffn_dim=96,
            expert_ffn_dim=32, n_experts=8, experts_per_token=2,
            dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return KimiLinearConfig(**base)


def run_name(i: int) -> str:
    """The key of run ``i`` in ``params["runs"]``."""
    return f"run{i:02d}"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: KimiLinearConfig, attn: str, ffn: str
                  ) -> Dict[str, Tuple]:
    """``{name: (shape, init, spec)}`` of one block. ``init`` is
    "normal", "ones", "zeros" or a KDA rule; ``spec`` the partition of
    the leaf's own axes: a matrix shards its model-width side over fsdp,
    an expert layer's stack of experts over ep, the rest is replicated."""
    D = cfg.dim
    rows, cols, rep = (FSDP, None), (None, FSDP), (None, None)
    shapes = {"attn_norm": ((D,), "ones", (None,)),
              "mlp_norm": ((D,), "ones", (None,))}
    if attn == "kda":
        h, d = cfg.kda_heads, cfg.kda_head_dim
        for name in ("q", "k", "v"):
            shapes[f"w_{name}"] = ((D, h * d), "normal", rows)
            shapes[f"conv_{name}"] = ((h * d, cfg.conv_size), "conv", rep)
        shapes.update({
            "w_f1": ((D, d), "normal", rows),
            "w_f2": ((d, h * d), "normal", rep),
            "a_log": ((h,), "a_log", (None,)),
            "dt_bias": ((h * d,), "dt_bias", (None,)),
            "w_b": ((D, h), "normal", rows),
            "w_g1": ((D, d), "normal", rows),
            "w_g2": ((d, h * d), "normal", rep),
            "b_g2": ((h * d,), "zeros", (None,)),
            "o_norm": ((d,), "ones", (None,)),
            "w_o": ((h * d, D), "normal", cols),
        })
    else:
        h, rkv = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        shapes.update({
            "w_q": ((D, h * (dn + dr)), "normal", rows),
            "w_kva": ((D, rkv + dr), "normal", rows),
            "kv_a_norm": ((rkv,), "ones", (None,)),
            "w_kvb": ((rkv, h * (dn + dv)), "normal", rep),
            "w_o": ((h * dv, D), "normal", cols),
        })
    if ffn == "dense":
        F = cfg.dense_ffn_dim
        shapes.update({
            "w_gate": ((D, F), "normal", rows),
            "w_up": ((D, F), "normal", rows),
            "w_down": ((F, D), "normal", cols),
        })
        return shapes
    E, F = cfg.as_moe().n_held, cfg.expert_ffn_dim
    shapes.update({
        "router": ((D, cfg.n_experts), "normal", rows),
        "router_bias": ((cfg.n_experts,), "zeros", (None,)),
        "w_gate": ((E, D, F), "normal", (EP,) + rows),
        "w_up": ((E, D, F), "normal", (EP,) + rows),
        "w_down": ((E, F, D), "normal", (EP,) + cols),
    })
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        shapes.update({
            "ws_gate": ((D, Fs), "normal", rows),
            "ws_up": ((D, Fs), "normal", rows),
            "ws_down": ((Fs, D), "normal", cols),
        })
    return shapes


def _init_leaf(key, shape, rule: str, conv_size: int):
    if rule == "normal":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if rule == "ones":
        return jnp.ones(shape, jnp.float32)
    if rule == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if rule == "conv":
        # a depthwise Conv1d's default: uniform within fan_in^-1/2
        bound = conv_size ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if rule == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    # dt_bias: softplus^-1 of a step drawn log-uniform over [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_slab(cfg: KimiLinearConfig, key, run: Tuple[str, str, int]
               ) -> Params:
    attn, ffn, layers = run
    shapes = _block_shapes(cfg, attn, ffn)
    return {
        name: _init_leaf(k, (layers,) + shape, rule, cfg.conv_size).astype(
            cfg.param_dtype)
        for k, (name, (shape, rule, _)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))
    }


def init_params(cfg: KimiLinearConfig, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_head, k_runs = jax.random.split(rng, 3)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(pd)

    return {
        "embed": normal(k_embed, (V, D)),
        "runs": {
            run_name(i): _init_slab(cfg, k, run)
            for i, (k, run) in enumerate(zip(
                jax.random.split(k_runs, len(cfg.runs)), cfg.runs))
        },
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def param_specs(cfg: KimiLinearConfig) -> Params:
    """Data and expert parallelism only (``validate_for_mesh``). The
    leading axis of a run's leaves is the layer."""
    return {
        "embed": P(None, FSDP),
        "runs": {
            run_name(i): {
                name: P(None, *spec) for name, (_, _, spec)
                in _block_shapes(cfg, attn, ffn).items()
            }
            for i, (attn, ffn, _) in enumerate(cfg.runs)
        },
        "final_norm": P(None),
        "lm_head": P(FSDP, None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def validate_for_mesh(cfg: KimiLinearConfig, mesh: Mesh, batch: int = 0
                      ) -> None:
    shape = dict(mesh.shape)
    for axis in (TP, SP, PP):
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"kimi_linear: mesh {axis}={shape[axis]}: a KDA layer's "
                "recurrent state is not handed across ranks and latent "
                "attention runs whole heads and whole sequences on a "
                "device (dp, fsdp and ep only)"
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
# The KDA layer, the block, the forward
# ---------------------------------------------------------------------------

def kda_inputs(cfg: KimiLinearConfig, lp: Params, y, mesh=None,
               interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> what the delta rule takes (``q, k,
    v (b, s, h, 128)`` in the activation dtype, log-decay ``g (b, s, h,
    128)`` and step ``beta (b, s, h)`` float32) and the output gate's
    logits ``(b, s, h, 128)``. ``interpret`` (tests): the layer's Pallas
    forms on the CPU."""
    dt = cfg.dtype
    b, s, _ = y.shape
    h, d = cfg.kda_heads, cfg.kda_head_dim
    f32 = jnp.float32
    with trace.scope("kda_proj"):
        qkv = [y @ lp[name].astype(dt) for name in ("w_q", "w_k", "w_v")]
        decay = (y @ lp["w_f1"].astype(dt)) @ lp["w_f2"].astype(dt)
        gate = ((y @ lp["w_g1"].astype(dt)) @ lp["w_g2"].astype(dt)
                + lp["b_g2"].astype(dt))
        step = y @ lp["w_b"].astype(dt)
    with trace.scope("kda_conv"):
        q, k, v = kda.conv_silu_norm(
            qkv, [lp[name] for name in ("conv_q", "conv_k", "conv_v")],
            heads=h, scales=(d ** -0.5, 1.0, None), interpret=interpret,
            mesh=mesh)
    with trace.scope("kda_gate"):
        g = -jnp.exp(lp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
            decay.astype(f32) + lp["dt_bias"].astype(f32)).reshape(b, s, h, d)
        beta = jax.nn.sigmoid(step.astype(f32))
    return q, k, v, g, beta, gate.reshape(b, s, h, d)


def kda_attention(cfg: KimiLinearConfig, lp: Params, y, mesh=None,
                  interpret: bool = False):
    q, k, v, g, beta, gate = kda_inputs(cfg, lp, y, mesh, interpret)
    with trace.scope("kda_chunk"):
        o = kda.chunk_kda(q, k, v, g, beta, chunk=cfg.kda_chunk,
                          interpret=interpret, mesh=mesh)
    with trace.scope("kda_out"):
        o = kda.norm_gate(o, gate, lp["o_norm"], cfg.norm_eps,
                          interpret=interpret, mesh=mesh)
        return o @ lp["w_o"].astype(cfg.dtype)


def block(cfg: KimiLinearConfig, mesh, attn: str, ffn: str, lp: Params, x):
    """``h += Attn(RMSNorm(h)); h += FFN(RMSNorm(h))`` for the kinds the
    pattern gives this layer."""
    eps = cfg.norm_eps
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], eps)
    if attn == "kda":
        x = x + kda_attention(cfg, lp, y, mesh=mesh)
    else:
        x = x + xing4.latent_attention(cfg, mesh, None, None, lp, y)
    with trace.scope("norm"):
        y = rms_norm(x, lp["mlp_norm"], eps)
    if ffn == "moe":
        x = x + moe.moe_mlp(cfg.as_moe(), lp, y, mesh)[0]
    else:
        with trace.scope("dense_mlp"):
            y = llama.swiglu(
                y, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)
        x = x + y
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def _report_shapes(cfg: KimiLinearConfig):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    trace.gauge("kda.layers", len(cfg.kda_layers))
    trace.gauge("kda.heads", cfg.kda_heads)
    trace.gauge("kda.head_dim", cfg.kda_head_dim)
    trace.gauge("kda.chunk", cfg.kda_chunk)
    trace.gauge("kda.conv", cfg.conv_size)
    trace.gauge("kda.state_kept", 0)  # as `attn.out_kept`, of the rule's states
    trace.gauge("mla.qk_head_dim", cfg.qk_nope_dim + cfg.qk_rope_dim)
    trace.gauge("mla.v_head_dim", cfg.v_head_dim)
    trace.gauge("mla.kv_lora_rank", cfg.kv_lora_rank)
    trace.gauge("mla.q_rank", 0)
    trace.gauge("mla.rotary", 0)
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one (`_block_fn`)
    trace.gauge("attn.scale", cfg.softmax_scale)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _report_kept(name: str):
    """``recompute``'s one callback feeds both kernels' gauges."""
    attention.report_kept(name)
    kda.report_kept(name)


def _block_fn(cfg: KimiLinearConfig, mesh, attn: str, ffn: str):
    """A block is recomputed whole in the backward pass, but for what its
    attention's forward kernel leaves its backward: the flash forward's
    output and ``lse`` (65 MiB a latent layer at 8192 tokens), the delta
    rule's output and the float32 state every chunk started from (64 +
    256 MiB a KDA layer). Either kernel runs once a step."""
    return stack.recompute(
        functools.partial(block, cfg, mesh, attn, ffn), cfg.remat,
        attention.KEPT + kda.KEPT, _report_kept)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: KimiLinearConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm:
    (b, s, dim). One scan a run of like layers."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    fns = {kind: _block_fn(cfg, mesh, *kind) for kind in set(cfg.pattern)}
    trees = [(params["runs"][run_name(i)],) for i in range(len(cfg.layout))]
    return stack.walk(x, cfg.layout, trees,
                      lambda kind, lp, x: (fns[kind](lp, x), None))[0]


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: KimiLinearConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored)."""
    x = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
