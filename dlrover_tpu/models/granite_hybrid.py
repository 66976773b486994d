"""The ``granite_hybrid`` decoder family (``granitemoehybrid``:
granite-4.0-h-small, 32B-A9B): **Mamba-2 state-space layers** nine to one
with **softmax attention without rotary**, every layer over softmax-routed
experts beside a shared SwiGLU of its own width, under Granite's four
multipliers and a **tied** head.

Every piece another family has is that family's: the expert path is
``models/moe.py``'s (this file hands it a ``MoeConfig`` view: the ten
largest of the router's 72 logits, weighed by the softmax over those ten,
the held share; its load-balancing loss is not in this model's loss),
attention is ``ops/attention.py``'s flash kernels at the model's own
scale, the convolution is ``ops/kda.py``'s XLA form, the embedding and the
fused cross-entropy are the shared ops, ``models/stack.py`` lays the
period out and walks it. The scan is ``ops/ssd.py``.

What is this family's own (``e, r, a, l`` the embedding, residual,
attention and logits multipliers; ``Norm`` an RMSNorm with a weight)::

    x_0 = e E[tokens]
    h = x + r Mixer(Norm(x));   x' = h + r (MoE(y) + Shared(y)), y = Norm(h)
    logits = Norm(x_L) E^T / l                       E the one table

- **the Mamba-2 mixer** (``mamba_mixer``), ``h`` heads of ``p``, one group
  of state width ``n``, inner width ``h p``::

      [z | xBC | dt] = u W_in            (h p | h p + 2 n | h), no bias
      xBC = silu(conv4(xBC) + b)         depthwise, causal
      dt = softplus(dt + dt_bias);  A = -exp(A_log)          float32
      S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
      out = (RMSNorm_(h p)(y silu(z)) w) W_out

  under the scopes ``ssm_proj`` (both projections), ``ssm_conv``,
  ``ssm_dt``, ``ssm_chunk`` and ``ssm_out`` (the gate, then the norm). The
  gate comes **before** the norm and the norm's mean square runs over the
  whole inner width, so ``ops/kda.py``'s ``norm_gate`` (a norm a head,
  gated after) is not it.
- **attention** (``attention_mixer``): ``heads`` query heads on ``kv``
  key heads of ``hd``, no rotary, causal, softmax at ``a`` (published:
  1 / 128, not ``hd^-1/2``), under ``attn_proj`` around the flash kernels.
- **held heads** (``mamba_heads_held``, ``heads_held``): one chip's share
  of a deployment that divides a layer's heads holds the held heads'
  columns of ``W_in``'s ``z``, ``x`` and ``dt`` parts and rows of
  ``W_out``, their channels of the convolution and of the gated norm
  (``B``, ``C`` and their convolution are what every holder computes
  alike), and for attention the held query heads with their key heads.
  **The gated norm's mean square is then over the held channels**: the
  rank's own sum of squares over its own count. The sum across the head
  holders, one float a token, which a tensor-parallel run of this norm
  exchanges, is left out, and no code stands in for the absent chips.
  ``A_log`` of head ``j`` is that of published head ``first + j``.
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

from dlrover_tpu.models import moe, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    attention,
    embed_lookup,
    flash_attention,
    kda,
    rms_norm,
    ssd,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]

KINDS = {"mamba": "M", "attention": "A"}

_PUBLISHED_LAYERS = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """ibm-granite/granite-4.0-h-small's config.json by default."""
    vocab_size: int = 100352
    dim: int = 4096
    #: the mixer of each layer held, first to last
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    # Mamba-2 (one group)
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_state: int = 128
    conv_size: int = 4
    mamba_chunk: int = 256
    # attention
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    # experts
    expert_ffn_dim: int = 768
    shared_ffn_dim: int = 1536
    n_experts: int = 72                  # the router's width
    experts_per_token: int = 10
    # Granite's multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 1.0 / 128
    logits_scaling: float = 16.0
    # one chip's share of a deployment that divides each layer: see
    # MoeConfig for the experts and the module docstring for the heads
    experts_held: Optional[int] = None
    first_expert: int = 0
    mamba_heads_held: Optional[int] = None
    first_mamba_head: int = 0
    heads_held: Optional[int] = None     # query heads; key heads with them
    first_head: int = 0
    norm_eps: float = 1e-5
    # sigma of the normal draws, and of the projections that close a
    # residual branch (w_out, w_o, w_down, ws_down) where it is another
    init_std: float = 0.02
    out_proj_std: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"layer_types: {sorted(unknown)} is none of "
                             f"{sorted(KINDS)}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads do not group over "
                             f"{self.n_kv_heads} key heads")
        if self.held_heads % self.group:
            raise ValueError(
                f"heads_held={self.held_heads}: whole groups of "
                f"{self.group} query heads go with their key head")
        for what, held, first, of in (
                ("mamba heads", self.held_mamba_heads, self.first_mamba_head,
                 self.mamba_heads),
                ("heads", self.held_heads, self.first_head, self.n_heads)):
            if not 0 < held <= of or of % held or first % held or first >= of:
                raise ValueError(
                    f"{what}: {held} held from {first} is no share of {of}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"M"`` (Mamba-2) or ``"A"`` (attention) a layer."""
        return tuple(KINDS[t] for t in self.layer_types)

    @property
    def pattern_string(self) -> str:
        return "".join(self.kinds)

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """One stacked part: the shortest period that divides the depth."""
        return stack.periodic(self.kinds, whole=True)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def held_mamba_heads(self) -> int:
        return (self.mamba_heads if self.mamba_heads_held is None
                else self.mamba_heads_held)

    @property
    def held_heads(self) -> int:
        return self.n_heads if self.heads_held is None else self.heads_held

    @property
    def held_kv_heads(self) -> int:
        return self.held_heads // self.group

    @property
    def inner(self) -> int:
        """The held heads' channels: the gated norm's count."""
        return self.held_mamba_heads * self.mamba_head_dim

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it): a softmax
        over all 72 renormalised over the chosen ten is the softmax over
        the ten largest logits."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, stated_head_dim=self.head_dim,
            ffn_dim=self.expert_ffn_dim, n_experts=self.n_experts,
            experts_per_token=self.experts_per_token, norm_topk_prob=True,
            scoring="softmax", experts_held=self.experts_held,
            first_expert=self.first_expert, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype,
            remat=self.remat,
        )

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        base = dict(
            vocab_size=256, dim=64,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            mamba_heads=4, mamba_head_dim=16, mamba_state=16, mamba_chunk=16,
            n_heads=4, n_kv_heads=2, head_dim=16, attention_multiplier=1 / 16,
            expert_ffn_dim=32, shared_ffn_dim=48, n_experts=8,
            experts_per_token=2, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return GraniteHybridConfig(**base)


def pos_name(i: int) -> str:
    """The key of the period's position ``i`` in ``params["layers"]``."""
    return f"pos{i}"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: GraniteHybridConfig, kind: str) -> Dict[str, Tuple]:
    """``{name: (shape, init, spec)}`` of one block. ``init`` is "normal",
    "out" (a projection that closes a residual branch), "ones", "zeros",
    "conv" or "a_log"; ``spec`` the partition of the leaf's own axes: a
    matrix shards its model-width side over fsdp, the stack of experts
    over ep, the rest is replicated."""
    D = cfg.dim
    rows, cols, rep = (FSDP, None), (None, FSDP), (None, None)
    shapes = {"attn_norm": ((D,), "ones", (None,)),
              "mlp_norm": ((D,), "ones", (None,))}
    if kind == "M":
        h, di, n = cfg.held_mamba_heads, cfg.inner, cfg.mamba_state
        shapes.update({
            # columns [z (h p) | x (h p) | B (n) | C (n) | dt (h)]
            "w_in": ((D, 2 * di + 2 * n + h), "normal", rows),
            "conv_w": ((di + 2 * n, cfg.conv_size), "conv", rep),
            "conv_b": ((di + 2 * n,), "zeros", (None,)),
            "a_log": ((h,), "a_log", (None,)),
            "dt_bias": ((h,), "ones", (None,)),
            "d_skip": ((h,), "ones", (None,)),
            "m_norm": ((di,), "ones", (None,)),
            "w_out": ((di, D), "out", cols),
        })
    else:
        h, kvh, hd = cfg.held_heads, cfg.held_kv_heads, cfg.head_dim
        shapes.update({
            "w_q": ((D, h * hd), "normal", rows),
            "w_k": ((D, kvh * hd), "normal", rows),
            "w_v": ((D, kvh * hd), "normal", rows),
            "w_o": ((h * hd, D), "out", cols),
        })
    E, F, Fs = cfg.as_moe().n_held, cfg.expert_ffn_dim, cfg.shared_ffn_dim
    shapes.update({
        "router": ((D, cfg.n_experts), "normal", rows),
        "w_gate": ((E, D, F), "normal", (EP,) + rows),
        "w_up": ((E, D, F), "normal", (EP,) + rows),
        "w_down": ((E, F, D), "out", (EP,) + cols),
        "ws_gate": ((D, Fs), "normal", rows),
        "ws_up": ((D, Fs), "normal", rows),
        "ws_down": ((Fs, D), "out", cols),
    })
    return shapes


def _init_leaf(cfg: GraniteHybridConfig, key, shape, rule: str):
    if rule in ("normal", "out"):
        std = (cfg.init_std if rule == "normal" or cfg.out_proj_std is None
               else cfg.out_proj_std)
        return jax.random.normal(key, shape, jnp.float32) * std
    if rule == "ones":
        return jnp.ones(shape, jnp.float32)
    if rule == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if rule == "conv":
        # a depthwise Conv1d's default: uniform within fan_in^-1/2
        bound = cfg.conv_size ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    # a_log, as the model's public implementation: A = 1 .. heads by the
    # published head, stored as its log
    heads = jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)
    return jnp.broadcast_to(jnp.log(cfg.first_mamba_head + heads), shape)


def _init_slab(cfg: GraniteHybridConfig, key, kind: str, rows: int) -> Params:
    shapes = _block_shapes(cfg, kind)
    return {
        name: _init_leaf(cfg, k, (rows,) + shape, rule).astype(
            cfg.param_dtype)
        for k, (name, (shape, rule, _)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))
    }


def init_params(cfg: GraniteHybridConfig, rng: jax.Array) -> Params:
    """No ``lm_head``: the table is the head (``tie_word_embeddings``)."""
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_layers = jax.random.split(rng)
    part, = cfg.layout
    return {
        "embed": (jax.random.normal(k_embed, (V, D), jnp.float32)
                  * cfg.init_std).astype(pd),
        "layers": {
            pos_name(i): _init_slab(cfg, k, kind, part.repeats)
            for i, (k, kind) in enumerate(zip(
                jax.random.split(k_layers, len(part.kinds)), part.kinds))
        },
        "final_norm": jnp.ones((D,), pd),
    }


def param_specs(cfg: GraniteHybridConfig) -> Params:
    """Data and expert parallelism only (``validate_for_mesh``). The
    leading axis of a position's leaves is the period."""
    return {
        "embed": P(None, FSDP),
        "layers": {
            pos_name(i): {
                name: P(None, *spec) for name, (_, _, spec)
                in _block_shapes(cfg, kind).items()
            }
            for i, kind in enumerate(cfg.layout[0].kinds)
        },
        "final_norm": P(None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(params: Params):
    """``params``' layers as the layout's one part takes them."""
    positions = params["layers"]
    return [tuple(positions[pos_name(i)] for i in range(len(positions)))]


def layer_params(cfg: GraniteHybridConfig, params: Params, layer: int
                 ) -> Params:
    """Layer ``layer``'s own leaves."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def validate_for_mesh(cfg: GraniteHybridConfig, mesh: Mesh, batch: int = 0
                      ) -> None:
    shape = dict(mesh.shape)
    why = {
        SP: "a Mamba layer's state and its convolution's last taps are not "
            "handed across the ranks of a sequence",
        TP: "the held heads are a share the configuration states, not a "
            "mesh axis: the gated norm's sum of squares is not exchanged",
        PP: "the period is walked under one scan on one device",
    }
    for axis in (SP, TP, PP):
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"granite_hybrid: mesh {axis}={shape[axis]}: {why[axis]} "
                "(dp, fsdp and ep only)")
    shards = math.prod(shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        raise ValueError(
            f"batch={batch} does not divide over the mesh's {shards} data "
            "shards (dp x fsdp x ep)")
    held, ep = cfg.as_moe().n_held, shape.get(EP, 1)
    if held % ep:
        raise ValueError(
            f"the {held} experts held are not divisible by mesh ep={ep}")


# ---------------------------------------------------------------------------
# The mixers, the block, the forward
# ---------------------------------------------------------------------------

def conv_bias_silu(x, weight, bias):
    """``silu(conv(x) + b)``: ``ops/kda.py``'s depthwise causal
    convolution (``x (b, s, c)``, ``weight (c, taps)``, the last tap the
    token's own) with a bias, float32 until the result. XLA's ops: the
    taps, the bias and the SiLU fuse into one pass over the projection."""
    f32 = jnp.float32
    y = kda.causal_conv(x.astype(f32), weight) + bias.astype(f32)
    return jax.nn.silu(y).astype(x.dtype)


def mamba_operands(cfg: GraniteHybridConfig, lp: Params, y):
    """``y (b, s, d)``, pre-normed -> what the scan takes (``x (b, s, h,
    p)``, ``dt (b, s, h)`` float32, ``A (h,)`` float32, ``B, C (b, s,
    n)``, ``D (h,)``) and the gate's logits ``z (b, s, h p)``."""
    dt_, f32 = cfg.dtype, jnp.float32
    b, s, _ = y.shape
    h, di, n = cfg.held_mamba_heads, cfg.inner, cfg.mamba_state
    with trace.scope("ssm_proj"):
        zxbcdt = y @ lp["w_in"].astype(dt_)
    with trace.scope("ssm_conv"):
        xbc = conv_bias_silu(zxbcdt[..., di:2 * di + 2 * n], lp["conv_w"],
                             lp["conv_b"])
    with trace.scope("ssm_dt"):
        step = jax.nn.softplus(zxbcdt[..., 2 * di + 2 * n:].astype(f32)
                               + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["a_log"].astype(f32))
    return (xbc[..., :di].reshape(b, s, h, cfg.mamba_head_dim), step, A,
            xbc[..., di:di + n], xbc[..., di + n:],
            lp["d_skip"].astype(f32)), zxbcdt[..., :di]


def gated(y, z):
    """``y silu(z)`` and its mean square over the last axis (the gated
    norm's statistic, one float a token), float32."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    return g, jnp.mean(g * g, axis=-1, keepdims=True)


def gated_norm(y, z, weight, eps: float):
    """``RMSNorm(y silu(z)) w`` over the whole last axis, float32 inside:
    the mean square is over the channels given, which under held heads
    are the held ones. XLA's ops: one pass forward, one backward."""
    g, mean_square = gated(y, z)
    return (g * lax.rsqrt(mean_square + eps) * weight.astype(jnp.float32)
            ).astype(y.dtype)


def mamba_mixer(cfg: GraniteHybridConfig, lp: Params, y, mesh=None,
                interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> the Mamba-2 sublayer's output before
    the residual."""
    b, s, _ = y.shape
    operands, z = mamba_operands(cfg, lp, y)
    with trace.scope("ssm_chunk"):
        o = ssd.ssd(*operands, chunk=cfg.mamba_chunk, interpret=interpret,
                    mesh=mesh)
    with trace.scope("ssm_out"):
        o = gated_norm(o.reshape(b, s, cfg.inner), z, lp["m_norm"],
                       cfg.norm_eps)
    with trace.scope("ssm_proj"):
        return o @ lp["w_out"].astype(cfg.dtype)


def attention_operands(cfg: GraniteHybridConfig, lp: Params, y):
    dt = cfg.dtype
    b, s, _ = y.shape
    h, kvh, hd = cfg.held_heads, cfg.held_kv_heads, cfg.head_dim
    return ((y @ lp["w_q"].astype(dt)).reshape(b, s, h, hd),
            (y @ lp["w_k"].astype(dt)).reshape(b, s, kvh, hd),
            (y @ lp["w_v"].astype(dt)).reshape(b, s, kvh, hd))


def attention_mixer(cfg: GraniteHybridConfig, lp: Params, y, mesh=None,
                    interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> the attention sublayer's output
    before the residual: no rotary, the model's own softmax scale."""
    b, s, _ = y.shape
    with trace.scope("attn_proj"):
        q, k, v = attention_operands(cfg, lp, y)
    out = flash_attention(q, k, v, causal=True, mesh=mesh,
                          scale=cfg.attention_multiplier, interpret=interpret)
    with trace.scope("attn_proj"):
        return out.reshape(b, s, -1) @ lp["w_o"].astype(cfg.dtype)


def mixed(cfg: GraniteHybridConfig, mesh, kind: str, lp: Params, x):
    """``x + r Mixer(Norm(x))`` for the layer's kind."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    mixer = mamba_mixer if kind == "M" else attention_mixer
    return x + (cfg.residual_multiplier * mixer(cfg, lp, y, mesh=mesh)
                ).astype(x.dtype)


def block(cfg: GraniteHybridConfig, mesh, kind: str, lp: Params, x):
    """One layer -> the residual after it."""
    x = mixed(cfg, mesh, kind, lp, x)
    with trace.scope("norm"):
        u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    out, _ = moe.moe_mlp(cfg.as_moe(), lp, u, mesh)
    x = x + (cfg.residual_multiplier * out).astype(x.dtype)
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def _report_shapes(cfg: GraniteHybridConfig):
    """The gauges that say what this build's layers are (set while the
    step is traced); the pattern is a text."""
    trace.gauge("ssm.heads_held", cfg.held_mamba_heads)
    trace.gauge("ssm.heads", cfg.mamba_heads)
    trace.gauge("ssm.head_dim", cfg.mamba_head_dim)
    trace.gauge("ssm.state", cfg.mamba_state)
    trace.gauge("ssm.norm_channels", cfg.inner)
    trace.gauge("ssm.state_kept", 0)   # 1 once a block keeps them
    trace.gauge("attn.scale", cfg.attention_multiplier)
    trace.gauge("attn.heads_held", cfg.held_heads)
    trace.gauge("attn.group", cfg.group)
    trace.gauge("attn.out_kept", 0)    # 1 once a block keeps one
    trace.gauge("layers.ssm", cfg.kinds.count("M"))
    trace.gauge("layers.attention", cfg.kinds.count("A"))
    trace.gauge("layers.tied_head", 1)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: GraniteHybridConfig, mesh, kind: str):
    """A block is recomputed whole in the backward pass, but for what it
    names: the attention block keeps the flash forward's output and
    ``lse`` (``attention.KEPT``, 32.5 MiB at 8 heads and 16384 tokens: the
    kernel runs once a step); a Mamba block keeps the scan's output and
    its chunks' starting states (``ssd.KEPT``, 128 MiB a layer at 32 heads
    and 16384 tokens: ``ssd_fwd`` runs once a step)."""
    keep, kept = ((attention.KEPT, attention.report_kept) if kind == "A"
                  else (ssd.KEPT, ssd.report_kept))
    return stack.recompute(functools.partial(block, cfg, mesh, kind),
                           cfg.remat, keep, kept)


def _embed(cfg: GraniteHybridConfig, params: Params, tokens, mesh):
    """``e E[token]`` in the activation dtype."""
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return (x.astype(jnp.float32) * cfg.embedding_multiplier
            ).astype(cfg.dtype)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: GraniteHybridConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm: ``(b, s,
    dim)``. One scan over the layout's periods."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    x = _embed(cfg, params, tokens, mesh)
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in set(cfg.kinds)}
    return stack.walk(x, cfg.layout, _trees(params),
                      lambda kind, lp, x: (fns[kind](lp, x), None))[0]


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: GraniteHybridConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per layer, first to last, the (token, choice) pairs of ``tokens``
    (b, s) whose chosen expert is a held one: the rows the grouped
    products really work on. A forward of its own beside the step, which
    has no output but the loss. (n_layers,) int32."""
    mcfg, first = cfg.as_moe(), cfg.first_expert
    x = _embed(cfg, params, tokens, mesh)

    def each(kind, lp, x):
        x = mixed(cfg, mesh, kind, lp, x)
        u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(mcfg, lp["router"], u.reshape(-1, cfg.dim))
        held = jnp.sum((top_e >= first) & (top_e < first + mcfg.n_held),
                       dtype=jnp.int32)
        out = moe.moe_mlp(mcfg, lp, u, mesh)[0]
        return x + (cfg.residual_multiplier * out).astype(x.dtype), held

    return stack.walk(x, cfg.layout, _trees(params), each)[1]


def head_input(cfg: GraniteHybridConfig, params: Params, x):
    """``Norm(x_L) / l``: what the table, as the head, reads."""
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x.astype(jnp.float32) / cfg.logits_scaling).astype(cfg.dtype)


def head_loss(
    params: Params, x: jnp.ndarray, tokens: jnp.ndarray,
    cfg: GraniteHybridConfig, mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored) of ``x``,
    the residual after the last block, through the one table."""
    return stack.next_token_loss(
        head_input(cfg, params, x), params["embed"].T, tokens,
        cfg.ce_chunk_size, mesh)


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: GraniteHybridConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The loss of ``tokens``: the table is read by the lookup and by the
    head, and its two gradients are summed in the one leaf."""
    return head_loss(params, forward_layers(params, tokens, cfg, mesh),
                     tokens, cfg, mesh)
