"""The ``qwen3_next`` decoder family (Qwen3-Next-80B-A3B): layers of two
mixers in one period, **Gated DeltaNet** (a gated delta rule with one
decay a head over grouped value heads, ``ops/kda.py``'s per-head form)
three times and **gated softmax attention** once, every layer over
softmax-routed experts beside a sigmoid-gated shared expert.

Every piece another family has is that family's: the expert path is
``models/moe.py``'s (this file hands it a ``MoeConfig`` view: softmax
over the router's whole width, the chosen renormalised, the aux loss,
the held share), the convolution, the norms of q and k and the output's
norm and gate are ``ops/kda.py``'s passes (here with a v that is not
normed and a SiLU gate), attention is ``ops/attention.py``'s flash
kernels, rotary, the embedding and the fused cross-entropy are the
shared ops, and ``models/stack.py`` lays the period out and walks it.

What is this family's own (hidden ``d``, ``x`` the normed input; every
block is ``h += Mixer(Norm(h)); h += MoE(Norm(h))``):

- **the norm**: ``x rsqrt(mean(x^2) + eps) (1 + w)``, ``w`` stored (and
  decayed) as published, zeros at init; the final norm and the norms a
  head of q and k the same. The output norm of a Gated DeltaNet layer is
  a plain weight, ones at init.
- **the Gated DeltaNet layer** (``gdn_attention``), ``hk`` key heads,
  ``hv`` value heads, value head ``j`` on key head ``j // (hv / hk)``::

      [q~ | k~ | v~ | z] = x W_qkvz                  head-major in each
      [b | a] = x W_ba
      [q^ | k^ | v^] = SiLU(Conv4([q~ | k~ | v~]))   depthwise, causal
      q = L2norm(q^) dk^-1/2;  k = L2norm(k^);  v = v^     a head each
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   float32
      o = the gated delta rule over (q, k, v, g, beta)    kda.chunk_gdn
      y = W_o concat_j[RMSNorm_dv(o^j) w_n SiLU(z^j)]

  under the scopes ``gdn_proj``, ``gdn_conv`` (the third and fourth
  lines), ``gdn_gate``, ``gdn_chunk``, ``gdn_out``. The convolution and
  the norms run over the ``hk`` key heads; no array of q or k is ever
  repeated over the value heads.
- **the gated attention layer** (``gated_attention``), ``h`` query heads
  on ``kvh`` key heads of ``hd``::

      [q_h | gate_h] = x W_q   a head;   k, v = x W_k, x W_v
      q, k = Norm_hd(q), Norm_hd(k)        a head, one weight for all
      rotary on the first ``rotary_dim`` channels of q and k
      y = W_o concat_h[softmax(q k^T / sqrt(hd)) v * sigmoid(gate_h)]

  under ``gattn_proj`` and ``gattn_gate`` around the flash kernels.
- **the loss**: next-token cross-entropy + ``router_aux_coef`` x the
  layers' mean load-balancing loss (``models/moe.py``'s).

The multi-token module the model card mentions has no key in the
config: it is not here.
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
    apply_rope,
    attention,
    embed_lookup,
    flash_attention,
    kda,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Qwen/Qwen3-Next-80B-A3B-Instruct's config.json by default."""
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4     # layer i is full where (i + 1) % 4 == 0
    # Gated DeltaNet
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    conv_size: int = 4
    gdn_chunk: int = 64
    # gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # experts
    expert_ffn_dim: int = 512
    shared_ffn_dim: int = 512
    n_experts: int = 512                 # the router's width
    experts_per_token: int = 10
    norm_topk_prob: bool = True
    router_aux_coef: float = 0.001
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    norm_eps: float = 1e-6
    # sigma of the normal draws, and of the projections that close a
    # residual branch (w_o, w_down, ws_down) where it is another
    init_std: float = 0.02
    out_proj_std: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"{self.gdn_value_heads} value heads do not group over "
                f"{self.gdn_key_heads} key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} is no even number of channels")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"G"`` (Gated DeltaNet) or ``"F"`` (gated attention) a layer."""
        return tuple(
            "F" if (i + 1) % self.full_attention_interval == 0 else "G"
            for i in range(self.n_layers))

    @property
    def pattern_string(self) -> str:
        return "".join(self.kinds)

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """One stacked part: the shortest period that divides the depth."""
        return stack.periodic(self.kinds, whole=True)

    @property
    def period(self) -> int:
        return len(self.layout[0].kinds)

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, stated_head_dim=self.head_dim,
            ffn_dim=self.expert_ffn_dim, n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, scoring="softmax",
            experts_held=self.experts_held, first_expert=self.first_expert,
            router_aux_coef=self.router_aux_coef, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype,
            remat=self.remat,
        )

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=8, gdn_key_heads=2,
            gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16,
            gdn_chunk=16, n_heads=4, n_kv_heads=2, head_dim=32,
            expert_ffn_dim=32, shared_ffn_dim=32, n_experts=8,
            experts_per_token=2, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return Qwen3NextConfig(**base)


def pos_name(i: int) -> str:
    """The key of the period's position ``i`` in ``params["layers"]``."""
    return f"pos{i}"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: Qwen3NextConfig, kind: str) -> Dict[str, Tuple]:
    """``{name: (shape, init, spec)}`` of one block. ``init`` is "normal",
    "out" (a projection that closes a residual branch), "ones", "zeros"
    or a Gated DeltaNet rule; ``spec`` the partition of the leaf's own
    axes: a matrix shards its model-width side over fsdp, the stack of
    experts over ep, the rest is replicated."""
    D = cfg.dim
    rows, cols, rep = (FSDP, None), (None, FSDP), (None, None)
    shapes = {"attn_norm": ((D,), "zeros", (None,)),
              "mlp_norm": ((D,), "zeros", (None,))}
    if kind == "G":
        kw = cfg.gdn_key_heads * cfg.gdn_key_dim
        vw = cfg.gdn_value_heads * cfg.gdn_value_dim
        hv = cfg.gdn_value_heads
        shapes.update({
            # columns [q | k | v | z], head-major inside each
            "w_qkvz": ((D, 2 * kw + 2 * vw), "normal", rows),
            "w_ba": ((D, 2 * hv), "normal", rows),       # [b | a]
            "conv": ((2 * kw + vw, cfg.conv_size), "conv", rep),
            "a_log": ((hv,), "a_log", (None,)),
            "dt_bias": ((hv,), "ones", (None,)),
            "o_norm": ((cfg.gdn_value_dim,), "ones", (None,)),
            "w_o": ((vw, D), "out", cols),
        })
    else:
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        shapes.update({
            # a head's columns are [q_h | gate_h]
            "w_q": ((D, h * 2 * hd), "normal", rows),
            "w_k": ((D, kvh * hd), "normal", rows),
            "w_v": ((D, kvh * hd), "normal", rows),
            "q_norm": ((hd,), "zeros", (None,)),
            "k_norm": ((hd,), "zeros", (None,)),
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
        "w_s": ((D, 1), "normal", rows),
    })
    return shapes


def _init_leaf(cfg: Qwen3NextConfig, key, shape, rule: str):
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
    # a_log: the public implementation's A ~ U(0, 16), stored as its log
    # (the draw's floor keeps the log finite)
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0))


def _init_slab(cfg: Qwen3NextConfig, key, kind: str, rows: int) -> Params:
    shapes = _block_shapes(cfg, kind)
    return {
        name: _init_leaf(cfg, k, (rows,) + shape, rule).astype(
            cfg.param_dtype)
        for k, (name, (shape, rule, _)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))
    }


def init_params(cfg: Qwen3NextConfig, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_head, k_layers = jax.random.split(rng, 3)
    part, = cfg.layout

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * cfg.init_std).astype(pd)

    return {
        "embed": normal(k_embed, (V, D)),
        "layers": {
            pos_name(i): _init_slab(cfg, k, kind, part.repeats)
            for i, (k, kind) in enumerate(zip(
                jax.random.split(k_layers, len(part.kinds)), part.kinds))
        },
        "final_norm": jnp.zeros((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def param_specs(cfg: Qwen3NextConfig) -> Params:
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
        "lm_head": P(FSDP, None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(params: Params):
    """``params``' layers as the layout's one part takes them."""
    positions = params["layers"]
    return [tuple(positions[pos_name(i)] for i in range(len(positions)))]


def layer_params(cfg: Qwen3NextConfig, params: Params, layer: int) -> Params:
    """Layer ``layer``'s own leaves."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def validate_for_mesh(cfg: Qwen3NextConfig, mesh: Mesh, batch: int = 0
                      ) -> None:
    shape = dict(mesh.shape)
    for axis in (TP, SP, PP):
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"qwen3_next: mesh {axis}={shape[axis]}: a Gated DeltaNet "
                "layer's recurrent state is not handed across ranks and "
                "its heads are not split (dp, fsdp and ep only)"
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
# The mixers, the block, the forward
# ---------------------------------------------------------------------------

def norm(x, w, eps: float):
    """``x rsqrt(mean(x^2) + eps) (1 + w)``: the weight is stored as its
    offset from one."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def gdn_inputs(cfg: Qwen3NextConfig, lp: Params, y, mesh=None,
               interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> what the delta rule takes (``q, k
    (b, s, hk, dk)``, ``v (b, s, hv, dv)`` in the activation dtype,
    log-decay ``g`` and step ``beta (b, s, hv)`` float32) and the output
    gate's logits ``z (b, s, hv, dv)``. ``interpret`` (tests): the
    passes' Pallas forms on the CPU."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = y.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    kw, vw = hk * dk, hv * dv
    with trace.scope("gdn_proj"):
        qkvz = y @ lp["w_qkvz"].astype(dt)
        ba = y @ lp["w_ba"].astype(dt)
    with trace.scope("gdn_conv"):
        taps = lp["conv"]
        q, k = kda.conv_silu_norm(
            [qkvz[..., :kw], qkvz[..., kw:2 * kw]],
            [taps[:kw], taps[kw:2 * kw]], heads=hk,
            scales=(dk ** -0.5, 1.0), scope="gdn_conv", interpret=interpret,
            mesh=mesh)
        v, = kda.conv_silu_norm(
            [qkvz[..., 2 * kw:2 * kw + vw]], [taps[2 * kw:]], heads=hv,
            scales=(None,), scope="gdn_conv", interpret=interpret, mesh=mesh)
    with trace.scope("gdn_gate"):
        beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
        g = -jnp.exp(lp["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., hv:].astype(f32) + lp["dt_bias"].astype(f32))
    return q, k, v, g, beta, qkvz[..., 2 * kw + vw:].reshape(b, s, hv, dv)


def gdn_attention(cfg: Qwen3NextConfig, lp: Params, y, mesh=None,
                  interpret: bool = False):
    q, k, v, g, beta, z = gdn_inputs(cfg, lp, y, mesh, interpret)
    with trace.scope("gdn_chunk"):
        o = kda.chunk_gdn(q, k, v, g, beta, chunk=cfg.gdn_chunk,
                          interpret=interpret, mesh=mesh)
    with trace.scope("gdn_out"):
        o = kda.norm_gate(o, z, lp["o_norm"], cfg.norm_eps, act="silu",
                          scope="gdn_out", interpret=interpret, mesh=mesh)
        return o @ lp["w_o"].astype(cfg.dtype)


def gated_attention(cfg: Qwen3NextConfig, mesh, lp: Params, y):
    """``y (b, s, d)``, pre-normed -> the gated attention sublayer's
    output before the residual."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with trace.scope("gattn_proj"):
        qg = (y @ lp["w_q"].astype(dt)).reshape(b, s, h, 2 * hd)
        q = norm(qg[..., :hd], lp["q_norm"], cfg.norm_eps)
        k = norm((y @ lp["w_k"].astype(dt)).reshape(b, s, kvh, hd),
                 lp["k_norm"], cfg.norm_eps)
        v = (y @ lp["w_v"].astype(dt)).reshape(b, s, kvh, hd)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        inv_freq = rope_frequencies(cfg.rotary_dim, cfg.rope_theta)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
    out = flash_attention(q, k, v, causal=True, mesh=mesh)
    with trace.scope("gattn_gate"):
        out = (out.astype(f32) * jax.nn.sigmoid(qg[..., hd:].astype(f32))
               ).astype(dt)
    with trace.scope("gattn_proj"):
        return out.reshape(b, s, h * hd) @ lp["w_o"].astype(dt)


def mixed(cfg: Qwen3NextConfig, mesh, kind: str, lp: Params, x):
    """``x + Mixer(Norm(x))`` for the layer's kind."""
    with trace.scope("norm"):
        y = norm(x, lp["attn_norm"], cfg.norm_eps)
    if kind == "G":
        return x + gdn_attention(cfg, lp, y, mesh=mesh)
    return x + gated_attention(cfg, mesh, lp, y)


def block(cfg: Qwen3NextConfig, mesh, kind: str, lp: Params, x):
    """One layer -> ``(the residual after it, its load-balancing loss)``."""
    x = mixed(cfg, mesh, kind, lp, x)
    with trace.scope("norm"):
        u = norm(x, lp["mlp_norm"], cfg.norm_eps)
    out, aux = moe.moe_mlp(cfg.as_moe(), lp, u, mesh)
    x = x + out
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x, aux


def _report_shapes(cfg: Qwen3NextConfig):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    gdn = cfg.kinds.count("G")
    trace.gauge("attn.gdn_layers", gdn)
    trace.gauge("attn.full_layers", cfg.n_layers - gdn)
    trace.gauge("attn.gdn_key_heads", cfg.gdn_key_heads)
    trace.gauge("attn.gdn_value_heads", cfg.gdn_value_heads)
    trace.gauge("attn.gdn_chunk", cfg.gdn_chunk)
    trace.gauge("attn.group", cfg.n_heads // cfg.n_kv_heads)
    trace.gauge("attn.rotary_dim", cfg.rotary_dim)
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one (`_block_fn`)
    trace.gauge("layers.period", cfg.period)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: Qwen3NextConfig, mesh, kind: str):
    """A block is recomputed whole in the backward pass, but for the
    flash forward's output and ``lse``, its backward's residuals (129
    MiB a gated attention layer at 16384 tokens): the kernel runs once a
    step. The delta rule's forward names its output and states too
    (``ops/kda.py``'s own ``KEPT``): 128 + 512 MiB a layer, 3.75 GiB for
    six layers where the step's plan has 1.1 free, so this block does
    not keep them and the rule stays recomputed."""
    return stack.recompute(
        functools.partial(block, cfg, mesh, kind), cfg.remat,
        attention.KEPT, attention.report_kept)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: Qwen3NextConfig,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(the residual after the last block, before the final norm: (b,
    s, dim); the layers' mean load-balancing loss)``. One scan over the
    layout's periods."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in set(cfg.kinds)}
    x, aux = stack.walk(x, cfg.layout, _trees(params),
                        lambda kind, lp, x: fns[kind](lp, x))
    return x, jnp.mean(aux)


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: Qwen3NextConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per layer, first to last, the (token, choice) pairs of ``tokens``
    (b, s) whose chosen expert is a held one: the rows the grouped
    products really work on. A forward of its own beside the step, which
    has no output but the loss (``smallthinker.live_rows``). (n_layers,)
    int32."""
    mcfg, first = cfg.as_moe(), cfg.first_expert
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)

    def each(kind, lp, x):
        x = mixed(cfg, mesh, kind, lp, x)
        u = norm(x, lp["mlp_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(mcfg, lp["router"], u.reshape(-1, cfg.dim))
        held = jnp.sum((top_e >= first) & (top_e < first + mcfg.n_held),
                       dtype=jnp.int32)
        return x + moe.moe_mlp(mcfg, lp, u, mesh)[0], held

    return stack.walk(x, cfg.layout, _trees(params), each)[1]


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: Qwen3NextConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored) + the
    routers' load-balancing loss at ``router_aux_coef``."""
    x, aux = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = norm(x, params["final_norm"], cfg.norm_eps)
    ce = stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
    return ce + cfg.router_aux_coef * aux
