"""The ``laguna`` decoder family (poolside Laguna-XS.2): sliding-window
layers and yarn-rotated full layers in one period **at two query-head
counts** on the same key heads, a sigmoid gate a head on attention's
output, a leading dense layer, and ``models/moe.py``'s expert layer
(sigmoid routing renormalised over the chosen and scaled, an ungated
shared expert) everywhere else.

Every piece another family has is that family's: attention is
``ops/attention.py``'s flash kernels (with ``window=`` on a window
layer; the group is a layer's own), rotary ``ops/rotary.py``'s (yarn's
frequencies on a partial head, plain ones on a whole head), the expert
layer ``moe.moe_mlp`` unchanged, the dense feed-forward
``llama.swiglu``, the embedding and the fused cross-entropy the shared
ops, the layout and the walk ``models/stack.py``'s.

What is this family's own:

- **the head count is a property of the layer**
  (``num_attention_heads_per_layer``: 48 on full layers, 64 on window
  layers, both on 8 key heads of 128: groups 6 and 8). A layer's *kind*
  is ``(F or S, its heads)``, static to the kernels; ``wq``, ``w_g`` and
  ``wo`` of the two kinds differ in *shape*, so the layers are stacked
  **a position of the period** as ``models/dots3.py`` stacks its kinds,
  each position's slab with its own shapes. The leading dense layers
  come before the scan, each with its own tree; the expert layers are
  one scan over their shortest period (``stack.periodic``: the
  published 40 layers are the dense layer and nine periods ``S S S F``),
  and what the depth leaves past whole periods (``S S S``) follows as
  runs of like layers, each a scan of its own (``stack.runs``): in line
  the three would be three more copies of the window block to compile
  and to plan memory for.
- **the block**, ``y = RMSNorm(x)``::

      q = y W_q (s, H_l, 128);  k, v = y W_k, y W_v (s, 8, 128)
      F: q, k turned on channels 0-63 of a head by yarn's frequencies,
         cos and sin times ``attention_factor``; channels 64-127 as they
         are.  S: q, k turned on all 128 channels at another theta
      o_h = softmax(q_h k_{h // group}^T / sqrt(128) + mask) v_{h // group}
            mask: j <= i, and on S ``0 <= i - j < window``
      x = x + concat_h(sigmoid(y W_g)_h o_h) W_o
      u = RMSNorm(x);  x = x + SwiGLU(u) (dense) or the expert layer's
          routed part and the shared expert (sparse)

- no norm on q or k, no choice bias, no auxiliary loss: the config has
  no key for any of them.
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
    attention as attn_ops,
    embed_lookup,
    rms_norm,
    rope_frequencies,
    yarn_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]

FULL, WINDOW = "F", "S"
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
#: a layer's kind: its attention and its query heads
Kind = Tuple[str, int]


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """poolside/Laguna-XS.2's config.json by default."""
    vocab_size: int = 100352
    dim: int = 2048
    layer_kinds: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW) * 10
    heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    n_dense_layers: int = 1          # mlp_layer_types' leading "dense"
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512                # sliding_window
    # full layers: yarn on the first rotary_factor of a head
    rope_theta: float = 5e5
    rotary_factor: float = 0.5
    yarn_factor: float = 64.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.4158883083359672
    # window layers: plain rotary
    swa_rope_theta: float = 1e4
    swa_rotary_factor: float = 1.0
    dense_ffn_dim: int = 8192
    expert_ffn_dim: int = 512
    shared_ffn_dim: int = 512
    n_experts: int = 256
    experts_per_token: int = 8
    norm_topk_prob: bool = True      # the chosen eight renormalised
    routed_scaling: float = 2.5
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    init_std: float = 0.02
    # the sigma of the projections that close a residual branch (wo,
    # w_down, ws_down) where a configuration states one apart
    out_proj_std: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if set(self.layer_kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: each {FULL!r} (full "
                f"attention) or {WINDOW!r} (sliding window)")
        if len(self.heads_per_layer) != len(self.layer_kinds):
            raise ValueError(
                f"{len(self.heads_per_layer)} head counts for "
                f"{len(self.layer_kinds)} layers")
        for h in set(self.heads_per_layer):
            if h < 1 or h % self.n_kv_heads:
                raise ValueError(
                    f"{h} query heads do not group over the "
                    f"{self.n_kv_heads} key heads")
        if not 0 <= self.n_dense_layers <= len(self.layer_kinds):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of "
                f"{len(self.layer_kinds)} layers")
        for factor in (self.rotary_factor, self.swa_rotary_factor):
            turned = factor * self.head_dim
            if not 0 < factor <= 1 or turned != int(turned) or turned % 2:
                raise ValueError(
                    f"a rotary factor of {factor} turns no even number of "
                    f"a head's {self.head_dim} channels")

    @staticmethod
    def from_hf(config: dict, **overrides) -> "LagunaConfig":
        """From a ``config.json`` of ``model_type: laguna``
        (``overrides``: this program's own fields, the held share among
        them)."""
        rope = config["rope_parameters"]
        full, swa = rope["full_attention"], rope["sliding_attention"]
        for key, want in (("attention_bias", False),
                          ("tie_word_embeddings", False), ("gating", True),
                          ("moe_apply_router_weight_on_input", False),
                          ("hidden_act", "silu")):
            if config.get(key, want) != want:
                raise ValueError(
                    f"laguna: {key}={config[key]!r} is not what "
                    f"models/laguna.py computes ({want!r})")
        if full["rope_type"] != "yarn" or swa["rope_type"] != "default":
            raise ValueError(
                "laguna: models/laguna.py turns full layers by yarn and "
                f"window layers plainly, not by {full['rope_type']!r} and "
                f"{swa['rope_type']!r}")
        n = config["num_hidden_layers"]
        mlp = list(config["mlp_layer_types"])
        dense = mlp.count("dense")
        lists = (config["layer_types"], mlp,
                 config["num_attention_heads_per_layer"])
        if any(len(a) != n for a in lists) or mlp != (
                ["dense"] * dense + ["sparse"] * (n - dense)):
            raise ValueError(
                f"laguna: the three per-layer lists give {n} layers each, "
                f"the dense ones leading; mlp_layer_types {mlp}")
        fields = dict(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_kinds=tuple(_KINDS[t] for t in config["layer_types"]),
            heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
            n_dense_layers=dense,
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"], window=config["sliding_window"],
            rope_theta=float(full["rope_theta"]),
            rotary_factor=float(full["partial_rotary_factor"]),
            yarn_factor=float(full["factor"]),
            yarn_original_max=full["original_max_position_embeddings"],
            yarn_beta_fast=float(full["beta_fast"]),
            yarn_beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]),
            swa_rope_theta=float(swa["rope_theta"]),
            swa_rotary_factor=float(swa["partial_rotary_factor"]),
            dense_ffn_dim=config["intermediate_size"],
            expert_ffn_dim=config["moe_intermediate_size"],
            shared_ffn_dim=config["shared_expert_intermediate_size"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            routed_scaling=float(config["moe_routed_scaling_factor"]),
            max_seq_len=config["max_position_embeddings"],
            norm_eps=float(config["rms_norm_eps"]),
        )
        fields.update(overrides)
        return LagunaConfig(**fields)

    # -- the layout ---------------------------------------------------------

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def kinds(self) -> Tuple[Kind, ...]:
        """``(F or S, query heads)`` of each layer, first to last."""
        return tuple(zip(self.layer_kinds, self.heads_per_layer))

    @property
    def moe_kinds(self) -> Tuple[Kind, ...]:
        return self.kinds[self.n_dense_layers:]

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """The dense layers, each a part; the expert layers' shortest
        period, stacked; what the depth leaves past whole periods, a
        stacked part a run of like layers."""
        parts = stack.periodic(self.kinds, head=self.n_dense_layers)
        return (parts[:len(parts) - len(self.tail_kinds)]
                + stack.runs(self.tail_kinds))

    @property
    def period(self) -> int:
        return stack.shortest_period(self.moe_kinds)

    @property
    def n_periods(self) -> int:
        return len(self.moe_kinds) // self.period

    @property
    def tail_kinds(self) -> Tuple[Kind, ...]:
        return self.moe_kinds[self.n_periods * self.period:]

    @property
    def pattern_string(self) -> str:
        """A letter a layer: F full attention, S sliding window; the
        dense layers' in lower case."""
        n = self.n_dense_layers
        return ("".join(self.layer_kinds[:n]).lower()
                + "".join(self.layer_kinds[n:]))

    def heads_of(self, kind: str) -> int:
        """The query heads of the first layer of ``kind`` (0: there is
        none): what the gauges say of a kind, whose layers the published
        model gives one count."""
        return next((h for k, h in self.kinds if k == kind), 0)

    def rotary(self, kind: str) -> Tuple[jnp.ndarray, float]:
        """``(inverse frequencies, what cos and sin are multiplied by)``
        of a layer of ``kind``; the frequencies' count is half the
        channels turned."""
        if kind == WINDOW:
            return rope_frequencies(
                int(self.swa_rotary_factor * self.head_dim),
                self.swa_rope_theta), 1.0
        return yarn_frequencies(
            int(self.rotary_factor * self.head_dim), self.rope_theta,
            self.yarn_factor, self.yarn_original_max, self.yarn_beta_fast,
            self.yarn_beta_slow), self.attention_factor

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=len(self.moe_kinds), n_heads=self.n_kv_heads,
            n_kv_heads=self.n_kv_heads, stated_head_dim=self.head_dim,
            ffn_dim=self.expert_ffn_dim, n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, scoring="sigmoid",
            routed_scaling=self.routed_scaling,
            experts_held=self.experts_held, first_expert=self.first_expert,
            router_aux_coef=0.0, norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=self.remat,
        )

    @staticmethod
    def tiny(**kw) -> "LagunaConfig":
        base = dict(
            vocab_size=256, dim=64,
            layer_kinds=(FULL, WINDOW, WINDOW, WINDOW) * 2,
            heads_per_layer=(4, 6, 6, 6) * 2, n_dense_layers=1,
            n_kv_heads=2, head_dim=16, window=16, rope_theta=1e4,
            yarn_factor=4.0, yarn_original_max=32, yarn_beta_fast=8.0,
            yarn_beta_slow=1.0, attention_factor=1.1386294361119891,
            swa_rope_theta=1e3, dense_ffn_dim=96, expert_ffn_dim=32,
            shared_ffn_dim=32, n_experts=8, experts_per_token=2,
            max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return LagunaConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: LagunaConfig, kind: Kind, dense: bool
                  ) -> Dict[str, Tuple]:
    """``{name: (shape, init)}`` of one block of ``kind``; ``init`` is
    "normal", "out" (a projection that closes a residual branch) or
    "ones"."""
    D, hd = cfg.dim, cfg.head_dim
    h, kvh = kind[1], cfg.n_kv_heads
    shapes = {
        "attn_norm": ((D,), "ones"),
        "wq": ((D, h * hd), "normal"),
        "wk": ((D, kvh * hd), "normal"),
        "wv": ((D, kvh * hd), "normal"),
        "w_g": ((D, h), "normal"),          # gating: a sigmoid a head
        "wo": ((h * hd, D), "out"),
        "mlp_norm": ((D,), "ones"),
    }
    if dense:
        F = cfg.dense_ffn_dim
        shapes.update({
            "w_gate": ((D, F), "normal"), "w_up": ((D, F), "normal"),
            "w_down": ((F, D), "out"),
        })
        return shapes
    E, F, Fs = cfg.as_moe().n_held, cfg.expert_ffn_dim, cfg.shared_ffn_dim
    shapes.update({
        "router": ((D, cfg.n_experts), "normal"),
        "w_gate": ((E, D, F), "normal"), "w_up": ((E, D, F), "normal"),
        "w_down": ((E, F, D), "out"),
        "ws_gate": ((D, Fs), "normal"), "ws_up": ((D, Fs), "normal"),
        "ws_down": ((Fs, D), "out"),
    })
    return shapes


def _init_block(cfg: LagunaConfig, key, kind: Kind, dense: bool,
                layers: Optional[int] = None) -> Params:
    """One block's tree, or ``layers`` of them stacked."""
    shapes = _block_shapes(cfg, kind, dense)
    lead = () if layers is None else (layers,)
    out = {}
    for k, (name, (shape, rule)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if rule == "ones":
            leaf = jnp.ones(lead + shape, jnp.float32)
        else:
            std = (cfg.init_std if rule == "normal"
                   or cfg.out_proj_std is None else cfg.out_proj_std)
            leaf = jax.random.normal(k, lead + shape, jnp.float32) * std
        out[name] = leaf.astype(cfg.param_dtype)
    return out


def pos_name(i: int) -> str:
    """The key of the period's position ``i`` in ``params["layers"]``."""
    return f"pos{i}"


def layer_name(i: int) -> str:
    """The key of layer ``i`` of ``params["dense"]``."""
    return f"layer{i}"


def run_name(i: int) -> str:
    """The key of the tail's run ``i`` in ``params["tail"]``."""
    return f"run{i}"


def init_params(cfg: LagunaConfig, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_dense, k_moe, k_tail, k_head = jax.random.split(rng, 5)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * cfg.init_std).astype(pd)

    def each(key, kinds):
        return [(i, k, kind) for i, (k, kind) in enumerate(zip(
            jax.random.split(key, max(len(kinds), 1)), kinds))]

    return {
        "embed": normal(k_embed, (V, D)),
        "dense": {
            layer_name(i): _init_block(cfg, k, kind, dense=True)
            for i, k, kind in each(k_dense, cfg.kinds[:cfg.n_dense_layers])},
        "layers": {
            pos_name(i): _init_block(
                cfg, k, kind, dense=False, layers=cfg.n_periods)
            for i, k, kind in each(k_moe, cfg.moe_kinds[:cfg.period])
            if cfg.n_periods},
        "tail": {
            run_name(i): _init_block(
                cfg, k, run.kinds[0], dense=False, layers=run.repeats)
            for i, k, run in each(k_tail, stack.runs(cfg.tail_kinds))},
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def _block_specs(cfg: LagunaConfig, kind: Kind, dense: bool, stacked: bool
                 ) -> Params:
    """A matrix shards its model-width side over fsdp (the side it
    projects back to, for ``wo`` and the down projections), an expert
    layer's stack of experts over ep; norms are replicated. ``stacked``:
    a leading axis of layers."""
    lead = (None,) if stacked else ()
    specs = {}
    for name, (shape, init) in _block_shapes(cfg, kind, dense).items():
        matrix = (None, FSDP) if init == "out" else (FSDP, None)
        if init == "ones":
            specs[name] = P(*lead, None)
        elif len(shape) == 3:
            specs[name] = P(*lead, EP, *matrix)
        else:
            specs[name] = P(*lead, *matrix)
    return specs


def param_specs(cfg: LagunaConfig) -> Params:
    """Data and expert parallelism, as ``models/dots3.py``: no tp, sp or
    pp (see `validate_for_mesh`)."""
    return {
        "embed": P(None, FSDP),
        "dense": {
            layer_name(i): _block_specs(cfg, kind, True, False)
            for i, kind in enumerate(cfg.kinds[:cfg.n_dense_layers])},
        "layers": {
            pos_name(i): _block_specs(cfg, kind, False, True)
            for i, kind in enumerate(cfg.moe_kinds[:cfg.period])
            if cfg.n_periods},
        "tail": {
            run_name(i): _block_specs(cfg, run.kinds[0], False, True)
            for i, run in enumerate(stack.runs(cfg.tail_kinds))},
        "final_norm": P(None),
        "lm_head": P(FSDP, None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(params: Params):
    """``params``' layers as the layout's parts take them."""
    dense, tail = params["dense"], params["tail"]
    period = tuple(params["layers"][pos_name(i)]
                   for i in range(len(params["layers"])))
    return ([dense[layer_name(i)] for i in range(len(dense))]
            + [period] * bool(period)
            + [(tail[run_name(i)],) for i in range(len(tail))])


def layer_params(cfg: LagunaConfig, params: Params, layer: int) -> Params:
    """Layer ``layer``'s own leaves, wherever the layout keeps them."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def validate_for_mesh(cfg: LagunaConfig, mesh: Mesh, batch: int = 0) -> None:
    """dp, fsdp and ep only; each other axis refused with what it lacks."""
    shape = dict(mesh.shape)
    heads = sorted(set(cfg.heads_per_layer))
    missing = {
        TP: f"the layers' query heads differ ({heads} on "
            f"{cfg.n_kv_heads} key heads) and the gate has a column a "
            "head: no head-sharded form of wq, w_g and wo is written for "
            "two head counts over one divisor",
        SP: f"window layers (window {cfg.window}): ring and ulysses "
            "attention have no window, and a sequence shard would need "
            f"its neighbour's last {cfg.window - 1} keys; run the sequence "
            "whole on a device",
        PP: "the stage split has no form for a period whose blocks differ "
            "in shape, nor for a leading dense layer",
    }
    for axis, why in missing.items():
        if shape.get(axis, 1) > 1:
            raise ValueError(f"laguna: mesh {axis}={shape[axis]}: {why}")
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
# The block, the forward
# ---------------------------------------------------------------------------

def head_gate(lp: Params, y, dt):
    """``sigmoid(y W_g)``: a number a head and position, (b, s, h)."""
    return jax.nn.sigmoid(y @ lp["w_g"].astype(dt))


def attention(cfg: LagunaConfig, mesh, kind: Kind, lp: Params, y):
    """``y (b, s, d)``, pre-normed -> the attention sublayer's output
    before the residual: the kind's query heads on the key heads, its
    rotary, its mask, the gate a head."""
    dt = cfg.dtype
    b, s, _ = y.shape
    (name, h), kvh, hd = kind, cfg.n_kv_heads, cfg.head_dim
    with trace.scope("attn_proj"):
        q = (y @ lp["wq"].astype(dt)).reshape(b, s, h, hd)
        k = (y @ lp["wk"].astype(dt)).reshape(b, s, kvh, hd)
        v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        inv_freq, magnitude = cfg.rotary(name)
        q = apply_rope(q, positions, inv_freq, magnitude)
        k = apply_rope(k, positions, inv_freq, magnitude)
    out = attn_ops.flash_attention(
        q, k, v, causal=True, mesh=mesh,
        window=cfg.window if name == WINDOW else None)
    with trace.scope("attn_gate"):
        out = out * head_gate(lp, y, dt)[..., None]
    with trace.scope("attn_proj"):
        return out.reshape(b, s, h * hd) @ lp["wo"].astype(dt)


def attention_half(cfg: LagunaConfig, mesh, kind: Kind, lp: Params, x):
    """The block's first half -> ``(x + attention, the feed-forward's
    normed input)``."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    x = x + attention(cfg, mesh, kind, lp, y)
    with trace.scope("norm"):
        return x, rms_norm(x, lp["mlp_norm"], cfg.norm_eps)


def feed_forward_half(cfg: LagunaConfig, mesh, lp: Params, x, u):
    """The block's second half: the expert layer where ``lp`` has a
    router, the dense SwiGLU otherwise."""
    if "router" in lp:
        x = x + moe.moe_mlp(cfg.as_moe(), lp, u, mesh)[0]
    else:
        with trace.scope("dense_mlp"):
            x = x + llama.swiglu(
                u, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def block(cfg: LagunaConfig, mesh, kind: Kind, lp: Params, x):
    """One layer of ``kind``."""
    x, u = attention_half(cfg, mesh, kind, lp, x)
    return feed_forward_half(cfg, mesh, lp, x, u)


def _report_shapes(cfg: LagunaConfig):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    kinds = cfg.layer_kinds
    full, swa = cfg.heads_of(FULL), cfg.heads_of(WINDOW)
    trace.gauge("attn.heads_full", full)
    trace.gauge("attn.heads_window", swa)
    trace.gauge("attn.group_full", full // cfg.n_kv_heads)
    trace.gauge("attn.group_window", swa // cfg.n_kv_heads)
    trace.gauge("attn.window", cfg.window if swa else 0)
    trace.gauge("attn.window_layers", kinds.count(WINDOW))
    trace.gauge("attn.full_layers", kinds.count(FULL))
    trace.gauge("attn.gate", 1)
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one (`_block_fn`)
    trace.gauge("rotary.yarn_factor", cfg.yarn_factor)
    trace.gauge("rotary.attention_factor", cfg.attention_factor)
    trace.gauge("rotary.dims_full", int(cfg.rotary_factor * cfg.head_dim))
    trace.gauge("rotary.dims_window",
                int(cfg.swa_rotary_factor * cfg.head_dim))
    trace.gauge("layers.period", cfg.period)
    trace.gauge("layers.dense", cfg.n_dense_layers)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: LagunaConfig, mesh, kind: Kind):
    """A block is recomputed whole in the backward pass, but for the
    flash forward's output and ``lse``, its backward's residuals (16384
    x 8192 x 2 B = 256 MiB a window layer at 16384 tokens, 192 MiB a
    full one): the kernel runs once a step. q, k and v are recomputed."""
    return stack.recompute(
        functools.partial(block, cfg, mesh, kind), cfg.remat,
        attn_ops.KEPT, attn_ops.report_kept)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: LagunaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm:
    (b, s, dim)."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in set(cfg.kinds)}
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return stack.walk(x, cfg.layout, _trees(params),
                      lambda kind, lp, x: (fns[kind](lp, x), None))[0]


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: LagunaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per expert layer, first to last, the (token, choice) pairs of
    ``tokens`` (b, s) whose chosen expert is a held one (as
    ``smallthinker.live_rows``): a forward of its own beside the step.
    (n_expert_layers,) int32."""
    mcfg, first = cfg.as_moe(), cfg.first_expert

    def each(kind, lp, x):
        x, u = attention_half(cfg, mesh, kind, lp, x)
        held = jnp.zeros((), jnp.int32)
        if "router" in lp:
            _, _, top_e = moe.route(
                mcfg, lp["router"], u.reshape(-1, cfg.dim))
            held = jnp.sum((top_e >= first) & (top_e < first + mcfg.n_held),
                           dtype=jnp.int32)
        return feed_forward_half(cfg, mesh, lp, x, u), held

    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    _, counts = stack.walk(x, cfg.layout, _trees(params), each)
    return counts[cfg.n_dense_layers:]


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: LagunaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored)."""
    x = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
