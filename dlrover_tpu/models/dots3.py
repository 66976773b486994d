"""The ``dots3_note`` decoder family (dots3-note-prev, language model):
latent attention over a learned selection of keys in the full-attention
layers, window latent attention with ranks of its own in the others, a
sigmoid gate a head on attention's output, a leading dense layer, and
``models/moe.py``'s expert layer (sigmoid routing with a choice bias, a
shared expert) everywhere else.

Every piece another family has is that family's: latent attention is
``xing4.latent_attention`` (called with a layer kind's own view of the
widths; it hands the normed q latent out and takes the selection or the
window in), the flash kernels and the selection's own pieces are
``ops/attention.py``'s and ``ops/dsa.py``'s, the expert layer is
``moe.moe_mlp`` unchanged, the dense feed-forward ``llama.swiglu``, the
embedding and the fused cross-entropy the shared ops.

What is this family's own:

- **two kinds of layer in one period** (``layer_types``): ``F`` full
  attention with the indexer, ``S`` sliding-window attention. Their
  attention parameters differ in *shape* (ranks, head counts, widths;
  the indexer is ``F``'s alone), so the layers are stacked **a position
  of the period** as ``models/smallthinker.py`` stacks its kinds, each
  position's slab with its own shapes. The leading
  ``first_k_dense_replace`` layers (dense feed-forward) come before the
  scan, each with its own tree; the expert layers are one scan over
  their shortest period (``stack.walk``), and what the depth leaves past
  whole periods (the published layout ends on one more ``F``) follows it.
- **the full layer**, ``y = RMSNorm(x)``::

      c_q  = a_q RMSNorm(y W_qa);  q_h = c_q W_qb,h      (rotary on 64)
      c_kv = a_kv RMSNorm(y W_kva[:r]);  [k_h | v_h] = c_kv W_kvb,h
      I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])      the indexer, on
                qI = sg(c_q) W_Iq, kI = LayerNorm(sg(y) W_Ik), rotary on
                the first 64 of each, w = sg(y) W_Iw / sqrt(64 * 128)
      S_t  = the index_topk keys s <= t of largest I[t, s]
      o_h  = softmax_{s in S_t}(q_h . k_h / sqrt(192)) v_h
      out  = concat_h(sigmoid(y W_g)_h o_h) W_o

  with ``a = sqrt(hidden / rank)`` (``apply_mla_qkv_lora_rescale``). The
  window layer is the same without indexer at its own ranks, widths and
  theta over ``0 <= t - s < sliding_window_size``.
- **the loss in two parts**: mean CE, plus the indexer's ``L_I``, the
  mean over full layers and tokens of ``KL(p^_t || softmax_{s in S_t}
  I[t, s])``, ``p`` the main attention's probabilities summed over the
  held heads, under ``stop_gradient`` as are the indexer's inputs: ``L_I``
  moves the indexer's four parameters alone, CE everything else (top-k
  gives the indexer no gradient through the selection).
- **held heads** (``heads_held`` of ``n_heads``): one chip's share of a
  deployment that splits a layer's heads. ``W_qb``, ``W_kvb``, ``W_g``
  hold the held heads' columns and ``W_o`` their rows; ``W_qa``,
  ``W_kva``, the norms and the whole indexer are what every chip of the
  group computes alike. What the absent heads would add to the layer's
  output, and to ``p``, is left out. No code stands in for the others.
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
from dlrover_tpu.models.xing4 import latent_attention
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    attention as attn_ops,
    dsa,
    embed_lookup,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.ops.norms import layer_norm
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]

FULL, WINDOW = "F", "S"
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}
_PUBLISHED_TYPES = (FULL,) + (FULL, WINDOW, WINDOW, WINDOW) * 11 + (FULL,)


@dataclasses.dataclass(frozen=True)
class LatentShape:
    """What ``xing4.latent_attention`` reads of a config, for one kind of
    layer."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float
    latent_rescale: Tuple[float, float]
    dtype: Any
    norm_eps: float
    rope_magnitude: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """dots-studio/dots3-note-prev's config.json by default."""
    vocab_size: int = 152064
    dim: int = 5120
    layer_kinds: Tuple[str, ...] = _PUBLISHED_TYPES   # layer_types
    n_dense_layers: int = 1                           # first_k_dense_replace
    # full-attention layers
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding-window layers
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    window: int = 513                                 # sliding_window_size
    # the heads this job holds of a layer's n_heads / swa_n_heads (None:
    # all): heads first_head .. first_head + heads_held - 1
    heads_held: Optional[int] = None
    swa_heads_held: Optional[int] = None
    first_head: int = 0
    swa_first_head: int = 0
    latent_rescale: bool = True       # apply_mla_qkv_lora_rescale
    dense_ffn_dim: int = 13824
    expert_ffn_dim: int = 1536
    n_experts: int = 256
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    scoring: str = "sigmoid"
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 524288
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if set(self.layer_kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: each {FULL!r} (full "
                f"attention) or {WINDOW!r} (sliding window)")
        if not 0 <= self.n_dense_layers <= len(self.layer_kinds):
            raise ValueError(
                f"n_dense_layers={self.n_dense_layers} of "
                f"{len(self.layer_kinds)} layers")
        for held, of, first in (
                (self.n_held_heads(FULL), self.n_heads, self.first_head),
                (self.n_held_heads(WINDOW), self.swa_n_heads,
                 self.swa_first_head)):
            if not 0 < held <= of or first < 0 or first + held > of:
                raise ValueError(
                    f"heads {first}..{first + held - 1} held of {of}")

    @staticmethod
    def from_hf(config: dict, **overrides) -> "Dots3Config":
        """From a ``config.json`` of ``model_type: dots3_note``
        (``overrides``: this program's own fields, the held shares among
        them)."""
        fields = dict(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_kinds=tuple(_KINDS[t] for t in config["layer_types"]),
            n_dense_layers=config["first_k_dense_replace"],
            n_heads=config["num_attention_heads"],
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_theta=float(config["rope_theta"]),
            index_n_heads=config["index_n_heads"],
            index_head_dim=config["index_head_dim"],
            index_topk=config["index_topk"],
            swa_n_heads=config["swa_num_attention_heads"],
            swa_q_lora_rank=config["swa_q_lora_rank"],
            swa_kv_lora_rank=config["swa_kv_lora_rank"],
            swa_qk_nope_dim=config["swa_qk_nope_head_dim"],
            swa_qk_rope_dim=config["swa_qk_rope_head_dim"],
            swa_v_head_dim=config["swa_v_head_dim"],
            swa_rope_theta=float(config["swa_rope_theta"]),
            window=config["sliding_window_size"],
            latent_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
            dense_ffn_dim=config["intermediate_size"],
            expert_ffn_dim=config["moe_intermediate_size"],
            n_experts=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["n_shared_experts"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling=float(config["routed_scaling_factor"]),
            scoring=config["scoring_func"],
            max_seq_len=config["max_position_embeddings"],
            norm_eps=float(config["rms_norm_eps"]),
        )
        for key, want in (("attention_gate_type", "headwise"),
                          ("swa_attention_gate_type", "headwise"),
                          ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                          ("rope_scaling", None), ("hidden_act", "silu"),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if config.get(key, want) != want:
                raise ValueError(
                    f"dots3: {key}={config[key]!r} is not what "
                    f"models/dots3.py computes ({want!r})")
        if len(config["layer_types"]) != config["num_hidden_layers"]:
            raise ValueError(
                f"{len(config['layer_types'])} layer_types for "
                f"{config['num_hidden_layers']} layers")
        fields.update(overrides)
        return Dots3Config(**fields)

    # -- the layout ---------------------------------------------------------

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def moe_kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds[self.n_dense_layers:]

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """The dense layers, each a part; the expert layers' shortest
        period, stacked; what the depth leaves past whole periods."""
        return stack.periodic(self.layer_kinds, head=self.n_dense_layers)

    @property
    def period(self) -> int:
        return max((len(p.kinds) for p in self.layout if p.repeats), default=1)

    @property
    def n_periods(self) -> int:
        return sum(p.repeats or 0 for p in self.layout)

    @property
    def tail_kinds(self) -> Tuple[str, ...]:
        return self.moe_kinds[self.n_periods * self.period:]

    @property
    def pattern_string(self) -> str:
        """A letter a layer: F full attention over the selection, S
        sliding window; the dense layers' in lower case."""
        n = self.n_dense_layers
        return ("".join(self.layer_kinds[:n]).lower()
                + "".join(self.layer_kinds[n:]))

    # -- a layer kind's widths ------------------------------------------------

    def n_held_heads(self, kind: str) -> int:
        if kind == FULL:
            return self.n_heads if self.heads_held is None else (
                self.heads_held)
        return self.swa_n_heads if self.swa_heads_held is None else (
            self.swa_heads_held)

    def latent(self, kind: str) -> LatentShape:
        """``xing4.latent_attention``'s view of a layer of ``kind``: the
        held heads, the kind's own ranks, widths and theta."""
        if kind == FULL:
            rq, rkv = self.q_lora_rank, self.kv_lora_rank
            widths = (self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim,
                      self.rope_theta)
        else:
            rq, rkv = self.swa_q_lora_rank, self.swa_kv_lora_rank
            widths = (self.swa_qk_nope_dim, self.swa_qk_rope_dim,
                      self.swa_v_head_dim, self.swa_rope_theta)
        rescale = ((self.dim / rq) ** 0.5, (self.dim / rkv) ** 0.5) if (
            self.latent_rescale) else (1.0, 1.0)
        return LatentShape(
            self.n_held_heads(kind), rq, rkv, *widths, rescale, self.dtype,
            self.norm_eps)

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=len(self.moe_kinds), n_heads=self.n_heads,
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
    def tiny(**kw) -> "Dots3Config":
        base = dict(
            vocab_size=256, dim=64,
            layer_kinds=(FULL,) + (FULL, WINDOW, WINDOW, WINDOW),
            n_dense_layers=1, n_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, rope_theta=1e4,
            index_n_heads=4, index_head_dim=16, index_topk=16,
            swa_n_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=32,
            swa_qk_nope_dim=24, swa_qk_rope_dim=8, swa_v_head_dim=16,
            swa_rope_theta=1e3, window=9, dense_ffn_dim=96,
            expert_ffn_dim=32, n_experts=8, experts_per_token=2,
            max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return Dots3Config(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

#: the indexer's parameters: what L_I moves, and nothing else does
INDEXER = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww")


def _block_shapes(cfg: Dots3Config, kind: str, dense: bool
                  ) -> Dict[str, Tuple]:
    """``{name: (shape, init)}`` of one block of ``kind``; ``init`` is
    "normal", "ones" or "zeros"."""
    D, a = cfg.dim, cfg.latent(kind)
    h, rq, rkv = a.n_heads, a.q_lora_rank, a.kv_lora_rank
    dn, dr, dv = a.qk_nope_dim, a.qk_rope_dim, a.v_head_dim
    shapes = {
        "attn_norm": ((D,), "ones"),
        "w_qa": ((D, rq), "normal"),
        "q_a_norm": ((rq,), "ones"),
        "w_qb": ((rq, h * (dn + dr)), "normal"),
        "w_kva": ((D, rkv + dr), "normal"),
        "kv_a_norm": ((rkv,), "ones"),
        "w_kvb": ((rkv, h * (dn + dv)), "normal"),
        "w_g": ((D, h), "normal"),        # attention_gate_type: headwise
        "w_o": ((h * dv, D), "normal"),
        "mlp_norm": ((D,), "ones"),
    }
    if kind == FULL:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        shapes.update({
            "idx_wq": ((rq, hi * di), "normal"),
            "idx_wk": ((D, di), "normal"),
            "idx_k_norm": ((di,), "ones"),
            "idx_k_bias": ((di,), "zeros"),
            "idx_ww": ((D, hi), "normal"),
        })
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


def _init_block(cfg: Dots3Config, key, kind: str, dense: bool,
                layers: Optional[int] = None) -> Params:
    """One block's tree, or ``layers`` of them stacked."""
    shapes = _block_shapes(cfg, kind, dense)
    lead = () if layers is None else (layers,)
    out = {}
    for k, (name, (shape, rule)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items())):
        if rule == "normal":
            leaf = jax.random.normal(k, lead + shape, jnp.float32) * 0.02
        else:
            leaf = jnp.full(lead + shape, float(rule == "ones"), jnp.float32)
        out[name] = leaf.astype(cfg.param_dtype)
    return out


def pos_name(i: int) -> str:
    """The key of the period's position ``i`` in ``params["layers"]``."""
    return f"pos{i}"


def layer_name(i: int) -> str:
    """The key of layer ``i`` of ``params["dense"]`` / ``params["tail"]``."""
    return f"layer{i}"


def init_params(cfg: Dots3Config, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_dense, k_moe, k_tail, k_head = jax.random.split(rng, 5)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(pd)

    def each(key, kinds):
        return [(i, k, kind) for i, (k, kind) in enumerate(zip(
            jax.random.split(key, max(len(kinds), 1)), kinds))]

    return {
        "embed": normal(k_embed, (V, D)),
        "dense": {
            layer_name(i): _init_block(cfg, k, kind, dense=True)
            for i, k, kind in each(
                k_dense, cfg.layer_kinds[:cfg.n_dense_layers])},
        "layers": {
            pos_name(i): _init_block(
                cfg, k, kind, dense=False, layers=cfg.n_periods)
            for i, k, kind in each(k_moe, cfg.moe_kinds[:cfg.period])},
        "tail": {
            layer_name(i): _init_block(cfg, k, kind, dense=False)
            for i, k, kind in each(k_tail, cfg.tail_kinds)},
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def _block_specs(cfg: Dots3Config, kind: str, dense: bool, stacked: bool
                 ) -> Params:
    """A matrix shards its model-width side over fsdp (the side it
    projects back to, for ``w_o`` and the down projections), an expert
    layer's stack of experts over ep; norms and biases are replicated.
    ``stacked``: a leading axis of layers."""
    lead = (None,) if stacked else ()
    specs = {}
    for name, (shape, init) in _block_shapes(cfg, kind, dense).items():
        matrix = (None, FSDP) if "down" in name or name == "w_o" else (
            FSDP, None)
        if init != "normal":
            specs[name] = P(*lead, *([None] * len(shape)))
        elif len(shape) == 3:
            specs[name] = P(*lead, EP, *matrix)
        elif shape[0] == cfg.dim or name == "w_o":
            specs[name] = P(*lead, *matrix)
        else:
            # a rank's side (w_qb, w_kvb, idx_wq): no model width to shard
            specs[name] = P(*lead, None, None)
    return specs


def param_specs(cfg: Dots3Config) -> Params:
    """Data and expert parallelism, as ``models/xing4.py``: no tp, sp or
    pp (see `validate_for_mesh`)."""
    return {
        "embed": P(None, FSDP),
        "dense": {
            layer_name(i): _block_specs(cfg, kind, True, False)
            for i, kind in enumerate(cfg.layer_kinds[:cfg.n_dense_layers])},
        "layers": {
            pos_name(i): _block_specs(cfg, kind, False, True)
            for i, kind in enumerate(cfg.moe_kinds[:cfg.period])},
        "tail": {
            layer_name(i): _block_specs(cfg, kind, False, False)
            for i, kind in enumerate(cfg.tail_kinds)},
        "final_norm": P(None),
        "lm_head": P(FSDP, None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(params: Params):
    """``params``' layers as the layout's parts take them."""
    def own(group):
        return [group[layer_name(i)] for i in range(len(group))]

    period = tuple(params["layers"][pos_name(i)]
                   for i in range(len(params["layers"])))
    return own(params["dense"]) + [period] * bool(period) + own(params["tail"])


def layer_params(cfg: Dots3Config, params: Params, layer: int) -> Params:
    """Layer ``layer``'s own leaves, wherever the layout keeps them."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def validate_for_mesh(cfg: Dots3Config, mesh: Mesh, batch: int = 0) -> None:
    """dp, fsdp and ep only; each other axis refused with what it lacks."""
    shape = dict(mesh.shape)
    missing = {
        TP: "the held heads are a share of a deployment, not a mesh axis: "
            "latent attention has no head-sharded form (its rank "
            "bottlenecks and the indexer are computed whole by every "
            "holder), nor have the gate's columns",
        SP: "the indexer scores every earlier key and the threshold is a "
            "row's over the whole sequence, and ring and ulysses attention "
            "take neither a selection nor a window",
        PP: "the stage split has no form for a period whose blocks differ "
            "in shape, nor for the loss's second part",
    }
    for axis, why in missing.items():
        if shape.get(axis, 1) > 1:
            raise ValueError(f"dots3: mesh {axis}={shape[axis]}: {why}")
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
# The indexer, the block, the forward
# ---------------------------------------------------------------------------

def index_inputs(cfg: Dots3Config, positions, inv_freq, lp, y, c_q):
    """The indexer's ``(q (b, s, hi, di), k (b, s, di), w (b, s, hi)
    float32)`` from the layer's normed input ``y`` and q latent ``c_q``,
    neither of which its gradient reaches."""
    dt = cfg.dtype
    b, s, _ = y.shape
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_dim
    with trace.scope("dsa_index"):
        y, c_q = lax.stop_gradient(y), lax.stop_gradient(c_q)
        q = (c_q @ lp["idx_wq"].astype(dt)).reshape(b, s, hi, di)
        k = layer_norm(y @ lp["idx_wk"].astype(dt), lp["idx_k_norm"],
                        lp["idx_k_bias"], cfg.norm_eps)[:, :, None, :]
        # rotary on the first qk_rope_dim of each
        q = jnp.concatenate(
            [apply_rope(q[..., :dr], positions, inv_freq), q[..., dr:]], -1)
        k = jnp.concatenate(
            [apply_rope(k[..., :dr], positions, inv_freq), k[..., dr:]], -1)
        w = (y @ lp["idx_ww"].astype(dt)).astype(jnp.float32) * (
            hi ** -0.5 * di ** -0.5)
        return q, k[:, :, 0], w


def selected_attention(cfg: Dots3Config, mesh, positions, inv_freq, lp, y,
                       interpret: bool = False):
    """``attend`` of a full layer for ``latent_attention``, and the dict
    it leaves ``l_i`` in (the layer's KL summed over its rows) with the
    selection ``mask`` and the indexer's ``scores``: the indexer's
    inputs are this family's, the sequence after them
    `dsa.selected_attention`'s."""
    scale = cfg.latent(FULL).softmax_scale
    aux = {}

    def attend(q, k, v, c_q):
        out, aux["l_i"], aux["mask"], aux["scores"] = dsa.selected_attention(
            q, k, v, *index_inputs(cfg, positions, inv_freq, lp, y, c_q),
            cfg.index_topk, scale, interpret=interpret, mesh=mesh)
        # under the prefix the jobs' `gauges:` line prints
        trace.gauge("attn.index_bwd_kernels",
                    trace.gauges().get("dsa.index_bwd_kernels", 0))
        return out

    return attend, aux


def attention(cfg: Dots3Config, mesh, kind: str, positions, lp, y,
              interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> ``(the attention sublayer's output
    before the residual, the layer's L_I summed over its rows)``."""
    shape = cfg.latent(kind)
    inv_freq = rope_frequencies(shape.qk_rope_dim, shape.rope_theta)
    if kind == WINDOW:
        return latent_attention(
            shape, mesh, positions, inv_freq, lp, y,
            window=cfg.window), jnp.zeros((), jnp.float32)
    attend, aux = selected_attention(
        cfg, mesh, positions, inv_freq, lp, y, interpret)
    out = latent_attention(
        shape, mesh, positions, inv_freq, lp, y, attend=attend)
    return out, aux["l_i"]


def attention_half(cfg: Dots3Config, mesh, kind: str, positions, lp, x):
    """The block's first half -> ``(x + attention, the feed-forward's
    normed input, the layer's L_I summed over its rows)``."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    attn, l_i = attention(cfg, mesh, kind, positions, lp, y)
    x = x + attn
    with trace.scope("norm"):
        return x, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), l_i


def feed_forward_half(cfg: Dots3Config, mesh, lp: Params, x, u):
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


def block(cfg: Dots3Config, mesh, kind: str, positions, lp: Params, x):
    """One layer of ``kind`` -> ``(x', L_I summed over its rows)``."""
    x, u, l_i = attention_half(cfg, mesh, kind, positions, lp, x)
    return feed_forward_half(cfg, mesh, lp, x, u), l_i


def _report_shapes(cfg: Dots3Config):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    kinds = cfg.layer_kinds
    full = cfg.latent(FULL)
    trace.gauge("dsa.topk", cfg.index_topk)
    trace.gauge("dsa.index_heads", cfg.index_n_heads)
    # 1 once a full block's checkpoint has met the loss's gradient and
    # kept it (`_block_fn`): in a differentiated build under remat
    trace.gauge("dsa.loss_grad_kept", 0)
    trace.gauge("attn.out_kept", 0)  # as above, of a flash forward's output
    trace.gauge("attn.heads_held", cfg.n_held_heads(FULL))
    trace.gauge("attn.heads", cfg.n_heads)
    trace.gauge("attn.swa_heads_held", cfg.n_held_heads(WINDOW))
    trace.gauge("attn.swa_heads", cfg.swa_n_heads)
    trace.gauge("attn.window", cfg.window if WINDOW in kinds else 0)
    trace.gauge("attn.window_layers", kinds.count(WINDOW))
    trace.gauge("attn.full_layers", kinds.count(FULL))
    trace.gauge("attn.scale", full.softmax_scale)
    trace.gauge("mla.qk_head_dim", full.qk_head_dim)
    trace.gauge("mla.kv_lora_rank", full.kv_lora_rank)
    trace.gauge("mla.swa_qk_head_dim", cfg.latent(WINDOW).qk_head_dim)
    trace.gauge("mla.swa_kv_lora_rank", cfg.swa_kv_lora_rank)
    trace.gauge("layers.period", cfg.period)
    trace.gauge("layers.dense", cfg.n_dense_layers)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: Dots3Config, mesh, kind: str, positions):
    """A block is recomputed whole in the backward pass, but for the
    flash forward's output and ``lse`` (its backward's residuals: the
    kernel runs once a step) and the two arrays a full layer names. The
    selection's mask, 1 byte a pair, spares the threshold's 45 passes
    over the scores. d L_I / d scores, 4 bytes a pair
    (`dsa.indexer_loss` forms it in the forward: its target is a
    constant), spares the indexer's score kernel, `dsa_probs` and the
    KL, which nothing else in the backward reads."""
    def kept(name):
        attn_ops.report_kept(name)
        if name == dsa.LOSS_GRAD:
            trace.gauge("dsa.loss_grad_kept", 1)

    return stack.recompute(
        functools.partial(block, cfg, mesh, kind, positions), cfg.remat,
        attn_ops.KEPT + ((dsa.SELECT, dsa.LOSS_GRAD) if kind == FULL
                         else ()), kept)


def _positions(tokens):
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: Dots3Config,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(the residual after the last block (b, s, dim), before the final
    norm; each layer's L_I summed over its rows (n_layers,), 0 for a
    window layer)``."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg)
    positions = _positions(tokens)
    fns = {kind: _block_fn(cfg, mesh, kind, positions)
           for kind in (FULL, WINDOW)}
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return stack.walk(x, cfg.layout, _trees(params),
                      lambda kind, lp, x: fns[kind](lp, x))


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: Dots3Config,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per expert layer, first to last, the (token, choice) pairs of
    ``tokens`` (b, s) whose chosen expert is a held one (as
    ``smallthinker.live_rows``): a forward of its own beside the step.
    (n_expert_layers,) int32."""
    mcfg = cfg.as_moe()
    first = cfg.first_expert
    positions = _positions(tokens)

    def each(kind, lp, x):
        x, u, _ = attention_half(cfg, mesh, kind, positions, lp, x)
        held = jnp.zeros((), jnp.float32)
        if "router" in lp:
            _, _, top_e = moe.route(
                mcfg, lp["router"], u.reshape(-1, cfg.dim),
                bias=lp.get("router_bias"))
            held = jnp.sum(
                (top_e >= first) & (top_e < first + mcfg.n_held),
                dtype=jnp.float32)
        return feed_forward_half(cfg, mesh, lp, x, u), held

    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    _, counts = stack.walk(x, cfg.layout, _trees(params), each)
    return counts[cfg.n_dense_layers:].astype(jnp.int32)


def loss_terms(
    params: Params, tokens: jnp.ndarray, cfg: Dots3Config,
    mesh: Optional[Mesh] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(CE, L_I)``: mean next-token cross-entropy (pad tokens < 0
    ignored) and the indexer's KL, its mean over the full layers and the
    tokens. Their gradients are disjoint."""
    x, l_i = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    ce = stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
    n_full = max(cfg.layer_kinds.count(FULL), 1)
    return ce, jnp.sum(l_i) / (n_full * tokens.size)


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: Dots3Config,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    ce, l_i = loss_terms(params, tokens, cfg, mesh)
    return ce + l_i
