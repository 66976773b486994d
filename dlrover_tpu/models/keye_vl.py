"""The ``KeyeVL2`` decoder family (Keye-VL-2.0-30B-A3B, language model):
grouped-query attention over a learned selection of keys in **every**
layer, a norm a head on q and k, rotary positions of three rows a token,
and ``models/moe.py``'s expert layer (softmax top-k, renormalised, no
shared expert) after it.

Every piece another family has is that family's: the selection's whole
sequence (index scores, the exact top-k mask, the flash kernels' masked
walk, the head-summed probabilities, the KL) is
``ops/dsa.py selected_attention``, which ``models/dots3.py`` calls too;
the expert layer is ``moe.moe_mlp`` unchanged; the layers are one
stacked part of ``models/stack.py``; the embedding and the fused
cross-entropy are the shared ops.

What is this family's own, ``y = RMSNorm(x)``::

    q_h = RMSNorm_128(y W_q)_h,  k_g = RMSNorm_128(y W_k)_g,  v_g = (y W_v)_g
          32 query heads on 4 key heads of 128; one norm weight of 128 for
          all heads of q, one for k
    rotary on the whole head, halves against halves: pair i of 64 turns by
          p_c(i)[t] theta^(-2i/128), c(i) the row whose section of
          mrope_section (16, 24, 24) holds i
    qI_j = sg(y) W_Iq,j  (16 heads of 64),  kI = LayerNorm(sg(y) W_Ik)
          the same rotary on all 64 channels of each (32 pairs, the rows'
          sections halved),  w = sg(y) W_Iw / sqrt(16 * 64)
    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s])
    S_t  = the index_topk keys s <= t of largest I[t, s]
    o_h  = softmax_{s in S_t}(q_h . k_(h // 8) / sqrt(128)) v_(h // 8)
    x    = x + concat_h(o_h) W_o;   x = x + MoE(RMSNorm(x))

- **the positions are data**: ``loss_fn(params, tokens, cfg, mesh,
  positions)`` with ``positions (3, b, s)`` int32 (time, height, width);
  absent, a text's (three equal rows counting up). The angles' cosines
  and sines are formed once a step (`rotary_tables`, scope ``mrope``)
  and every layer of the scan reads them.
- **the loss in two parts**, as ``models/dots3.py``: mean CE, plus the
  mean over layers and tokens of ``KL(p^_t || softmax_{s in S_t} I[t,
  s])``, whose gradient reaches the indexer's five parameters alone.
- **what a block keeps** (`KEPT`): recomputed whole in the backward
  pass but for the selection's mask (1 byte a pair), the flash
  forward's output and ``lse``, and d L_I / d scores (4 bytes a pair),
  each behind a gauge.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import moe, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    attention as attn_ops,
    dsa,
    embed_lookup,
    mrope_tables,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.ops.norms import layer_norm
from dlrover_tpu.ops.rotary import turn
from dlrover_tpu.parallel.mesh import BATCH_AXES, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]

#: what a recomputed block keeps (`_block_fn`)
KEPT = (dsa.SELECT,) + attn_ops.KEPT + (dsa.LOSS_GRAD,)
#: the gauge that reads 1 once a block's checkpoint has kept the residual
KEPT_GAUGES = {dsa.SELECT: "attn.mask_kept", attn_ops.KEPT[0]: "attn.out_kept",
               dsa.LOSS_GRAD: "attn.loss_grad_kept"}


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    """Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json (the language model) by
    default."""
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_n_heads: int = 16               # sa_config.indexer_num_heads
    index_head_dim: int = 64              # sa_config.indexer_head_dim
    index_topk: int = 2048                # sa_config.topk
    expert_ffn_dim: int = 768             # moe_intermediate_size
    n_experts: int = 128                  # the router's width
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads on {self.n_kv_heads} key heads")
        for width in (self.head_dim, self.index_head_dim):
            if sum(self.sections(width)) != width // 2:
                raise ValueError(
                    f"mrope_section {self.mrope_section} does not deal out "
                    f"the {width // 2} pairs of a {width}-wide head")

    @staticmethod
    def from_hf(config: dict, **overrides) -> "KeyeVLConfig":
        """From a ``config.json`` of ``model_type: KeyeVL2`` (the language
        model's keys; ``overrides``: this program's own fields, the held
        share among them)."""
        sa, rope = config["sa_config"], config["rope_scaling"]
        fields = dict(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            rope_theta=float(config["rope_theta"]),
            mrope_section=tuple(rope["mrope_section"]),
            index_n_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"],
            index_topk=sa["topk"],
            expert_ffn_dim=config["moe_intermediate_size"],
            n_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            max_seq_len=config["max_position_embeddings"],
            norm_eps=float(config["rms_norm_eps"]),
        )
        for key, want in (("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("use_sliding_window", False)):
            if config.get(key, want) != want:
                raise ValueError(
                    f"keye_vl: {key}={config[key]!r} is not what "
                    f"models/keye_vl.py computes ({want!r})")
        if sa["indexer_num_kv_heads"] != 1 or rope.get(
                "rope_type", "default") != "default":
            raise ValueError(
                f"keye_vl: one index key a position and plain frequencies "
                f"are what models/keye_vl.py computes (sa_config {sa}, "
                f"rope_scaling {rope})")
        fields.update(overrides)
        return KeyeVLConfig(**fields)

    def sections(self, width: int) -> Tuple[int, ...]:
        """``mrope_section`` for a head of ``width``: as published at the
        main head's, in proportion (halved) at the indexer's."""
        return tuple(n * width // self.head_dim for n in self.mrope_section)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """Every layer alike: one stacked part of one position."""
        return (stack.Part(("K",), self.n_layers),)

    @property
    def pattern_string(self) -> str:
        """A letter a layer: K, selected grouped-query attention and the
        expert layer."""
        return "K" * self.n_layers

    def as_moe(self) -> moe.MoeConfig:
        """The expert layer's view (``models/moe.py`` runs it)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            stated_head_dim=self.head_dim, ffn_dim=self.expert_ffn_dim,
            n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, scoring="softmax",
            experts_held=self.experts_held, first_expert=self.first_expert,
            router_aux_coef=0.0, max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, remat=self.remat,
            ce_chunk_size=self.ce_chunk_size,
        )

    @staticmethod
    def tiny(**kw) -> "KeyeVLConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, rope_theta=1e4, mrope_section=(2, 2, 4),
            index_n_heads=2, index_head_dim=8, index_topk=16,
            expert_ffn_dim=32, n_experts=8, experts_per_token=2,
            max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return KeyeVLConfig(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

#: the indexer's parameters: what L_I moves, and nothing else does
INDEXER = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww")


def _block_shapes(cfg: KeyeVLConfig) -> Dict[str, Tuple]:
    """``{name: (shape, init)}`` of one block; ``init`` is "normal",
    "ones" or "zeros"."""
    D, hd, F = cfg.dim, cfg.head_dim, cfg.expert_ffn_dim
    H, KV = cfg.n_heads * hd, cfg.n_kv_heads * hd
    hi, di, E = cfg.index_n_heads, cfg.index_head_dim, cfg.as_moe().n_held
    return {
        "attn_norm": ((D,), "ones"),
        "wq": ((D, H), "normal"), "wk": ((D, KV), "normal"),
        "wv": ((D, KV), "normal"), "wo": ((H, D), "normal"),
        "q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones"),
        "idx_wq": ((D, hi * di), "normal"),
        "idx_wk": ((D, di), "normal"),
        "idx_k_norm": ((di,), "ones"), "idx_k_bias": ((di,), "zeros"),
        "idx_ww": ((D, hi), "normal"),
        "mlp_norm": ((D,), "ones"),
        "router": ((D, cfg.n_experts), "normal"),
        "w_gate": ((E, D, F), "normal"), "w_up": ((E, D, F), "normal"),
        "w_down": ((E, F, D), "normal"),
    }


def init_params(cfg: KeyeVLConfig, rng: jax.Array) -> Params:
    """Normal at 0.02, norms at one, the LayerNorm's bias at zero; the
    layers' leaves stacked on a leading axis of ``n_layers``."""
    pd, D, V, L = cfg.param_dtype, cfg.dim, cfg.vocab_size, cfg.n_layers
    k_embed, k_layers, k_head = jax.random.split(rng, 3)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(pd)

    shapes = _block_shapes(cfg)
    layers = {}
    for key, (name, (shape, rule)) in zip(
            jax.random.split(k_layers, len(shapes)), sorted(shapes.items())):
        layers[name] = normal(key, (L,) + shape) if rule == "normal" else (
            jnp.full((L,) + shape, float(rule == "ones"), pd))
    return {
        "embed": normal(k_embed, (V, D)),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def param_specs(cfg: KeyeVLConfig) -> Params:
    """Data and expert parallelism (see `validate_for_mesh`): a matrix
    shards its model-width side over fsdp, the experts' stack over ep;
    norms and the bias are replicated."""
    layers = {}
    for name, (shape, init) in _block_shapes(cfg).items():
        matrix = (None, FSDP) if name in ("wo", "w_down") else (FSDP, None)
        if init != "normal":
            layers[name] = P(None, *([None] * len(shape)))
        elif len(shape) == 3:
            layers[name] = P(None, EP, *matrix)
        else:
            layers[name] = P(None, *matrix)
    return {"embed": P(None, FSDP), "layers": layers,
            "final_norm": P(None), "lm_head": P(FSDP, None)}


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(params: Params):
    """``params``' layers as the layout's one part takes them."""
    return [(params["layers"],)]


def layer_params(cfg: KeyeVLConfig, params: Params, layer: int) -> Params:
    """Layer ``layer``'s own leaves."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def validate_for_mesh(cfg: KeyeVLConfig, mesh: Mesh, batch: int = 0) -> None:
    """dp, fsdp and ep only; each other axis refused with what it lacks."""
    shape = dict(mesh.shape)
    missing = {
        TP: "the indexer's one key a position and the selection's mask are "
            "every head's alike, and the head-summed probabilities sum "
            "over all the heads: a head shard would need the others' part "
            "of p before the KL",
        SP: "the indexer scores every earlier key and the threshold is a "
            "row's over the whole sequence, and ring and ulysses attention "
            "take no selection",
        PP: "the stage split carries one loss and token ids alone: it has "
            "no form for the loss's second part nor for the position rows",
    }
    for axis, why in missing.items():
        if shape.get(axis, 1) > 1:
            raise ValueError(f"keye_vl: mesh {axis}={shape[axis]}: {why}")
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
# The positions, the block, the forward
# ---------------------------------------------------------------------------

def text_positions(tokens) -> jnp.ndarray:
    """A text's ``(3, b, s)``: the three rows count up alike."""
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (3, b, s))


def rotary_tables(cfg: KeyeVLConfig, positions):
    """``((cos, sin) of the main heads' 64 pairs, (cos, sin) of the index
    heads' 32)``, each ``(b, s, pairs)`` float32: formed once a step,
    read by every layer."""
    with trace.scope("mrope"):
        return tuple(
            mrope_tables(positions, rope_frequencies(width, cfg.rope_theta),
                         cfg.sections(width))
            for width in (cfg.head_dim, cfg.index_head_dim))


def projections(cfg: KeyeVLConfig, tables, lp: Params, y):
    """``y (b, s, d)``, pre-normed -> ``(q (b, s, h, hd), k, v (b, s, hkv,
    hd), the indexer's q (b, s, hi, di), k (b, s, di), w (b, s, hi)
    float32)``: q and k through the norm a head, q, k and the indexer's
    pair turned by the step's tables."""
    dt = cfg.dtype
    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    main, index = tables
    with trace.scope("attn_proj"):
        q = rms_norm((y @ lp["wq"].astype(dt)).reshape(b, s, h, hd),
                     lp["q_norm"], cfg.norm_eps)
        k = rms_norm((y @ lp["wk"].astype(dt)).reshape(b, s, kvh, hd),
                     lp["k_norm"], cfg.norm_eps)
        v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
    with trace.scope("dsa_index"):
        # the indexer reads a constant: grouped heads have no q latent
        yi = lax.stop_gradient(y)
        iq = (yi @ lp["idx_wq"].astype(dt)).reshape(b, s, hi, di)
        ik = layer_norm(yi @ lp["idx_wk"].astype(dt), lp["idx_k_norm"],
                        lp["idx_k_bias"], cfg.norm_eps)[:, :, None, :]
        iw = (yi @ lp["idx_ww"].astype(dt)).astype(jnp.float32) * (
            hi ** -0.5 * di ** -0.5)
    with trace.scope("mrope"):
        q, k = turn(q, *main), turn(k, *main)
        iq, ik = turn(iq, *index), turn(ik, *index)[:, :, 0]
    return q, k, v, iq, ik, iw


def attention(cfg: KeyeVLConfig, mesh, tables, lp: Params, y,
              interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> ``(the attention sublayer's output
    before the residual, the layer's L_I summed over its rows, the
    selection's mask, the indexer's scores)``."""
    b, s, _ = y.shape
    out, l_i, mask, scores = dsa.selected_attention(
        *projections(cfg, tables, lp, y), cfg.index_topk, cfg.softmax_scale,
        interpret=interpret, mesh=mesh)
    # under the prefix the jobs' `gauges:` line prints
    for name in ("select_kernel", "index_bwd_kernels", "probs_heads_a_trip"):
        trace.gauge("attn." + name, trace.gauges().get("dsa." + name, 0))
    with trace.scope("attn_proj"):
        return (out.reshape(b, s, -1) @ lp["wo"].astype(cfg.dtype), l_i,
                mask, scores)


def attention_half(cfg: KeyeVLConfig, mesh, tables, lp: Params, x):
    """The block's first half -> ``(x + attention, the expert layer's
    normed input, the layer's L_I summed over its rows)``."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    attn, l_i = attention(cfg, mesh, tables, lp, y)[:2]
    x = x + attn
    with trace.scope("norm"):
        return x, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), l_i


def expert_half(cfg: KeyeVLConfig, mesh, lp: Params, x, u):
    """The block's second half."""
    x = x + moe.moe_mlp(cfg.as_moe(), lp, u, mesh)[0]
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def block(cfg: KeyeVLConfig, mesh, tables, lp: Params, x):
    """One layer -> ``(x', L_I summed over its rows)``."""
    x, u, l_i = attention_half(cfg, mesh, tables, lp, x)
    return expert_half(cfg, mesh, lp, x, u), l_i


def _report_shapes(cfg: KeyeVLConfig, seq: int, positions):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    n = min(seq, cfg.index_topk)
    trace.gauge("attn.select_topk", cfg.index_topk)
    trace.gauge("attn.select_pairs", n * (n + 1) // 2 + (seq - n) * n)
    trace.gauge("attn.causal_pairs", seq * (seq + 1) // 2)
    trace.gauge("attn.group", cfg.group)
    trace.gauge("attn.index_heads", cfg.index_n_heads)
    trace.gauge("attn.index_dim", cfg.index_head_dim)
    # 1 once the block's checkpoint has met the residual and kept it
    # (`_block_fn`): in a differentiated build under remat
    for gauge in KEPT_GAUGES.values():
        trace.gauge(gauge, 0)
    # the share of tokens whose three rows are not one position: of a
    # text none; of positions the caller closed over (a fixed layout)
    # counted here; of a traced argument unknown while the step is built
    if positions is None:
        trace.gauge("attn.mrope_rows_differ", 0.0)
    elif not isinstance(positions, jax.core.Tracer):
        rows = np.asarray(positions)
        trace.gauge("attn.mrope_rows_differ",
                    float(np.mean(np.any(rows != rows[:1], axis=0))))
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: KeyeVLConfig, mesh, tables):
    """A block is recomputed whole in the backward pass, but for `KEPT`:
    the selection's mask (the threshold's counting passes are not made
    twice), the flash forward's output and ``lse`` (its backward's
    residuals: the kernel runs once a step) and d L_I / d scores (spares
    the recomputed forward the score kernel, ``dsa_probs`` and the
    KL)."""
    def kept(name):
        if name in KEPT_GAUGES:
            trace.gauge(KEPT_GAUGES[name], 1)

    return stack.recompute(
        functools.partial(block, cfg, mesh, tables), cfg.remat, KEPT, kept)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: KeyeVLConfig,
    mesh: Optional[Mesh] = None, positions=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(the residual after the last block (b, s, dim), before the final
    norm; each layer's L_I summed over its rows (n_layers,))``."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg, tokens.shape[1], positions)
    if positions is None:
        positions = text_positions(tokens)
    fn = _block_fn(cfg, mesh, rotary_tables(cfg, positions))
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return stack.walk(x, cfg.layout, _trees(params),
                      lambda kind, lp, x: fn(lp, x))


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: KeyeVLConfig,
    mesh: Optional[Mesh] = None, positions=None,
) -> jnp.ndarray:
    """Per layer, first to last, the (token, choice) pairs of ``tokens``
    (b, s) whose chosen expert is a held one (as
    ``smallthinker.live_rows``): a forward of its own beside the step.
    (n_layers,) int32."""
    mcfg, first = cfg.as_moe(), cfg.first_expert
    if positions is None:
        positions = text_positions(tokens)
    tables = rotary_tables(cfg, positions)

    def each(kind, lp, x):
        x, u, _ = attention_half(cfg, mesh, tables, lp, x)
        _, _, top_e = moe.route(mcfg, lp["router"], u.reshape(-1, cfg.dim))
        held = jnp.sum((top_e >= first) & (top_e < first + mcfg.n_held),
                       dtype=jnp.int32)
        return expert_half(cfg, mesh, lp, x, u), held

    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return stack.walk(x, cfg.layout, _trees(params), each)[1]


def loss_terms(
    params: Params, tokens: jnp.ndarray, cfg: KeyeVLConfig,
    mesh: Optional[Mesh] = None, positions=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(CE, L_I)``: mean next-token cross-entropy (pad tokens < 0
    ignored) and the indexer's KL, its mean over the layers and the
    tokens. Their gradients are disjoint."""
    x, l_i = forward_layers(params, tokens, cfg, mesh, positions)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    ce = stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
    return ce, jnp.sum(l_i) / (cfg.n_layers * tokens.size)


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: KeyeVLConfig,
    mesh: Optional[Mesh] = None, positions=None,
) -> jnp.ndarray:
    ce, l_i = loss_terms(params, tokens, cfg, mesh, positions)
    return ce + l_i
