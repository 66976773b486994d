"""Sparse-MoE decoders (Mixtral, OLMoE) with expert parallelism, TPU-first.

Expert parallelism is green-field relative to the reference (it is only
checkpoint-aware of Megatron EP ranks, ``megatron_dist_ckpt.py:247``); here
it is a real compute path, and there is one of it:

- **route** (``moe_route``): router matmul and scores in float32 over
  all experts (``scoring``: softmax, or a sigmoid an expert), the ``k``
  largest of a token and their experts, chosen by score plus a
  per-expert bias where the layer has one (``router_bias``; the weights
  stay the scores); renormalised over the chosen ``k`` only where the
  model's config says so (``norm_topk_prob``: Mixtral true, OLMoE
  false) and scaled by ``routed_scaling``.
- **the held share** (``experts_held``, ``first_expert``): a chip that
  holds ``experts_held`` of the ``n_experts`` the router scores (one
  chip's share of an expert-parallel job) computes the pairs that chose
  its experts; the others sort into the tail and add nothing. Under a
  mesh ``ep`` divides the held experts further.
- **what the router reads** (``route_on``): the tensor the choice is
  made on is by default the tensor the experts are applied to; a model
  whose router reads the attention's input (``models/smallthinker.py``)
  hands that in beside it, and the router's gradient goes where it
  came from.
- **the experts' activation** (``expert_act``): ``silu`` (SwiGLU) or
  ``relu`` (ReGLU) on the gate.
- **shared expert**: where the layer has ``ws_gate`` / ``ws_up`` /
  ``ws_down``, every token also passes through that dense SwiGLU
  (``moe_shared``), added to the routed result; where it also has
  ``w_s``, under a sigmoid gate a token (Qwen3-Next).
- **sorted dispatch** (``moe_dispatch``): the ``t * k`` (token, choice)
  pairs are ordered by expert (one stable sort of int32 keys);
  ``group_sizes (e,)`` counts each expert's pairs and the rows are
  gathered to ``(t * k, d)``. Dropless: every pair is computed whatever
  the load, shapes are static, cost is linear in ``t``. No tensor of
  size ``t x e x capacity`` exists.
- **grouped matmul** (``moe_experts``): gate, up and down are each one
  grouped product over the ragged groups (``ops/grouped_matmul.py``'s
  kernels on the TPU, up's d-lhs adding onto gate's; ``lax.ragged_dot``
  off it and where a shape does not tile), bf16 operands under f32
  accumulation; docs/design/kernels.md has what was measured.
- **combine** (``moe_combine``): rows go back to token order weighted by
  the router's probabilities and the ``k`` of a token are summed.
  Dispatch and combine are ``custom_vjp`` pairs whose backward is again a
  gather (by the inverse permutation), never a scatter-add.
- **the live count** (``ops/moe_rows.py``): where a tail can exist (a
  held share on one chip), nothing between dispatch's gather and the
  layer's result touches a row at or past ``sum(group_sizes)``: combine,
  its backward and dispatch's backward are Pallas kernels on the TPU
  that move the rows below it and no others, ``act(gate) x up`` and its
  backward are passes over the blocks below it, and the grouped
  products walk no tile of the tail, forward or backward
  (``tail_unread``; the gauge ``moe.tail_skipped``). The count stays on
  the device and the arrays keep their ``(t * k, .)``; past the count
  they are unwritten memory (``lax.ragged_dot``, where a shape falls
  back to it, would read it, which is why all of this runs only beside
  the grouped kernels). Dispatch's forward gathers the live rows and no
  others where the held share and the tokens' size make that the faster
  (`moe_rows.gather_pays`; the gauge ``moe.dispatch_bounded``) and is
  XLA's gather of every row elsewhere, as is everything where every
  expert is held and under a mesh.
- **under a mesh** the same path runs inside ``shard_map``: tokens stay
  where the batch axes put them, the rows are gathered over ``ep``, each
  rank sorts by its local experts (pairs for other ranks' experts fall
  in a tail past ``sum(group_sizes)`` that the grouped matmul leaves
  zero and ``combine_rows`` weighs into nothing) and the result is
  ``psum_scatter``-ed back; ``tp`` splits the
  expert width (a ``psum`` closes the down projection) and ``fsdp``
  shards are gathered on the way in. Correct on the CPU meshes of
  tests/test_moe.py; its speed is nobody's subject until a four-chip
  MoE cell can exist (PERF.md section 7).
- **aux load-balance loss** ``E * sum_i f_i P_i`` per layer (``f``: share
  of (token, choice) pairs on expert ``i``; ``P``: mean router
  probability), averaged over the layers through the layer scan.
- attention/rope/norm reuse the Llama blocks (ring attention over sp when
  the mesh has it); OLMoE adds RMSNorm over the whole q and k
  projections (``qk_norm``) before the split into heads and rotary.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    embed_lookup,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.ops import moe_rows
from dlrover_tpu.ops.grouped_matmul import choose_tiles, grouped_matmuls
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
    # what the model's config.json states: whether the chosen experts'
    # probabilities are renormalised to sum to one (Mixtral) or used as
    # they are (OLMoE), and whether q and k pass through an RMSNorm of
    # their own before rotary (OLMoE)
    norm_topk_prob: bool = True
    qk_norm: bool = False
    # how the router scores ("softmax" over all experts, or "sigmoid" an
    # expert) and what the chosen weights are multiplied by
    scoring: str = "softmax"
    routed_scaling: float = 1.0
    # the experts this job holds of the n_experts the router scores:
    # experts first_expert .. first_expert + experts_held - 1 (None: all)
    experts_held: Optional[int] = None
    first_expert: int = 0
    # the gate's activation inside an expert: "silu" or "relu"
    expert_act: str = "silu"
    # a head's width where it is not dim / n_heads (None: it is)
    stated_head_dim: Optional[int] = None
    router_aux_coef: float = 0.01
    max_seq_len: int = 8192
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "auto"
    # chunked fused cross-entropy (ops/chunked_ce.py): vocab columns per
    # loss scan step
    ce_chunk_size: int = 2048

    @property
    def head_dim(self) -> int:
        if self.stated_head_dim is not None:
            return self.stated_head_dim
        return self.dim // self.n_heads

    @property
    def n_held(self) -> int:
        return self.n_experts if self.experts_held is None else (
            self.experts_held)

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
    def olmoe_1b_7b() -> "MoeConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct's config.json."""
        return MoeConfig(
            vocab_size=50304, dim=2048, n_layers=16, n_heads=16,
            n_kv_heads=16, ffn_dim=1024, n_experts=64, experts_per_token=8,
            norm_topk_prob=False, qk_norm=True, max_seq_len=4096,
            rope_theta=10000.0, norm_eps=1e-5,
        )

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
    L, D, E, F = cfg.n_layers, cfg.dim, cfg.n_held, cfg.ffn_dim
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
        "router": norm_init(ks[4], (L, D, cfg.n_experts), std),
        "w_gate": norm_init(ks[5], (L, E, D, F), std),
        "w_up": norm_init(ks[6], (L, E, D, F), std),
        "w_down": norm_init(ks[7], (L, E, F, D), out_scale),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, H), pd)
        layers["k_norm"] = jnp.ones((L, KV), pd)
    return {
        "embed": norm_init(k_embed, (cfg.vocab_size, D), std),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": norm_init(k_head, (D, cfg.vocab_size), std),
    }


def param_specs(cfg: MoeConfig) -> Params:
    """Expert weights shard over EP on the expert axis; within an expert
    the ffn shards like the dense model (fsdp x tp)."""
    layers = {
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
    }
    if cfg.qk_norm:
        # the norm runs over the whole projection, across tp's head shards
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    return {
        "embed": P(TP, FSDP),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(FSDP, TP),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def active_param_count(cfg: MoeConfig) -> int:
    """Params touched per token (the 'x7B' in 8x7B marketing math)."""
    total = param_count(cfg)
    expert = 3 * cfg.dim * cfg.ffn_dim * cfg.n_layers
    return total - expert * (cfg.n_held - cfg.experts_per_token)


# ---------------------------------------------------------------------------
# MoE block: route, sorted dispatch, grouped matmul, combine
# ---------------------------------------------------------------------------

def route(cfg: MoeConfig, router: jnp.ndarray, yt: jnp.ndarray,
          bias: Optional[jnp.ndarray] = None):
    """``yt (t, d)`` -> ``(probs (t, e), top_p (t, k), top_e (t, k))``,
    all float32 / int32. The router runs in float32 at full matmul
    precision whatever the activations' dtype: which expert is a token's
    8th and which its 9th hangs on differences bf16 does not hold.
    ``bias (e,)`` enters the choice only (``noaux_tc``): the weights are
    the chosen experts' own scores."""
    logits = jnp.dot(
        yt.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if cfg.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif cfg.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring={cfg.scoring!r}: softmax or sigmoid")
    if bias is None:
        top_p, top_e = lax.top_k(probs, cfg.experts_per_token)
    else:
        _, top_e = lax.top_k(
            probs + bias.astype(jnp.float32), cfg.experts_per_token)
        top_p = jnp.take_along_axis(probs, top_e, axis=1)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    if cfg.routed_scaling != 1.0:
        top_p = top_p * cfg.routed_scaling
    return probs, top_p, top_e


def sort_pairs(top_e: jnp.ndarray, n_groups: int, first: Any = 0):
    """Order the ``n = t * k`` (token, choice) pairs of ``top_e (t, k)``
    by expert. Pair ``i`` is token ``i // k``'s choice ``i % k``. Returns
    ``order (n,)``: the pair at each sorted row; ``inverse (n,)``: the
    sorted row of each pair; ``group_sizes (n_groups,)``: how many pairs
    chose each of the experts ``first .. first + n_groups - 1``. Pairs
    for any other expert (another ep rank's) sort into a tail past
    ``sum(group_sizes)``, which a grouped matmul leaves zero."""
    flat = top_e.reshape(-1) - first
    key = jnp.where((flat >= 0) & (flat < n_groups), flat, n_groups)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # the inverse of a permutation is its argsort: a second sort of
    # int32 keys, a sixth of what the scatter of n scalars took (v5e,
    # 98304 pairs: 0.08 against 0.45 ms, docs/design/kernels.md 1c)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(n_groups, dtype=key.dtype)[None, :],
        axis=0, dtype=jnp.int32,
    )
    return order, inverse, group_sizes


def _int_zeros(*arrays):
    return tuple(
        None if a is None else np.zeros(a.shape, jax.dtypes.float0)
        for a in arrays)


def _permute(to, values):
    """``values`` with element ``i`` moved to ``to[i]`` (``to`` a
    permutation): ``values[argsort(to)]`` in one two-operand sort."""
    return lax.sort((to, values), num_keys=1)[1]


def _row_blocks(rows, weights_shape, live, interpret: bool):
    """The row kernels' blocks for this call (``ops/moe_rows.py``), or
    None where XLA's gathers run: a call that names no live count has no
    tail to skip."""
    if live is None:
        return None
    t, k = weights_shape
    return moe_rows.row_blocks(t, k, rows.shape[1], rows.dtype,
                               interpret=interpret)


def dispatch_rows(yt, order, inverse, k: int, live=None, *,
                  whole: bool = False, interpret: bool = False):
    """``yt (t, d)`` -> ``(t * k, d)``: sorted row ``r`` is the token of
    pair ``order[r]`` (one gather of whole rows: XLA's runs at the HBM's
    rate). The backward gathers by ``inverse`` and sums a token's ``k``
    rows, where autodiff would scatter-add. ``live ()``: the sorted rows
    that any product reads, ``sum(group_sizes)``. Given it, on the TPU
    (or under ``interpret``) the forward gathers the rows below it, to
    the end of the block that holds row ``live``, and writes no other
    (the same rows bit for bit; ``whole``: every row all the same, by
    XLA's gather, where that is the faster), and the backward reads the
    cotangent's rows below it and no others (``ops/moe_rows.py``)."""
    blocks = _row_blocks(yt, (yt.shape[0], k), live, interpret)
    return _dispatch(yt, order, inverse, live, k, blocks, whole, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _dispatch(yt, order, inverse, live, k, blocks, whole, interpret):
    if blocks is None or whole:
        return yt[order // k]
    return moe_rows.gathered_rows(yt, order // k, live, block=blocks[0],
                                  interpret=interpret)


def _dispatch_fwd(yt, order, inverse, live, k, blocks, whole, interpret):
    return (_dispatch.fun(yt, order, inverse, live, k, blocks, whole,
                          interpret),
            (order, inverse, live))


def _dispatch_bwd(k, blocks, whole, interpret, res, g):
    order, inverse, live = res
    n, d = g.shape
    if blocks is None:
        d_yt = jnp.sum(
            g[inverse].reshape(n // k, k, d), axis=1, dtype=jnp.float32
        ).astype(g.dtype)
    else:
        # a custom_vjp's backward is traced outside the caller's scopes
        with trace.scope("moe_dispatch"):
            d_yt = moe_rows.token_sums(g, inverse, live, k, block=blocks[1],
                                       interpret=interpret)
    return (d_yt,) + _int_zeros(order, inverse, live)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def combine_rows(rows, weights, order, inverse, live=None, *,
                 interpret: bool = False):
    """``rows (t * k, d)`` in sorted order, ``weights (t, k)`` float32 ->
    ``(t, d)``: token ``i``'s output is the sum over its ``k`` pairs of
    weight x row. Forward and backward are gathers. ``live``: as
    `dispatch_rows`. Given it, on the TPU (or under ``interpret``) a
    pair whose sorted row is at or past it adds nothing and its row is
    never read; its weight's cotangent is exactly 0, and its row's is
    not written past the 256-row block that holds row ``live`` (nothing
    reads it: the grouped products' backward visits no tile there)."""
    blocks = _row_blocks(rows, weights.shape, live, interpret)
    return _combine(rows, weights, order, inverse, live, blocks, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine(rows, weights, order, inverse, live, blocks, interpret):
    t, k = weights.shape
    if blocks is None:
        picked = rows[inverse].reshape(t, k, rows.shape[1])
        return jnp.sum(
            picked.astype(jnp.float32) * weights[..., None], axis=1
        ).astype(rows.dtype)
    return moe_rows.token_sums(rows, inverse, live, k, block=blocks[1],
                               weights=weights.reshape(-1),
                               interpret=interpret)


def _combine_fwd(rows, weights, order, inverse, live, blocks, interpret):
    return (_combine.fun(rows, weights, order, inverse, live, blocks,
                         interpret),
            (rows, weights, order, inverse, live))


def _combine_bwd(blocks, interpret, res, g):
    rows, weights, order, inverse, live = res
    k = weights.shape[1]
    # both cotangents from one gather of g, in sorted order; the
    # weights' comes back to (t, k) as a permutation of t * k scalars
    if blocks is None:
        g_rows = g[order // k]
        d_rows = (
            g_rows.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
        ).astype(rows.dtype)
        d_weights = jnp.sum(
            rows.astype(jnp.float32) * g_rows.astype(jnp.float32), axis=1
        )[inverse]
    else:
        with trace.scope("moe_combine"):
            # a permutation of n scalars as a sort by the inverse
            # permutation: a gather of scalars runs an element at a time
            sorted_weights = _permute(inverse, weights.reshape(-1))
            d_rows, d_weights = moe_rows.sorted_cotangents(
                g, order // k, rows, sorted_weights, live, block=blocks[0],
                interpret=interpret)
            d_weights = jnp.where(
                inverse < live, _permute(order, d_weights), 0.0)
    return (d_rows, d_weights.reshape(weights.shape)) + _int_zeros(
        order, inverse, live)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route(cfg: MoeConfig, router, yt, token_axes=(), bias=None):
    """Route ``yt (t, d)``: ``(top_p, top_e, aux)``. ``aux`` is the
    load-balancing loss ``E * sum_i f_i P_i`` (1 when routing is
    uniform) over all the tokens of the step: inside a ``shard_map`` the
    counts and the mean probabilities are reduced over ``token_axes``."""
    e = cfg.n_experts
    with trace.scope("moe_route"):
        probs, top_p, top_e = route(cfg, router, yt, bias)
        counts = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.int32), (0, 1))
        mean_prob = probs.mean(axis=0)
        if token_axes:
            counts = lax.psum(counts, token_axes)
            mean_prob = lax.pmean(mean_prob, token_axes)
        fraction = counts.astype(jnp.float32) / jnp.sum(counts)
        aux = e * jnp.sum(fraction * mean_prob)
    return top_p, top_e, aux


_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def gated_rows(gate, up, act: str, live=None, *, interpret: bool = False):
    """``act(gate) x up`` of two ``(t * k, f)`` arrays in sorted order.
    ``live``: as `dispatch_rows`. Given it, on the TPU (or under
    ``interpret``) the pass and its backward read and write the rows
    below it and no others, to the end of the block that holds row
    ``live`` (zeros from ``live`` on), in float32 from the load to the
    one store (``ops/moe_rows.py``)."""
    blocks = _row_blocks(gate, (gate.shape[0], 1), live, interpret)
    if blocks is None:
        return _ACTS[act](gate) * up
    return _gated(gate, up, live, act, blocks[0], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated(gate, up, live, act, block, interpret):
    return moe_rows.gated_rows(gate, up, live, act=act, block=block,
                               interpret=interpret)


def _gated_fwd(gate, up, live, act, block, interpret):
    hidden = _gated.fun(gate, up, live, act, block, interpret)
    return hidden, (gate, up, live)


def _gated_bwd(act, block, interpret, res, g):
    gate, up, live = res
    with trace.scope("moe_experts"):
        d_gate, d_up = moe_rows.gated_rows_cotangents(
            gate, up, g, live, act=act, block=block, interpret=interpret)
    return (d_gate, d_up) + _int_zeros(live)


_gated.defvjp(_gated_fwd, _gated_bwd)


def _experts(lp: Params, yt, top_p, top_e, n_local: int, first: Any = 0,
             act: str = "silu", tail: bool = False, *,
             share: Optional[float] = None, interpret: bool = False):
    """The ``n_local`` experts ``first ..`` applied to the pairs of
    ``yt (t, d)`` that chose them, weighted and summed: ``(t, d)``.
    ``tail``: the router scores experts that are not among them, so
    pairs can sort past ``live = sum(group_sizes)``. Nothing between
    `dispatch_rows`' gather and the result then touches a row at or past
    that count: ``act(gate) x up`` and its backward stop at the block
    that holds row ``live``, the grouped products walk no tile of the
    tail, forward or backward (``tail_unread``), and combine, its
    backward and dispatch's backward read below it
    (``ops/moe_rows.py``); what lies past it in every ``(t * k, .)``
    array but the gathered rows is unwritten memory. The gather stops at
    that block too where that is the faster (``share``: the held
    experts' share of the router's, which `moe_rows.gather_pays` weighs
    with the tokens' size; None: it stops); the gathered rows are then
    no residual of gate's and up's products, whose backward gathers the
    live rows again. ``lax.ragged_dot``, where a shape falls back to it,
    would read unwritten rows, so all of this runs only where the three
    grouped products tile. Without a tail every row is live and XLA's
    ops, which move a row without the seven beside it, are the faster
    (docs/design/kernels.md 1c). ``interpret`` runs every kernel in
    interpreter mode (the CPU tests)."""
    t, k = top_e.shape
    d, f = lp["w_gate"].shape[1:]
    blocks = (tail and choose_tiles(t * k, d, f, yt.dtype)
              and choose_tiles(t * k, f, d, yt.dtype)
              and moe_rows.row_blocks(t, k, d, yt.dtype,
                                      interpret=interpret))
    bounded = bool(blocks) and (
        share is None or moe_rows.gather_pays(t, d, yt.dtype, share))
    trace.gauge("moe.rows_kernel", int(bool(blocks)))
    trace.gauge("moe.dispatch_bounded", int(bounded))
    trace.gauge("moe.row_block", blocks[0] if blocks else 0)
    with trace.scope("moe_dispatch"):
        order, inverse, group_sizes = sort_pairs(top_e, n_local, first)
        live = jnp.sum(group_sizes) if blocks else None
    products = functools.partial(
        grouped_matmuls, group_sizes=group_sizes,
        tail_unread=bool(blocks), interpret=interpret)

    def gate_and_up(yt, w_gate, w_up):
        with trace.scope("moe_dispatch"):
            xs = dispatch_rows(yt, order, inverse, k, live,
                               whole=not bounded, interpret=interpret)
        with trace.scope("moe_experts"):
            return products(xs, (w_gate, w_up))

    # the gathered rows are the products' residual, (t k, d) whatever the
    # count. XLA gathers every row a second time by itself where a step
    # does not fit with them kept (granite's); a kernel it cannot repeat,
    # so the backward is told to gather the live rows again, and gate's
    # and up's cotangents wait for down's d-rhs (a barrier's transpose is
    # a barrier), or the scheduler holds combine's d_rows across them
    # (PERF.md section 6, PR 53)
    gate, up = stack.recompute(gate_and_up, bounded)(
        yt, lp["w_gate"], lp["w_up"])
    w_down = lp["w_down"]
    if bounded:
        gate, up, w_down = lax.optimization_barrier((gate, up, w_down))
    with trace.scope("moe_experts"):
        rows, = products(
            gated_rows(gate, up, act, live, interpret=interpret), (w_down,))
    with trace.scope("moe_combine"):
        return combine_rows(rows, top_p, order, inverse, live,
                            interpret=interpret)


def _moe_tokens_sharded(cfg: MoeConfig, lp: Params, y, route_on=None):
    """The body of ``moe_mlp``'s ``shard_map``: this device's tokens
    ``y (b, s, d)`` (and, where the router reads another tensor, that:
    ``route_on``), its ``e / ep`` experts at ``1 / tp`` of their width.
    Rows, choices and weights are gathered over ep; every rank computes
    the pairs that chose its experts for all of the group's tokens and
    the partial outputs are summed back to their owners. An axis of size
    one makes its collective a no-op, so one body serves every mesh."""
    b, s, d = y.shape
    e_local = lp["w_gate"].shape[0]
    yt = y.reshape(b * s, d)
    top_p, top_e, aux = _route(
        cfg, lp["router"], yt if route_on is None else route_on.reshape(
            b * s, d), BATCH_AXES + (SP,), lp.get("router_bias"))
    yt, top_p, top_e = (
        lax.all_gather(a, EP, axis=0, tiled=True) for a in (yt, top_p, top_e)
    )
    # each tp rank holds a slice of every expert's width, so what comes
    # back for the rows and the weights is a partial sum over tp: say
    # so, and autodiff closes them with a psum (dispatch and combine are
    # custom_vjps, which get no such help by themselves)
    yt, top_p = (lax.pcast(a, TP, to="varying") for a in (yt, top_p))
    first = cfg.first_expert + lax.axis_index(EP) * e_local
    # XLA's gathers over every row here, tail or no tail: this
    # shard_map checks how values vary over the mesh (tp's psum hangs
    # on it), which puts a `pvary` into a kernel's body that Mosaic
    # does not lower (the grouped kernels meet the same wall)
    out = _experts(lp, yt, top_p, top_e, e_local, first, cfg.expert_act)
    out = lax.psum_scatter(out, EP, scatter_dimension=0, tiled=True)
    return lax.psum(out, TP).reshape(b, s, d), aux


def _shared_expert(lp: Params, y: jnp.ndarray) -> jnp.ndarray:
    """The always-on expert: the dense SwiGLU of ``models/llama.py`` on
    every token, partitioned as any dense matmul is; where the layer has
    ``w_s (d, 1)``, times a gate a token, ``sigmoid(y w_s)``."""
    trace.gauge("moe.shared_gate", int("w_s" in lp))
    with trace.scope("moe_shared"):
        out = llama.swiglu(
            y, lp["ws_gate"], lp["ws_up"], lp["ws_down"], y.dtype)
        if "w_s" in lp:
            out = out * jax.nn.sigmoid(y @ lp["w_s"].astype(y.dtype))
        return out


def moe_mlp(
    cfg: MoeConfig, lp: Params, y: jnp.ndarray, mesh: Optional[Mesh] = None,
    route_on: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, S, D) -> (out (B, S, D), aux_loss scalar). ``route_on (B, S,
    D)``: the tensor the router reads where that is not ``y``."""
    b, s, d = y.shape
    if cfg.expert_act not in _ACTS:
        raise ValueError(
            f"expert_act={cfg.expert_act!r}: one of {sorted(_ACTS)}")
    _report_shapes(cfg, b * s, "ws_gate" in lp, route_on is not None)
    # beside y where it is another tensor; else y alone, as it was
    routed = () if route_on is None else (route_on,)
    if mesh is None or mesh.size == 1:
        yt = y.reshape(b * s, d)
        top_p, top_e, aux = _route(
            cfg, lp["router"],
            yt if route_on is None else route_on.reshape(b * s, d),
            bias=lp.get("router_bias"))
        out = _experts(lp, yt, top_p, top_e, cfg.n_held, cfg.first_expert,
                       cfg.expert_act, cfg.n_held < cfg.n_experts,
                       share=cfg.n_held / cfg.n_experts)
        out = out.reshape(b, s, d)
    else:
        specs = {"router": P(None, None), "w_gate": P(EP, None, TP),
                 "w_up": P(EP, None, TP), "w_down": P(EP, TP, None)}
        if "router_bias" in lp:
            specs["router_bias"] = P(None)
        sharded = shard_map(
            functools.partial(_moe_tokens_sharded, cfg),
            mesh=mesh,
            # fsdp's shards of the weights are gathered on the way in
            in_specs=(specs,) + (P(BATCH_AXES, SP, None),) * (
                1 + len(routed)),
            out_specs=(P(BATCH_AXES, SP, None), P()),
        )
        out, aux = sharded({k: lp[k] for k in specs}, y, *routed)
    if "ws_gate" in lp:
        out = out + _shared_expert(lp, y)
    return out, aux


def _report_shapes(cfg: MoeConfig, tokens: int, shared: bool = False,
                   route_on: bool = False):
    """The gauges that say what the expert layer of this build is given
    (set while the step is traced, as ``attn.block_q`` is).
    ``moe.rows_held`` is what uniform routing sends the held experts of
    a layer: the step's own count hangs on the router. ``moe.route_on``:
    1 where the router reads a tensor handed in beside the experts'
    input (the attention's input), 0 where it reads that input;
    ``moe.act``: the gate's activation, 0 silu, 1 relu."""
    k, e = cfg.experts_per_token, cfg.n_experts
    trace.gauge("moe.experts", e)
    trace.gauge("moe.top_k", k)
    trace.gauge("moe.rows_per_expert", tokens * k / e)
    trace.gauge("moe.experts_held", cfg.n_held)
    trace.gauge("moe.rows_held", tokens * k * cfg.n_held / e)
    trace.gauge("moe.tail_rows", tokens * k * (e - cfg.n_held) / e)
    trace.gauge("moe.shared_experts", int(shared))
    trace.gauge("moe.route_on", int(route_on))
    trace.gauge("moe.act", list(_ACTS).index(cfg.expert_act))


def _decoder_layer(cfg: MoeConfig, mesh, inv_freq, positions, lp, x):
    dt = cfg.dtype
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with trace.scope("attn_proj"):
        q = y @ lp["wq"].astype(dt)
        k = y @ lp["wk"].astype(dt)
        v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
        if cfg.qk_norm:
            # a norm's backward reads its input: q and k are named (and
            # kept, `forward_hidden`) where the backward's reads begin
            q, k, v = llama.name_qkv(q, k, v)
            # over the whole projection, before the split into heads
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, kvh, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        if not cfg.qk_norm:
            q, k, v = llama.name_qkv(q, k, v)
    attn = llama._attention(cfg.as_llama(), mesh, q, k, v).reshape(b, s, h * hd)
    with trace.scope("attn_proj"):
        attn = attn @ lp["wo"].astype(dt)
    x = x + attn

    with trace.scope("norm"):
        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    moe_out, aux = moe_mlp(cfg, lp, y, mesh)
    x = x + moe_out

    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, SP, None))
        )
    return x, aux


def validate_for_mesh(
    cfg: MoeConfig, mesh: Mesh, seq_len: int = 0, batch: int = 0
) -> None:
    llama.validate_for_mesh(cfg.as_llama(), mesh, seq_len)
    shape = dict(mesh.shape)
    shards = math.prod(shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        raise ValueError(
            f"batch={batch} does not divide over the mesh's {shards} data "
            f"shards (dp x fsdp x ep): the expert layer runs on each "
            f"shard's own tokens under shard_map"
        )
    ep, tp = shape.get(EP, 1), shape.get(TP, 1)
    if cfg.n_held % max(1, ep):
        raise ValueError(
            f"n_experts={cfg.n_held} (held) not divisible by mesh ep={ep}"
        )
    if cfg.ffn_dim % max(1, tp):
        raise ValueError(
            f"ffn_dim={cfg.ffn_dim} not divisible by mesh tp={tp}"
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
        validate_for_mesh(cfg, mesh, seq_len=s, batch=b)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    # recomputed, but for what the attention's backward reads
    layer_fn = llama._maybe_remat(
        cfg.as_llama(),
        functools.partial(_decoder_layer, cfg, mesh, inv_freq, positions))

    def scan_body(carry, lp):
        x, aux_sum = carry
        x, aux = layer_fn(lp, x)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"]
    )
    with trace.scope("norm"):
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
    return llama.unembed(x, params["lm_head"]), aux


def loss_fn(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoeConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Next-token CE + router aux loss (pad tokens < 0 ignored). The
    head runs as models/llama.py runs it: operands in the dtype they
    arrive in, f32 accumulation, the fused-CE kernel on the TPU."""
    x, aux = forward_hidden(params, tokens, cfg, mesh)
    ce = stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
    return ce + cfg.router_aux_coef * aux
