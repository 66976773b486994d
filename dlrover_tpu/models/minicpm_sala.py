"""The ``minicpm_sala`` decoder family (MiniCPM-SALA 9B): dense SwiGLU
layers whose mixer is one of two, by a per-layer list that is not
periodic (``mixer_types``: 8 ``minicpm4`` to 24 ``lightning-attn``), under
MiniCPM's scalings.

What is this family's own (``x`` a layer's input after its RMSNorm,
``d`` = 128 a head, ``r = scale_depth / sqrt(published layers)``)::

    h_0 = scale_emb E[token]
    h  <- h + r Mixer(Norm(h));   h <- h + r SwiGLU(Norm(h))
    logits = W_head (Norm(h_L) / (hidden / dim_model_base))

- **``lightning-attn``** (kind ``"L"``, ``lightning_layer``): ``q, k, v = x
  W_q, x W_k, x W_v`` as ``h`` heads of ``d``; an RMSNorm a head on q and
  k; rotary on the whole head; ``q / sqrt(d)``; linear attention with a
  decay that is a constant of the head (``ops/lightning.py``: ``S_t =
  exp(-s) S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``; the slopes ``s`` are
  numbers of the config, a row a lightning layer, never trained); then
  ``(RMSNorm_d(o) w sigmoid(x W_g)) W_o`` (``ops/kda.py norm_gate``, the
  KDA layer's tail as it stands). Scopes ``la_proj``, ``la_norm_rope``,
  ``la_chunk``, ``la_out``.
- **``minicpm4``** (kind ``"S"``, ``sparse_layer``): ``h`` query heads on
  ``kvh`` key heads, an RMSNorm a head on q and k, no rotary, ``(o
  sigmoid(x W_g)) W_o``. Up to ``dense_len`` positions causal attention;
  past it every query's key-value group picks ``blk_topk`` blocks of
  ``blk_size`` keys from keys pooled over windows (``ops/blocksel.py``)
  and the flash kernels attend over the chosen blocks' causal keys
  (``ops/attention.py`` ``select_block=``). The choice is under
  ``stop_gradient``. Scopes ``attn_proj`` (projections, norms, ``W_o``),
  ``blk_pool``, ``blk_score``, ``blk_pick``, ``sattn_gate``.
- the feed-forward is ``llama.swiglu`` under the scope ``dense_mlp``.

The layers are laid out by ``stack.runs``: each run of like layers is one
stacked tree (``params["runs"]``) under one scan. A block is recomputed
whole in the backward pass but for what `_block_fn` names.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import stack
from dlrover_tpu.models.llama import swiglu
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    attention,
    blocksel,
    embed_lookup,
    flash_attention,
    kda,
    lightning,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, FSDP, PP, SP, TP

Params = Dict[str, Any]

KINDS = {"minicpm4": "S", "lightning-attn": "L"}

#: the name a sparse block's checkpoint keeps its choice of blocks by
SELECT = "blk_select"

_PUBLISHED_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))


def lightning_slopes(heads: int, layer: int, published_layers: int
                     ) -> Tuple[float, ...]:
    """The lightning-attention family's slopes (MiniMax-01's, and
    flash-linear-attention's ``LightningAttention``): ``2^(-8 (h + 1) /
    heads) (1 - layer / (layers - 1) + 1e-5)`` for head ``h`` of published
    layer ``layer``. A configuration states the numbers; this is where a
    tiny one gets them."""
    depth = 1.0 - layer / max(published_layers - 1, 1) + 1e-5
    return tuple(2.0 ** (-8.0 * (h + 1) / heads) * depth
                 for h in range(heads))


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    """openbmb/MiniCPM-SALA's config.json by default; the selection's
    sizes are MiniCPM4's published ``sparse_config``."""
    vocab_size: int = 73448
    dim: int = 4096
    ffn_dim: int = 16384
    #: the mixer of each layer held, first to last
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    #: the depth the residual scale is taken at, and the published index
    #: of the first layer held
    published_layers: int = 32
    # minicpm4
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    blk_kernel: int = 32
    blk_stride: int = 16
    blk_size: int = 64
    blk_topk: int = 64
    blk_init: int = 1
    blk_window: int = 2048
    dense_len: int = 8192
    # lightning-attn
    la_heads: int = 32
    la_head_dim: int = 128
    la_chunk: int = 256
    #: a row of ``la_heads`` slopes for each lightning layer held, in order
    la_slopes: Optional[Tuple[Tuple[float, ...], ...]] = None
    rope_theta: float = 1e4
    # MiniCPM's scalings
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    norm_eps: float = 1e-6
    init_std: float = 0.02
    out_proj_std: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        unknown = set(self.mixer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"mixer_types: {sorted(unknown)} is none of "
                             f"{sorted(KINDS)}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads do not group over "
                             f"{self.n_kv_heads} key heads")
        slopes = self.slopes
        if len(slopes) != self.kinds.count("L") or any(
                len(row) != self.la_heads or min(row) < 0 for row in slopes):
            raise ValueError(
                f"la_slopes: {self.kinds.count('L')} lightning layers of "
                f"{self.la_heads} heads want as many rows of as many slopes, "
                "none negative")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"S"`` (sparse, ``minicpm4``) or ``"L"`` (``lightning-attn``)
        a layer."""
        return tuple(KINDS[m] for m in self.mixer_types)

    @property
    def pattern_string(self) -> str:
        return "".join(self.kinds)

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """Each run of like layers, a stacked part."""
        return stack.runs(self.kinds)

    @property
    def slopes(self) -> Tuple[Tuple[float, ...], ...]:
        """A row a lightning layer; the family's formula by the layer's
        own index where the config states none."""
        if self.la_slopes is not None:
            return tuple(tuple(row) for row in self.la_slopes)
        return tuple(
            lightning_slopes(self.la_heads, i, self.published_layers)
            for i, kind in enumerate(self.kinds) if kind == "L")

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def head_divisor(self) -> float:
        return self.dim / self.dim_model_base

    def sparse_at(self, seq: int) -> bool:
        """Whether a sequence of ``seq`` takes the sparse branch."""
        return seq > self.dense_len

    @staticmethod
    def tiny(**kw) -> "MiniCPMSalaConfig":
        base = dict(
            vocab_size=256, dim=64, ffn_dim=128,
            mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                         "minicpm4"),
            published_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
            blk_kernel=4, blk_stride=2, blk_size=8, blk_topk=4, blk_init=1,
            blk_window=12, dense_len=32, la_heads=4, la_head_dim=16,
            la_chunk=16, dim_model_base=16, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return MiniCPMSalaConfig(**base)


def run_name(i: int) -> str:
    """The key of run ``i`` in ``params["runs"]``."""
    return f"run{i:02d}"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: MiniCPMSalaConfig, kind: str) -> Dict[str, Tuple]:
    """``{name: (shape, init, spec)}`` of one block. ``init`` is "normal",
    "out" (a projection that closes a residual branch) or "ones"; ``spec``
    the partition of the leaf's own axes: a matrix shards its model-width
    side over fsdp, the rest is replicated."""
    D, F = cfg.dim, cfg.ffn_dim
    rows, cols = (FSDP, None), (None, FSDP)
    if kind == "L":
        h, kvh, hd = cfg.la_heads, cfg.la_heads, cfg.la_head_dim
    else:
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes = {
        "attn_norm": ((D,), "ones", (None,)),
        "mlp_norm": ((D,), "ones", (None,)),
        "w_q": ((D, h * hd), "normal", rows),
        "w_k": ((D, kvh * hd), "normal", rows),
        "w_v": ((D, kvh * hd), "normal", rows),
        "w_g": ((D, h * hd), "normal", rows),       # the output gate
        "q_norm": ((hd,), "ones", (None,)),
        "k_norm": ((hd,), "ones", (None,)),
        "w_o": ((h * hd, D), "out", cols),
        "w_gate": ((D, F), "normal", rows),
        "w_up": ((D, F), "normal", rows),
        "w_down": ((F, D), "out", cols),
    }
    if kind == "L":
        shapes["o_norm"] = ((hd,), "ones", (None,))
    return shapes


def _init_slab(cfg: MiniCPMSalaConfig, key, kind: str, rows: int) -> Params:
    shapes = _block_shapes(cfg, kind)

    def leaf(key, shape, rule):
        if rule == "ones":
            return jnp.ones(shape, jnp.float32)
        std = (cfg.init_std if rule == "normal" or cfg.out_proj_std is None
               else cfg.out_proj_std)
        return jax.random.normal(key, shape, jnp.float32) * std

    return {
        name: leaf(k, (rows,) + shape, rule).astype(cfg.param_dtype)
        for k, (name, (shape, rule, _)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))
    }


def init_params(cfg: MiniCPMSalaConfig, rng: jax.Array) -> Params:
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, k_head, k_runs = jax.random.split(rng, 3)

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * cfg.init_std).astype(pd)

    return {
        "embed": normal(k_embed, (V, D)),
        "runs": {
            run_name(i): _init_slab(cfg, k, part.kinds[0], part.repeats)
            for i, (k, part) in enumerate(zip(
                jax.random.split(k_runs, len(cfg.layout)), cfg.layout))
        },
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(k_head, (D, V)),
    }


def param_specs(cfg: MiniCPMSalaConfig) -> Params:
    """Data parallelism only (``validate_for_mesh``). The leading axis of
    a run's leaves is the run."""
    return {
        "embed": P(None, FSDP),
        "runs": {
            run_name(i): {
                name: P(None, *spec) for name, (_, _, spec)
                in _block_shapes(cfg, part.kinds[0]).items()
            }
            for i, part in enumerate(cfg.layout)
        },
        "final_norm": P(None),
        "lm_head": P(FSDP, None),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(cfg: MiniCPMSalaConfig, params: Params):
    """``params``' runs as the layout's parts take them, a lightning run
    with its layers' slopes beside its leaves (numbers of the config, a
    row a layer: the scan hands each layer its own)."""
    rows = iter(cfg.slopes)
    trees = []
    for i, part in enumerate(cfg.layout):
        tree = params["runs"][run_name(i)]
        if part.kinds[0] == "L":
            tree = {**tree, "slopes": jnp.asarray(
                [next(rows) for _ in range(part.repeats)], jnp.float32)}
        trees.append((tree,))
    return trees


def layer_params(cfg: MiniCPMSalaConfig, params: Params, layer: int
                 ) -> Params:
    """Layer ``layer``'s own leaves (a lightning layer's with its
    ``slopes``)."""
    return stack.layer_params(cfg.layout, _trees(cfg, params), layer)


def validate_for_mesh(cfg: MiniCPMSalaConfig, mesh: Mesh, batch: int = 0
                      ) -> None:
    shape = dict(mesh.shape)
    why = {
        SP: "a lightning layer's recurrent state is not handed across "
            "ranks, and a sparse layer's queries choose among every block "
            "of the sequence",
        TP: "a key-value group's choice sums its own sixteen heads and "
            "the lightning kernels take whole heads: none is split",
        PP: "the layers are walked as runs on one device",
    }
    for axis in (SP, TP, PP):
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"minicpm_sala: mesh {axis}={shape[axis]}: {why[axis]} "
                "(dp and fsdp only)")
    shards = math.prod(shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        raise ValueError(
            f"batch={batch} does not divide over the mesh's {shards} data "
            "shards (dp x fsdp x ep)")


# ---------------------------------------------------------------------------
# The mixers, the block, the forward
# ---------------------------------------------------------------------------

def _heads(cfg, lp, y, h: int, kvh: int, hd: int):
    """``y`` -> q ``(b, s, h, hd)``, k, v ``(b, s, kvh, hd)``, q and k
    normed a head, and the gate's logits ``(b, s, h, hd)``."""
    dt = cfg.dtype
    b, s, _ = y.shape
    q = (y @ lp["w_q"].astype(dt)).reshape(b, s, h, hd)
    k = (y @ lp["w_k"].astype(dt)).reshape(b, s, kvh, hd)
    v = (y @ lp["w_v"].astype(dt)).reshape(b, s, kvh, hd)
    gate = (y @ lp["w_g"].astype(dt)).reshape(b, s, h, hd)
    return q, k, v, gate


def lightning_layer(cfg: MiniCPMSalaConfig, lp: Params, y, mesh=None,
                    interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> the lightning sublayer's output
    before the residual. ``lp["slopes"] (la_heads,)``: the layer's."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = y.shape
    h, hd = cfg.la_heads, cfg.la_head_dim
    with trace.scope("la_proj"):
        q, k, v, gate = _heads(cfg, lp, y, h, h, hd)
    with trace.scope("la_norm_rope"):
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        inv_freq = rope_frequencies(hd, cfg.rope_theta)
        q = apply_rope(rms_norm(q, lp["q_norm"], cfg.norm_eps), positions,
                       inv_freq)
        k = apply_rope(rms_norm(k, lp["k_norm"], cfg.norm_eps), positions,
                       inv_freq)
        q = (q.astype(f32) * hd ** -0.5).astype(dt)
    with trace.scope("la_chunk"):
        o = lightning.lightning_attention(
            q, k, v, lp["slopes"], chunk=min(cfg.la_chunk, s),
            interpret=interpret, mesh=mesh)
    with trace.scope("la_out"):
        o = kda.norm_gate(o, gate, lp["o_norm"], cfg.norm_eps,
                          act="sigmoid", scope="la_out", interpret=interpret,
                          mesh=mesh)
    with trace.scope("la_proj"):
        return o @ lp["w_o"].astype(dt)


def score_blocks(cfg: MiniCPMSalaConfig, q, k, mesh=None,
                 interpret: bool = False):
    """``q (b, s, h, hd)``, ``k (b, s, kvh, hd)`` as the softmax reads
    them -> the block scores ``(b, kvh, s, s / blk_size)`` float32."""
    with trace.scope("blk_pool"):
        pooled = blocksel.pooled_keys(k, cfg.blk_kernel, cfg.blk_stride)
    with trace.scope("blk_score"):
        return blocksel.block_scores(
            q, pooled, block=cfg.blk_size, kernel=cfg.blk_kernel,
            stride=cfg.blk_stride, scale=cfg.head_dim ** -0.5,
            interpret=interpret, mesh=mesh)


def choose_blocks(cfg: MiniCPMSalaConfig, q, k, mesh=None,
                  interpret: bool = False):
    """The int8 ``(b, kvh, s, s / blk_size)`` choice of blocks from
    `score_blocks`, no gradient through it."""
    scores = score_blocks(cfg, lax.stop_gradient(q), lax.stop_gradient(k),
                          mesh, interpret)
    with trace.scope("blk_pick"):
        return blocksel.pick_blocks(
            scores, block=cfg.blk_size, topk=cfg.blk_topk,
            init_blocks=cfg.blk_init, window=cfg.blk_window)


def sparse_operands(cfg: MiniCPMSalaConfig, lp: Params, y):
    """``y``, pre-normed -> q and k normed a head, v, and the gate's
    logits of a ``minicpm4`` layer."""
    q, k, v, gate = _heads(cfg, lp, y, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim)
    return (rms_norm(q, lp["q_norm"], cfg.norm_eps),
            rms_norm(k, lp["k_norm"], cfg.norm_eps), v, gate)


def sparse_layer(cfg: MiniCPMSalaConfig, lp: Params, y, mesh=None,
                 interpret: bool = False):
    """``y (b, s, d)``, pre-normed -> the ``minicpm4`` sublayer's output
    before the residual."""
    dt, f32 = cfg.dtype, jnp.float32
    b, s, _ = y.shape
    with trace.scope("attn_proj"):
        q, k, v, gate = sparse_operands(cfg, lp, y)
    if cfg.sparse_at(s):
        select = checkpoint_name(
            choose_blocks(cfg, q, k, mesh, interpret), SELECT)
        out = flash_attention(q, k, v, causal=True, mesh=mesh, select=select,
                              select_block=cfg.blk_size, interpret=interpret)
    else:
        out = flash_attention(q, k, v, causal=True, mesh=mesh,
                              interpret=interpret)
    with trace.scope("sattn_gate"):
        out = (out.astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(dt)
    with trace.scope("attn_proj"):
        return out.reshape(b, s, -1) @ lp["w_o"].astype(dt)


def mixed(cfg: MiniCPMSalaConfig, mesh, kind: str, lp: Params, x):
    """``x + r Mixer(Norm(x))`` for the layer's kind."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    mixer = lightning_layer if kind == "L" else sparse_layer
    return x + (cfg.residual_scale * mixer(cfg, lp, y, mesh=mesh)
                ).astype(x.dtype)


def block(cfg: MiniCPMSalaConfig, mesh, kind: str, lp: Params, x):
    """One layer -> the residual after it."""
    x = mixed(cfg, mesh, kind, lp, x)
    with trace.scope("norm"):
        u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    with trace.scope("dense_mlp"):
        out = swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)
    x = x + (cfg.residual_scale * out).astype(x.dtype)
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def _report_shapes(cfg: MiniCPMSalaConfig, seq: int):
    """The gauges that say what this build's layers are (set while the
    step is traced); the pattern is a text."""
    slopes = [s for row in cfg.slopes for s in row] or [0.0]
    sparse = cfg.sparse_at(seq)
    trace.gauge("la.heads", cfg.la_heads)
    trace.gauge("la.slope_min", min(slopes))
    trace.gauge("la.slope_max", max(slopes))
    trace.gauge("la.state_kept", 0)   # 1 once a block keeps them
    trace.gauge("attn.blk_size", cfg.blk_size)
    trace.gauge("attn.blk_topk", cfg.blk_topk)
    trace.gauge("attn.blk_forced",
                cfg.blk_init + cfg.blk_window // cfg.blk_size + 1)
    trace.gauge("attn.blk_dense_len", cfg.dense_len)
    trace.gauge("attn.blk_sparse", int(sparse))
    trace.gauge("attn.blk_pairs_share", blocksel.selected_pairs(
        seq, cfg.blk_size, cfg.blk_topk if sparse else seq
    ) / (seq * (seq + 1) // 2))
    trace.gauge("attn.group", cfg.n_heads // cfg.n_kv_heads)
    trace.gauge("attn.out_kept", 0)   # 1 once a block keeps one
    trace.gauge("layers.sparse", cfg.kinds.count("S"))
    trace.gauge("layers.lightning", cfg.kinds.count("L"))
    trace.gauge("mup.residual_scale", cfg.residual_scale)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: MiniCPMSalaConfig, mesh, kind: str):
    """A block is recomputed whole in the backward pass, but for what it
    names. A sparse block keeps its choice of blocks (8 MiB a layer at
    16384 positions: the pooling, the scoring kernel and the threshold
    run once a step) and the flash forward's output and ``lse``
    (``attention.KEPT``, 130 MiB: the ``_blk`` forward runs once). A
    lightning block keeps the rule's output and states
    (``lightning.KEPT``, 128 + 128 MiB a layer at 16384 positions and a
    chunk of 256): ``lightning_fwd`` runs once a step. The cell's step
    plans 13.43 GiB of 15.75 without them (PERF.md section 6)."""
    if kind == "S":
        keep, kept = (SELECT,) + attention.KEPT, attention.report_kept
    else:
        keep, kept = lightning.KEPT, lightning.report_kept
    return stack.recompute(functools.partial(block, cfg, mesh, kind),
                           cfg.remat, keep, kept)


def _embed(cfg: MiniCPMSalaConfig, params: Params, tokens, mesh):
    """``scale_emb E[token]`` in the activation dtype."""
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    return (x.astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: MiniCPMSalaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm: ``(b, s,
    dim)``. One scan a run of like layers."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg, tokens.shape[1])
    x = _embed(cfg, params, tokens, mesh)
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in set(cfg.kinds)}
    return stack.walk(x, cfg.layout, _trees(cfg, params),
                      lambda kind, lp, x: (fns[kind](lp, x), None))[0]


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: MiniCPMSalaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per sparse layer, first to last, the causal (q tile, k tile) visits
    of the ``_blk`` forward's own tiles in which any row of any group
    chose any block: what a visit list could skip is the causal total
    less this. A forward of its own beside the step, which has no output
    but the loss. ``(sparse layers,)`` int32; a sequence that takes the
    dense branch reads the causal total."""
    s = tokens.shape[1]
    bq, bk = attention.flash_tiles(
        s, s, cfg.head_dim, cfg.n_heads // cfg.n_kv_heads, cfg.dtype)["fwd"]
    last = max(i for i, kind in enumerate(cfg.kinds) if kind == "S")
    x = _embed(cfg, params, tokens, mesh)
    counts = []
    for layer, kind in enumerate(cfg.kinds[:last + 1]):
        lp = layer_params(cfg, params, layer)
        if kind == "S":
            q, k, _, _ = sparse_operands(
                cfg, lp, rms_norm(x, lp["attn_norm"], cfg.norm_eps))
            select = (choose_blocks(cfg, q, k, mesh) if cfg.sparse_at(s)
                      else blocksel.forced_blocks(
                          s, cfg.blk_size, 0, 0)[0][None, None])
            counts.append(blocksel.live_tiles(select, cfg.blk_size, bq, bk))
        if layer < last:        # nothing reads what follows the last choice
            x = block(cfg, mesh, kind, lp, x)
    return jnp.stack(counts)


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: MiniCPMSalaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored)."""
    x = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        x = (x.astype(jnp.float32) / cfg.head_divisor).astype(cfg.dtype)
    return stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
