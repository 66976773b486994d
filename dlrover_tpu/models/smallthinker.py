"""The ``smallthinker`` decoder family (SmallThinker-21BA3B-Instruct):
full-attention layers without rotary and sliding-window layers with it
in one period, grouped-query attention whose head width is stated, a
router that reads the attention's input, and ReGLU experts.

Every piece another family has is that family's: the parameters, their
partition and the expert path are ``models/moe.py``'s (this file hands
it a ``MoeConfig`` view with ``expert_act="relu"`` and a stated head
width, and the tensor to route on), attention is ``ops/attention.py``'s
flash kernels (here with ``window=``), rotary, norms, the embedding and
the fused cross-entropy are the shared ops.

What is this family's own:

- **the layer layout**: the config's two per-layer lists, ``rope_layout``
  (1: rotary on q and k) and ``window_layout`` (1: the layer sees
  ``window`` positions), give each layer's *kind*, static to the
  kernels. The layout's shortest period divides the depth (published:
  ``F W W W`` thirteen times), the layers' parameters are stacked **a
  position of the period** (``params["layers"]["pos0"]`` holds layers 0,
  4, 8, ..), and the forward is **one scan over the periods**
  (``stack.walk``) whose body is the period's blocks, each with its own
  kind and its own slab: 52 layers are one loop over four blocks, a kind is
  never a ``lax.cond`` that builds both. (Runs of like layers, as
  ``KimiLinearConfig`` makes them, would be 26 loops at the published
  depth over the same two bodies. One slab of all the layers, split
  inside the loop's body, was tried and dropped: the gradient of each
  split is a zero-padded slab, 8 GiB of temporaries at depth 8.)
- **the block**, ``y = RMSNorm(x)``; the router reads ``y``; attention on
  ``y`` (rotary or none, window or none); ``u = RMSNorm(x')``; the
  experts the router chose *from y* are applied to ``u``::

      x = x + Attn(y) W_o
      x = x + sum_j p_j W_down_j (relu(W_gate_j u) * (W_up_j u))

  so the router's gradient reaches ``attn_norm`` and the residual
  before attention, not ``mlp_norm``.
- no auxiliary loss (the config carries no coefficient) and no second
  kind of expert (it declares none).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import moe, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    attention as attn_ops,
    embed_lookup,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, SP

Params = Dict[str, Any]

_PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """PowerInfer/SmallThinker-21BA3B-Instruct's config.json by default."""
    vocab_size: int = 151936
    dim: int = 2560
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    ffn_dim: int = 768               # moe_ffn_hidden_size
    n_experts: int = 64              # the router's width
    experts_per_token: int = 6
    norm_topk_prob: bool = True
    rope_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    window_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    window: int = 4096               # sliding_window_size
    rope_theta: float = 1.5e6
    max_seq_len: int = 16384         # max_position_embeddings
    norm_eps: float = 1e-6
    # one chip's share of an expert-parallel job: see MoeConfig
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        for name in ("rope_layout", "window_layout"):
            layout = getattr(self, name)
            if len(layout) != self.n_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} {layout} does not give each of the "
                    f"{self.n_layers} layers a 0 or a 1")

    @property
    def kinds(self) -> Tuple[Tuple[bool, Optional[int]], ...]:
        """``(rotary, window or None)`` of each layer, first to last."""
        return tuple(
            (bool(r), self.window if w else None)
            for r, w in zip(self.rope_layout, self.window_layout))

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """One stacked part: the shortest period that divides the depth."""
        return stack.periodic(self.kinds, whole=True)

    @property
    def period(self) -> int:
        return len(self.layout[0].kinds)

    @property
    def pattern_string(self) -> str:
        """A letter a layer: F full attention without rotary, W window
        with rotary (the published two); R full with rotary, V window
        without."""
        return "".join(
            "FRVW"[int(r) + 2 * (w is not None)] for r, w in self.kinds)

    def as_moe(self) -> moe.MoeConfig:
        """The parameters' and the expert layer's view (``models/moe.py``
        makes, partitions and runs them)."""
        return moe.MoeConfig(
            vocab_size=self.vocab_size, dim=self.dim,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, stated_head_dim=self.head_dim,
            ffn_dim=self.ffn_dim, n_experts=self.n_experts,
            experts_per_token=self.experts_per_token,
            norm_topk_prob=self.norm_topk_prob, scoring="softmax",
            expert_act="relu", experts_held=self.experts_held,
            first_expert=self.first_expert, router_aux_coef=0.0,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, remat=self.remat, ce_chunk_size=self.ce_chunk_size,
        )

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
            head_dim=32, ffn_dim=32, n_experts=8, experts_per_token=2,
            rope_layout=(0, 1, 1, 1) * 2, window_layout=(0, 1, 1, 1) * 2,
            window=16, max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return SmallThinkerConfig(**base)


def pos_name(i: int) -> str:
    """The key of the period's position ``i`` in ``params["layers"]``."""
    return f"pos{i}"


def init_params(cfg: SmallThinkerConfig, rng: jax.Array) -> Params:
    """``models/moe.py``'s tree, its slab of layers dealt out to the
    period's positions: layer ``l`` is row ``l // period`` of position
    ``l % period``."""
    params = moe.init_params(cfg.as_moe(), rng)
    period = cfg.period
    params["layers"] = {
        pos_name(i): jax.tree.map(lambda a: a[i::period], params["layers"])
        for i in range(period)
    }
    return params


def param_specs(cfg: SmallThinkerConfig) -> Params:
    specs = moe.param_specs(cfg.as_moe())
    specs["layers"] = {
        pos_name(i): specs["layers"] for i in range(cfg.period)}
    return specs


def _trees(params: Params):
    """``params``' layers as the layout's one part takes them."""
    positions = params["layers"]
    return [tuple(positions[pos_name(i)] for i in range(len(positions)))]


def layer_params(cfg: SmallThinkerConfig, params: Params, layer: int
                 ) -> Params:
    """Layer ``layer``'s own leaves."""
    return stack.layer_params(cfg.layout, _trees(params), layer)


def param_count(cfg: SmallThinkerConfig) -> int:
    return moe.param_count(cfg.as_moe())


def validate_for_mesh(cfg: SmallThinkerConfig, mesh: Mesh, seq_len: int = 0,
                      batch: int = 0) -> None:
    """``models/moe.py``'s checks (heads over tp, the held experts over
    ep, the batch over the data shards), and no window over sp."""
    moe.validate_for_mesh(cfg.as_moe(), mesh, seq_len=seq_len, batch=batch)
    sp = dict(mesh.shape).get(SP, 1)
    if sp > 1 and any(w is not None for _, w in cfg.kinds):
        raise ValueError(
            f"smallthinker: mesh sp={sp} with window layers (window "
            f"{cfg.window}): ring and ulysses attention have no window, "
            "and a sequence shard would need its neighbour's last "
            f"{cfg.window - 1} keys; run the sequence whole on a device "
            "(sp=1)")


# ---------------------------------------------------------------------------
# The block, the forward
# ---------------------------------------------------------------------------

def attention(cfg: SmallThinkerConfig, mesh, lp: Params, y, rotary: bool,
              window: Optional[int]):
    """``y (b, s, d)``, pre-normed -> the attention sublayer's output
    before the residual: 28 query heads on 4 key heads, rotary where the
    layer has it, the last ``window`` positions where it has one."""
    dt = cfg.dtype
    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with trace.scope("attn_proj"):
        q = (y @ lp["wq"].astype(dt)).reshape(b, s, h, hd)
        k = (y @ lp["wk"].astype(dt)).reshape(b, s, kvh, hd)
        v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
        if rotary:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s))
            inv_freq = rope_frequencies(hd, cfg.rope_theta)
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
    out = attn_ops.flash_attention(
        q, k, v, causal=True, mesh=mesh, window=window)
    with trace.scope("attn_proj"):
        return out.reshape(b, s, h * hd) @ lp["wo"].astype(dt)


def block(cfg: SmallThinkerConfig, mesh, rotary: bool,
          window: Optional[int], lp: Params, x):
    """One layer of the kind ``(rotary, window)``."""
    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    x = x + attention(cfg, mesh, lp, y, rotary, window)
    with trace.scope("norm"):
        u = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    # the router reads the attention's input, the experts the
    # feed-forward's
    x = x + moe.moe_mlp(cfg.as_moe(), lp, u, mesh, route_on=y)[0]
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, SP, None)))
    return x


def _report_shapes(cfg: SmallThinkerConfig):
    """The gauges that say what this build's layers are (set while the
    step is traced, as ``attn.block_q`` is); the pattern is a text."""
    kinds = cfg.kinds
    windows = sum(w is not None for _, w in kinds)
    trace.gauge("attn.window", cfg.window if windows else 0)
    trace.gauge("attn.window_layers", windows)
    trace.gauge("attn.full_layers", len(kinds) - windows)
    trace.gauge("attn.rotary_layers", sum(r for r, _ in kinds))
    trace.gauge("attn.group", cfg.n_heads // cfg.n_kv_heads)
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one (`_block_fn`)
    trace.gauge("layers.period", cfg.period)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)


def _block_fn(cfg: SmallThinkerConfig, mesh, rotary: bool,
              window: Optional[int]):
    """A block is recomputed whole in the backward pass, but for the
    flash forward's output and ``lse``, its backward's residuals (114
    MiB a layer at 16384 tokens, full or window): the kernel runs once a
    step. q, k and v are recomputed."""
    return stack.recompute(
        functools.partial(block, cfg, mesh, rotary, window), cfg.remat,
        attn_ops.KEPT, attn_ops.report_kept)


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: SmallThinkerConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm:
    (b, s, dim). One scan over the layout's periods."""
    b, s = tokens.shape
    if mesh is not None:
        validate_for_mesh(cfg, mesh, seq_len=s, batch=b)
    _report_shapes(cfg)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    fns = {kind: _block_fn(cfg, mesh, *kind) for kind in set(cfg.kinds)}
    return stack.walk(x, cfg.layout, _trees(params),
                      lambda kind, lp, x: (fns[kind](lp, x), None))[0]


def live_rows(
    params: Params, tokens: jnp.ndarray, cfg: SmallThinkerConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Per layer, first to last, the (token, choice) pairs of ``tokens``
    (b, s) whose chosen expert is a held one: the rows the grouped
    products really work on (the rest take their zero branch). A forward
    of its own beside the step, which has no output but the loss: the
    gauge ``moe.rows_held`` is what uniform routing *would* send, this
    is what the router sends. (n_layers,) int32."""
    mcfg, first = cfg.as_moe(), cfg.first_expert
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)

    def each(kind, lp, x):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        _, _, top_e = moe.route(mcfg, lp["router"], y.reshape(-1, cfg.dim))
        held = jnp.sum((top_e >= first) & (top_e < first + mcfg.n_held),
                       dtype=jnp.int32)
        return block(cfg, mesh, *kind, lp, x), held

    return stack.walk(x, cfg.layout, _trees(params), each)[1]


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: SmallThinkerConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored)."""
    x = forward_layers(params, tokens, cfg, mesh)
    with trace.scope("norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)
