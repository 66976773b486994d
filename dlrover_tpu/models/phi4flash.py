"""The ``phi4flash`` decoder family (microsoft Phi-4-mini-flash-reasoning,
the decoder-hybrid-decoder layout of arXiv:2507.06607): **Mamba-1 layers
one to one with window attention, and a second decoder whose layers read
two tensors of the first's**: one Mamba layer's scan output (the memory)
and one full-attention layer's keys and values. LayerNorm with a bias, no
position term of any kind, a tied head.

A layer is one of five kinds (``d`` the model width, ``c`` the Mamba
channels, heads of ``hd`` on ``kv`` key heads; ``LN`` has a weight and a
bias; no other bias but the convolution's and ``W_dt``'s)::

    y = LN(x);  x = x + Mixer_K(y);  u = LN(x);  x = x + SwiGLU(u)

    M:  [xs | z] = y W_in;  t = silu(conv4(xs) + b_conv)
        [p | B | C] = t W_x;  dt = softplus(p W_dt + b_dt);  A = -exp(A_log)
        m = selective_scan(t, dt, A, B, C, D)               ops/selective_scan
        Mixer = (m silu(z)) W_out
    S:  [q | k | v] = y W_qkv;  Mixer = flash(q, k, v; 0 <= i - j < window) W_o
    F:  as S under the causal mask alone
    G:  Mixer = (m* silu(y W_1)) W_2         m* the memory: the last M's ``m``
    C:  Mixer = flash(y W_q, k*, v*; causal) W_o        k*, v* the one F's

    logits = LN(x_L) E^T,  E the lookup's table

The stack is ``(M S)^a  M F  (G C)^b`` and **not a chain**: the last ``M``
hands its ``m`` (before its gate, ``D`` term included) to every ``G`` and
the ``F`` hands its ``k``, ``v`` to every ``C``. ``models/stack.py``'s
``walk`` hands a block the residual alone, and stays so: the family walks
the first decoder, runs the two producers in line, and walks the second
decoder with ``m*``, ``k*``, ``v*`` closed over by the walk's ``each``,
so that they are constants of its ``lax.scan`` (their cotangents sum over
the readers) and **arguments of the recomputed block** (`_block_fn`: a
reader's backward recomputes the reader, never a producer).

Shared with other families: ``llama.swiglu`` under ``dense_mlp``,
``granite_hybrid.conv_bias_silu``, the flash kernels (``window=``), the
embedding and the fused cross-entropy on one table. Scopes: ``mamba_proj``,
``mamba_conv``, ``mamba_xdt``, ``mamba_scan``, ``mamba_gate``, ``gmu``,
``attn_proj`` (S and F), ``cross_proj`` (C), ``dense_mlp``, ``norm``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.models import llama, stack
from dlrover_tpu.models.granite_hybrid import conv_bias_silu
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    attention,
    embed_lookup,
    flash_attention,
    selective_scan,
)
from dlrover_tpu.ops.norms import layer_norm
from dlrover_tpu.parallel.mesh import BATCH_AXES, FSDP, PP, SP, TP

Params = Dict[str, Any]

#: what a layer of each kind is
KINDS = {"M": "Mamba-1", "S": "window attention", "F": "full attention",
         "G": "gated memory unit", "C": "cross attention"}
_LAYOUT = re.compile(r"((?:MS)*)MF((?:GC)*)")


def published_kinds(n_layers: int = 32) -> str:
    """The published stack: the first half ``M S``, then the two
    producers, then ``G C`` to the end."""
    half = n_layers // 2
    return "MS" * (half // 2) + "MF" + "GC" * ((n_layers - half - 2) // 2)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """microsoft/Phi-4-mini-flash-reasoning's config.json by default."""
    vocab_size: int = 200064
    dim: int = 2560
    #: a letter of `KINDS` a layer held, first to last
    layer_kinds: str = published_kinds()
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn_dim: int = 10240
    window: int = 512
    # Mamba-1 (Gu and Dao 2023): what the config has no key for
    mamba_expand: int = 2
    mamba_state: int = 16
    conv_size: int = 4
    mamba_chunk: int = 256
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    norm_eps: float = 1e-5
    # sigma of the normal draws, and of the projections that close a
    # residual branch (w_out, w_o, w_2, w_down) where it is another
    init_std: float = 0.02
    out_proj_std: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ce_chunk_size: int = 2048

    def __post_init__(self):
        if not _LAYOUT.fullmatch(self.layer_kinds):
            raise ValueError(
                f"layer_kinds={self.layer_kinds!r}: the stack is (M S)^a M F "
                "(G C)^b: the last M hands its scan to the G layers, the F "
                "its keys and values to the C layers")
        if self.n_heads % self.n_kv_heads or self.dim % self.n_heads:
            raise ValueError(
                f"{self.n_heads} query heads over {self.n_kv_heads} key "
                f"heads of width {self.dim} / {self.n_heads}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_kinds)

    @property
    def pattern_string(self) -> str:
        return self.layer_kinds

    @property
    def first_periods(self) -> int:
        return len(_LAYOUT.fullmatch(self.layer_kinds).group(1)) // 2

    @property
    def second_periods(self) -> int:
        return len(_LAYOUT.fullmatch(self.layer_kinds).group(2)) // 2

    @property
    def layout(self) -> Tuple[stack.Part, ...]:
        """The first decoder's periods stacked, the two producers each on
        its own, the second decoder's periods stacked."""
        a, b = self.first_periods, self.second_periods
        return ((stack.Part(("M", "S"), a),) * bool(a)
                + (stack.Part(("M",)), stack.Part(("F",)))
                + (stack.Part(("G", "C"), b),) * bool(b))

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def channels(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def dt_rank(self) -> int:
        return -(-self.dim // 16)

    @staticmethod
    def from_hf(config: dict, **kw) -> "Phi4FlashConfig":
        """From a ``config.json`` of ``model_type: phi4flash`` (and, where
        it has them, the held ``layer_kinds`` and the ``assumed`` Mamba
        sizes of a benchmark configuration)."""
        for key, want in (("model_type", "phi4flash"), ("hidden_act", "silu"),
                          ("tie_word_embeddings", True), ("mlp_bias", False),
                          ("lm_head_bias", False), ("mb_per_layer", 2)):
            if config.get(key, want) != want:
                raise ValueError(
                    f"{key}={config[key]!r} is not what models/phi4flash.py "
                    f"computes ({want!r})")
        mamba = config.get("assumed", {}).get("mamba", {})
        return Phi4FlashConfig(**{**dict(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_kinds=config.get(
                "layer_kinds", published_kinds(config["num_hidden_layers"])),
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            ffn_dim=config["intermediate_size"],
            window=config["sliding_window"],
            mamba_expand=mamba.get("expand", 2),
            mamba_state=mamba.get("d_state", 16),
            conv_size=mamba.get("d_conv", 4),
            norm_eps=float(config["layer_norm_eps"]),
        ), **kw})

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        base = dict(
            vocab_size=256, dim=64, layer_kinds="MSMSMFGCGC", n_heads=4,
            n_kv_heads=2, ffn_dim=96, window=16, mamba_chunk=16,
            dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return Phi4FlashConfig(**base)


def pos_name(i: int) -> str:
    """The key of a period's position ``i`` in ``params["first"]`` and
    ``params["second"]``."""
    return f"pos{i}"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _block_shapes(cfg: Phi4FlashConfig, kind: str) -> Dict[str, Tuple]:
    """``{name: (shape, init, spec)}`` of one block. ``init`` is "normal",
    "out" (a projection that closes a residual branch), "ones", "zeros",
    "conv", "a_log" or "dt_bias"; ``spec`` the partition of the leaf's own
    axes: a matrix shards its model-width side (its channel side where it
    has no other) over fsdp, the rest is replicated."""
    D, F = cfg.dim, cfg.ffn_dim
    rows, cols, rep = (FSDP, None), (None, FSDP), (None, None)
    shapes = {
        "attn_norm": ((D,), "ones", (None,)),
        "attn_norm_b": ((D,), "zeros", (None,)),
        "mlp_norm": ((D,), "ones", (None,)),
        "mlp_norm_b": ((D,), "zeros", (None,)),
        "w_gate": ((D, F), "normal", rows),
        "w_up": ((D, F), "normal", rows),
        "w_down": ((F, D), "out", cols),
    }
    c, n, r = cfg.channels, cfg.mamba_state, cfg.dt_rank
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kind == "M":
        shapes.update({
            "w_in": ((D, 2 * c), "normal", rows),         # [xs | z]
            "conv_w": ((c, cfg.conv_size), "conv", rep),
            "conv_b": ((c,), "conv", (None,)),
            "w_x": ((c, r + 2 * n), "normal", rows),      # [p | B | C]
            "w_dt": ((r, c), "normal", cols),
            "dt_bias": ((c,), "dt_bias", (None,)),
            "a_log": ((c, n), "a_log", rep),
            "d_skip": ((c,), "ones", (None,)),
            "w_out": ((c, D), "out", cols),
        })
    elif kind in "SF":
        shapes.update({
            "w_qkv": ((D, (h + 2 * kvh) * hd), "normal", rows),
            "w_o": ((h * hd, D), "out", cols),
        })
    elif kind == "G":
        shapes.update({
            "w_1": ((D, c), "normal", rows),
            "w_2": ((c, D), "out", cols),
        })
    else:
        shapes.update({
            "w_q": ((D, h * hd), "normal", rows),
            "w_o": ((h * hd, D), "out", cols),
        })
    return shapes


def _init_leaf(cfg: Phi4FlashConfig, key, shape, rule: str):
    f32 = jnp.float32
    if rule in ("normal", "out"):
        std = (cfg.init_std if rule == "normal" or cfg.out_proj_std is None
               else cfg.out_proj_std)
        return jax.random.normal(key, shape, f32) * std
    if rule == "ones":
        return jnp.ones(shape, f32)
    if rule == "zeros":
        return jnp.zeros(shape, f32)
    if rule == "conv":
        # a depthwise Conv1d's default, weight and bias: uniform within
        # fan_in^-1/2
        bound = cfg.conv_size ** -0.5
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if rule == "a_log":
        # Mamba-1's: A = -(1 .. n) a channel, stored as its log
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)), shape)
    # dt_bias, Mamba-1's: the inverse softplus of a step log-uniform in
    # [dt_min, dt_max]
    lo, hi = math.log(cfg.dt_min), math.log(cfg.dt_max)
    step = jnp.maximum(
        jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo), 1e-4)
    return step + jnp.log(-jnp.expm1(-step))


def _init_tree(cfg: Phi4FlashConfig, key, kind: str, lead: Tuple[int, ...]
               ) -> Params:
    shapes = _block_shapes(cfg, kind)
    return {
        name: _init_leaf(cfg, k, lead + shape, rule).astype(cfg.param_dtype)
        for k, (name, (shape, rule, _)) in zip(
            jax.random.split(key, len(shapes)), sorted(shapes.items()))
    }


def _part_name(part: stack.Part) -> str:
    return {("M", "S"): "first", ("M",): "memory", ("F",): "keys",
            ("G", "C"): "second"}[part.kinds]


def init_params(cfg: Phi4FlashConfig, rng: jax.Array) -> Params:
    """No ``lm_head``: the table is the head (``tie_word_embeddings``).
    ``first`` and ``second`` hold a decoder's periods stacked a position,
    ``memory`` and ``keys`` the two producers' own trees."""
    pd, D, V = cfg.param_dtype, cfg.dim, cfg.vocab_size
    k_embed, *k_parts = jax.random.split(rng, 1 + len(cfg.layout))
    params = {
        "embed": (jax.random.normal(k_embed, (V, D), jnp.float32)
                  * cfg.init_std).astype(pd),
        "final_norm": jnp.ones((D,), pd),
        "final_norm_b": jnp.zeros((D,), pd),
    }
    for part, key in zip(cfg.layout, k_parts):
        if part.repeats is None:
            params[_part_name(part)] = _init_tree(cfg, key, part.kinds[0], ())
        else:
            params[_part_name(part)] = {
                pos_name(i): _init_tree(cfg, k, kind, (part.repeats,))
                for i, (k, kind) in enumerate(zip(
                    jax.random.split(key, len(part.kinds)), part.kinds))}
    return params


def param_specs(cfg: Phi4FlashConfig) -> Params:
    """Data parallelism only (``validate_for_mesh``). The leading axis of
    a stacked position's leaves is the period."""
    def tree(kind, lead):
        return {name: P(*lead, *spec) for name, (_, _, spec)
                in _block_shapes(cfg, kind).items()}

    specs = {"embed": P(None, FSDP), "final_norm": P(None),
             "final_norm_b": P(None)}
    for part in cfg.layout:
        specs[_part_name(part)] = (
            tree(part.kinds[0], ()) if part.repeats is None else
            {pos_name(i): tree(kind, (None,))
             for i, kind in enumerate(part.kinds)})
    return specs


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


def _trees(cfg: Phi4FlashConfig, params: Params):
    """``params``' layers as the layout's parts take them."""
    return [params[_part_name(part)] if part.repeats is None else tuple(
        params[_part_name(part)][pos_name(i)] for i in range(len(part.kinds)))
        for part in cfg.layout]


def layer_params(cfg: Phi4FlashConfig, params: Params, layer: int) -> Params:
    """Layer ``layer``'s own leaves, wherever the layout keeps them."""
    return stack.layer_params(cfg.layout, _trees(cfg, params), layer)


def validate_for_mesh(cfg: Phi4FlashConfig, mesh: Mesh, batch: int = 0
                      ) -> None:
    """dp and fsdp only; each other axis refused with what it lacks."""
    shape = dict(mesh.shape)
    missing = {
        SP: "a Mamba layer's state and its convolution's last taps are not "
            "handed across the ranks of a sequence, window layers (window "
            f"{cfg.window}) would need their neighbour's last keys, and the "
            "shared memory and keys of the second decoder would have to be "
            "gathered over the ranks for every reader",
        TP: "no head- or channel-sharded form of the five kinds of layer is "
            "written (the scan's B and C sum over all channels)",
        PP: "a stage boundary inside or after the producers must carry the "
            "memory m and the keys and values k, v beside the residual x, "
            "and parallel/pp_schedule.py sends one array",
    }
    for axis, why in missing.items():
        if shape.get(axis, 1) > 1:
            raise ValueError(
                f"phi4flash: mesh {axis}={shape[axis]}: {why} (dp and fsdp "
                "only)")
    shards = math.prod(shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        raise ValueError(
            f"batch={batch} does not divide over the mesh's {shards} data "
            "shards (dp x fsdp x ep)")


# ---------------------------------------------------------------------------
# The mixers, the block, the forward
# ---------------------------------------------------------------------------

def mamba_operands(cfg: Phi4FlashConfig, lp: Params, y):
    """``y (b, s, d)``, pre-normed -> what the scan takes (``t (b, s, c)``,
    ``dt (b, s, c)`` float32, ``A (c, n)`` float32, ``B, C (b, s, n)``,
    ``D (c,)``) and the gate's logits ``z (b, s, c)``."""
    dt_, f32 = cfg.dtype, jnp.float32
    c, n, r = cfg.channels, cfg.mamba_state, cfg.dt_rank
    with trace.scope("mamba_proj"):
        xz = y @ lp["w_in"].astype(dt_)
    with trace.scope("mamba_conv"):
        t = conv_bias_silu(xz[..., :c], lp["conv_w"], lp["conv_b"])
    with trace.scope("mamba_xdt"):
        pbc = t @ lp["w_x"].astype(dt_)
        step = jax.nn.softplus(
            jnp.dot(pbc[..., :r], lp["w_dt"].astype(dt_),
                    preferred_element_type=f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["a_log"].astype(f32))
    return (t, step, A, pbc[..., r:r + n], pbc[..., r + n:],
            lp["d_skip"].astype(f32)), xz[..., c:]


def gate(m, logits):
    """``m silu(logits)``, float32 inside."""
    f32 = jnp.float32
    return (m.astype(f32) * jax.nn.silu(logits.astype(f32))).astype(m.dtype)


def mamba_mixer(cfg: Phi4FlashConfig, lp: Params, y, mesh=None):
    """``y (b, s, d)``, pre-normed -> ``(the Mamba sublayer's output before
    the residual, the scan's output m (b, s, c) before the gate)``."""
    operands, z = mamba_operands(cfg, lp, y)
    with trace.scope("mamba_scan"):
        m = selective_scan.selective_scan(
            *operands, chunk=cfg.mamba_chunk, mesh=mesh)
    with trace.scope("mamba_gate"):
        g = gate(m, z)
    with trace.scope("mamba_proj"):
        return g @ lp["w_out"].astype(cfg.dtype), m


def memory_unit(cfg: Phi4FlashConfig, lp: Params, y, memory):
    """A gated memory unit: the memory under ``silu`` of the unit's own
    projection of its layer's input."""
    with trace.scope("gmu"):
        g = gate(memory, y @ lp["w_1"].astype(cfg.dtype))
        return g @ lp["w_2"].astype(cfg.dtype)


def _heads(a, hd: int):
    return a.reshape(a.shape[:2] + (-1, hd))


def attention_operands(cfg: Phi4FlashConfig, lp: Params, y):
    h, hd = cfg.n_heads, cfg.head_dim
    qkv = y @ lp["w_qkv"].astype(cfg.dtype)
    kv = cfg.n_kv_heads * hd
    return (_heads(qkv[..., :h * hd], hd),
            _heads(qkv[..., h * hd:h * hd + kv], hd),
            _heads(qkv[..., h * hd + kv:], hd))


def attention_mixer(cfg: Phi4FlashConfig, lp: Params, y, window, mesh=None):
    """``y (b, s, d)``, pre-normed -> ``(the sublayer's output before the
    residual, k, v)``: no position term, softmax at ``hd^-1/2``."""
    b, s, _ = y.shape
    with trace.scope("attn_proj"):
        q, k, v = attention_operands(cfg, lp, y)
    out = flash_attention(q, k, v, causal=True, mesh=mesh, window=window)
    with trace.scope("attn_proj"):
        return out.reshape(b, s, -1) @ lp["w_o"].astype(cfg.dtype), k, v


def cross_mixer(cfg: Phi4FlashConfig, lp: Params, y, k, v, mesh=None):
    """A query projection only, on another layer's keys and values."""
    b, s, _ = y.shape
    with trace.scope("cross_proj"):
        q = _heads(y @ lp["w_q"].astype(cfg.dtype), cfg.head_dim)
    out = flash_attention(q, k, v, causal=True, mesh=mesh)
    with trace.scope("cross_proj"):
        return out.reshape(b, s, -1) @ lp["w_o"].astype(cfg.dtype)


def norm(x, lp: Params, name: str, eps: float):
    with trace.scope("norm"):
        return layer_norm(x, lp[name], lp[name + "_b"], eps)


def mixer(cfg: Phi4FlashConfig, mesh, kind: str, lp: Params, y, shared=()):
    """``(Mixer_kind(y), what the layer hands on)``: ``m`` of an M layer,
    ``(k, v)`` of an S or F layer, None of a reader. ``shared``: ``(m*,
    k*, v*)`` for the readers."""
    if kind == "M":
        return mamba_mixer(cfg, lp, y, mesh)
    if kind in "SF":
        out, k, v = attention_mixer(
            cfg, lp, y, cfg.window if kind == "S" else None, mesh)
        return out, (k, v)
    memory, k, v = shared
    if kind == "G":
        return memory_unit(cfg, lp, y, memory), None
    return cross_mixer(cfg, lp, y, k, v, mesh), None


def feed_forward(cfg: Phi4FlashConfig, mesh, lp: Params, x):
    u = norm(x, lp, "mlp_norm", cfg.norm_eps)
    with trace.scope("dense_mlp"):
        x = x + llama.swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"],
                             cfg.dtype)
    if mesh is not None:
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    return x


def block(cfg: Phi4FlashConfig, mesh, kind: str, lp: Params, x, *shared):
    """One layer of ``kind`` -> ``(the residual after it, what the layer
    hands to later ones, its mixer's output)``; what a caller drops is
    never kept."""
    mix, handed = mixer(cfg, mesh, kind, lp,
                        norm(x, lp, "attn_norm", cfg.norm_eps), shared)
    return feed_forward(cfg, mesh, lp, x + mix), handed, mix


def _report_shapes(cfg: Phi4FlashConfig, batch: int, seq: int):
    """The gauges that say what this build's layers are (set while the
    step is traced); the pattern is a text."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    trace.gauge("layers.tied_head", 1)
    trace.gauge("layers.memory_readers", cfg.kinds.count("G"))
    trace.gauge("layers.kv_readers", cfg.kinds.count("C"))
    trace.gauge("layers.memory_bytes", batch * seq * cfg.channels * itemsize)
    trace.gauge("layers.kv_bytes", 2 * batch * seq * cfg.n_kv_heads
                * cfg.head_dim * itemsize)
    trace.provide_text("layers.pattern", lambda: cfg.pattern_string)
    trace.gauge("mamba.channels", cfg.channels)
    trace.gauge("mamba.state", cfg.mamba_state)
    trace.gauge("mamba.dt_rank", cfg.dt_rank)
    trace.gauge("mamba.state_kept", 0)   # 1 once a block keeps them
    trace.gauge("attn.heads", cfg.n_heads)
    trace.gauge("attn.group", cfg.group)
    trace.gauge("attn.head_dim", cfg.head_dim)
    trace.gauge("attn.window", cfg.window)
    trace.gauge("attn.out_kept", 0)      # 1 once a block keeps one


def _block_fn(cfg: Phi4FlashConfig, mesh, kind: str):
    """A block is recomputed whole in the backward pass from its
    arguments (**the shared tensors among them: a reader never recomputes
    a producer**), but for what it names: an attention block the flash
    forward's output and ``lse`` (``attention.KEPT``: 84 MB at 40 heads of
    64 and 16384 tokens); a Mamba block the scan's output and its chunks'
    starting states (``selective_scan.KEPT``: 168 MB + 21 MB at 5120
    channels): each kernel's forward runs once a step. A memory unit keeps
    nothing."""
    keep, kept = {
        "M": (selective_scan.KEPT, selective_scan.report_kept),
        "G": ((), None),
    }.get(kind, (attention.KEPT, attention.report_kept))
    return stack.recompute(
        functools.partial(block, cfg, mesh, kind), cfg.remat, keep, kept)


def second_decoder(cfg: Phi4FlashConfig, mesh, params: Params, x, memory, k,
                   v, taps: bool = False):
    """The ``G C`` periods under one scan: ``memory``, ``k`` and ``v`` are
    its constants, read by every period, and arguments of each recomputed
    block. -> ``(x, the mixers' outputs a layer where ``taps``)``."""
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in "GC"}

    def each(kind, lp, x):
        x, _, mix = fns[kind](lp, x, memory, k, v)
        return x, mix if taps else None

    b = bool(cfg.second_periods)
    return stack.walk(x, cfg.layout[-1:] * b, _trees(cfg, params)[-1:] * b,
                      each)


def forward_taps(
    params: Params, tokens: jnp.ndarray, cfg: Phi4FlashConfig,
    mesh: Optional[Mesh] = None, taps: bool = True,
):
    """``(the residual after the last block, every layer's mixer output
    (n_layers, b, s, dim) where ``taps``)``. The first decoder's periods
    under one scan, the two producers in line, the second decoder."""
    if mesh is not None:
        validate_for_mesh(cfg, mesh, batch=tokens.shape[0])
    _report_shapes(cfg, *tokens.shape)
    fns = {kind: _block_fn(cfg, mesh, kind) for kind in "MSF"}

    def each(kind, lp, x):
        x, _, mix = fns[kind](lp, x)
        return x, mix if taps else None

    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    a = bool(cfg.first_periods)
    x, first = stack.walk(x, cfg.layout[:a], _trees(cfg, params)[:a], each)
    x, memory, mix_m = fns["M"](params["memory"], x)
    x, (k, v), mix_f = fns["F"](params["keys"], x)
    x, second = second_decoder(cfg, mesh, params, x, memory, k, v, taps)
    if not taps:
        return x, None
    return x, jnp.concatenate(
        [m for m in (first, mix_m[None], mix_f[None], second)
         if m is not None])


def forward_layers(
    params: Params, tokens: jnp.ndarray, cfg: Phi4FlashConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """The residual after the last block, before the final norm: ``(b, s,
    dim)``."""
    return forward_taps(params, tokens, cfg, mesh, taps=False)[0]


def head_input(cfg: Phi4FlashConfig, params: Params, x):
    """``LN(x_L)``: what the table, as the head, reads."""
    return norm(x, params, "final_norm", cfg.norm_eps)


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: Phi4FlashConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 ignored): the table
    is read by the lookup and by the head, and its two gradients are
    summed in the one leaf."""
    x = forward_layers(params, tokens, cfg, mesh)
    return stack.next_token_loss(
        head_input(cfg, params, x), params["embed"].T, tokens,
        cfg.ce_chunk_size, mesh)
