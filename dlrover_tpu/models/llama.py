"""Llama-3-family decoder, TPU-first.

The BASELINE.json north star is a Llama-3-8B JAX run on v5p; this is that
model, built for the XLA compilation model rather than translated from any
torch layout:

- **scan-over-layers**: per-layer params are stacked on a leading axis and
  the decoder is one `lax.scan` — O(1) HLO size, fast compiles at 8B scale,
  and the natural shape for per-layer remat (`jax.checkpoint`) which is how
  fsdp param gathers stay overlapped with compute.
- **explicit PartitionSpecs** (`param_specs`): megatron-style tp layout
  (column-parallel wq/wk/wv/w_gate/w_up, row-parallel wo/w_down) with fsdp
  on the opposite dim; XLA's SPMD partitioner inserts the all-gathers /
  reduce-scatters.
- **sequence parallelism**: when the mesh has sp>1 the attention runs as
  `ring_attention` inside a `shard_map` island (kv chunks rotate over ICI);
  otherwise the Pallas `flash_attention` path.
- bfloat16 compute / float32 params + optimizer; the loss fuses the
  unembed matmul into a chunked cross-entropy (``ops/chunked_ce.py``) so
  full [B, T, V] f32 logits are never materialized — f32 accumulation per
  vocab chunk instead.

The reference has no model code at all (it orchestrates wrapped trainers,
SURVEY.md §2.8); configs here mirror the public Llama-3 shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.models import stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention as attn_ops
from dlrover_tpu.ops import (
    apply_rope,
    cross_entropy_sums,
    embed_lookup,
    flash_attention,
    mha_reference,
    ring_attention,
    rms_norm,
    rope_frequencies,
)
from dlrover_tpu.parallel.mesh import BATCH_AXES, DP, EP, FSDP, PP, SP, TP

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # master params
    remat: bool = True
    # "all": the layer is recomputed in bwd but for what its attention
    # backward kernels read, which stays (`_maybe_remat`): q, k, v after
    # rotary and the flash forward's output and lse, tokens a chip x
    # (2 h hd + 2 kvh hd) x 2 B + the lse's tokens x h x 4 B a layer
    # (161 MiB at 8192 tokens and 32 x 128 q, 8 x 128 k and v heads)
    # beside the layer's input, so the recomputed forward runs neither
    # the flash kernel nor the three projections;
    # "mlp": also save the ffn gate/up activations — ~75% of a layer's
    # recompute FLOPs are the two d×ffn matmuls, so saving their outputs
    # (2*b*s*ffn elements/layer) buys most of no-remat's speed at a
    # fraction of its memory (`ffn_gate` names silu's output and its
    # backward reads its input, so the gate's product is still formed
    # again: about half of that, PERF.md section 7)
    remat_policy: str = "all"
    attn_impl: str = "auto"   # auto | flash | reference | ring | ulysses
    # chunked fused cross-entropy (ops/chunked_ce.py): vocab columns per
    # scan step of the loss — peak loss activation is b*s*ce_chunk_size
    # f32 instead of dense logits' b*s*vocab.
    ce_chunk_size: int = 2048
    # pipeline parallelism: microbatches in flight per step (0 → pp size).
    # More microbatches shrink the GPipe bubble (pp-1)/(n_micro+pp-1).
    pp_microbatches: int = 0
    # pipeline schedule: "gpipe" (all-forward-then-backward; simplest,
    # activation memory grows with n_micro) or "1f1b" (one-forward-
    # one-backward steady state; at most pp microbatches of boundary
    # activations live per stage — the Megatron default the reference's
    # checkpoint layer assumes)
    pp_schedule: str = "gpipe"
    # virtual pipeline stages per rank (interleaved 1F1B). v>1 cuts the
    # pipeline bubble by a factor v: the model is split into pp*v chunks,
    # chunk c on rank c%pp, and the static schedule tables interleave
    # chunks inside warmup/cooldown (parallel/pp_schedule.py; reference
    # parity: megatron_dist_ckpt.py:262,489 virtual-stage checkpoints)
    pp_virtual_stages: int = 1
    # layer-stack layout the interleaved executor expects:
    # - "canonical": train state keeps the natural layer order; the
    #   executor gathers to rank-major in-step and scatters grads back.
    #   Checkpoint-layout independent, but the gather moves ~(1-1/v) of
    #   layer params + grads across the pp axis EVERY step — fine for
    #   tests/small models, wasteful at scale.
    # - "rank_major": the state already holds layers in rank-major order
    #   (see ``interleave_layers``/``deinterleave_layers``); zero
    #   per-step movement. Canonicalize at checkpoint boundaries.
    pp_interleave_layout: str = "canonical"

    def __post_init__(self):
        if self.remat_policy not in ("all", "mlp"):
            raise ValueError(
                f"remat_policy={self.remat_policy!r}: expected 'all' or 'mlp'"
            )
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r}: expected 'gpipe' or '1f1b'"
            )
        if self.pp_virtual_stages < 1:
            raise ValueError("pp_virtual_stages must be >= 1")
        if self.pp_interleave_layout not in ("canonical", "rank_major"):
            raise ValueError(
                f"pp_interleave_layout={self.pp_interleave_layout!r}: "
                "expected 'canonical' or 'rank_major'"
            )
        if self.pp_virtual_stages > 1 and self.pp_schedule != "1f1b":
            raise ValueError(
                "pp_virtual_stages > 1 is the interleaved schedule; it "
                "requires pp_schedule='1f1b'"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # ---- presets -------------------------------------------------------
    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(
            dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28672
        )

    @staticmethod
    def gpt2_xl_class() -> "LlamaConfig":
        """~1.5B-param config matching the reference's flash-ckpt benchmark
        subject (GPT-2 xl, `docs/blogs/flash_checkpoint.md` there)."""
        return LlamaConfig(
            vocab_size=50304, dim=1600, n_layers=48, n_heads=25,
            n_kv_heads=25, ffn_dim=3712, max_seq_len=1024, rope_theta=10000.0
        )

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=128, max_seq_len=128, dtype=jnp.float32, remat=False,
        )
        base.update(kw)
        return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# Params: init + sharding specs
# ---------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, rng: jax.Array) -> Params:
    """Random init. For large models call under jit with
    ``out_shardings=named_shardings(mesh, param_specs(cfg))`` so params are
    born sharded, never materialized on one host."""
    pd = cfg.param_dtype
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    std = 0.02
    L, D, H, KV, F = (cfg.n_layers, cfg.dim, cfg.n_heads * cfg.head_dim,
                      cfg.n_kv_heads * cfg.head_dim, cfg.ffn_dim)

    def norm_init(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    ks = jax.random.split(k_layers, 7)
    out_scale = std / (2 * cfg.n_layers) ** 0.5  # gpt-2 residual scaling
    layers = {
        "attn_norm": jnp.ones((L, D), pd),
        "wq": norm_init(ks[0], (L, D, H), std),
        "wk": norm_init(ks[1], (L, D, KV), std),
        "wv": norm_init(ks[2], (L, D, KV), std),
        "wo": norm_init(ks[3], (L, H, D), out_scale),
        "mlp_norm": jnp.ones((L, D), pd),
        "w_gate": norm_init(ks[4], (L, D, F), std),
        "w_up": norm_init(ks[5], (L, D, F), std),
        "w_down": norm_init(ks[6], (L, F, D), out_scale),
    }
    return {
        "embed": norm_init(k_embed, (cfg.vocab_size, D), std),
        "layers": layers,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": norm_init(k_head, (D, cfg.vocab_size), std),
    }


def param_specs(cfg: LlamaConfig, pp: int = 1) -> Params:
    """PartitionSpec pytree mirroring `init_params`. The leading axis of
    every layer leaf is the scan/layer axis: unsharded normally, split
    over the ``pp`` mesh axis under pipeline parallelism (each stage holds
    its contiguous slab of layers)."""
    layer_axis = PP if pp > 1 else None
    return {
        "embed": P(TP, FSDP),
        "layers": {
            "attn_norm": P(layer_axis, None),
            "wq": P(layer_axis, FSDP, TP),
            "wk": P(layer_axis, FSDP, TP),
            "wv": P(layer_axis, FSDP, TP),
            "wo": P(layer_axis, TP, FSDP),
            "mlp_norm": P(layer_axis, None),
            "w_gate": P(layer_axis, FSDP, TP),
            "w_up": P(layer_axis, FSDP, TP),
            "w_down": P(layer_axis, TP, FSDP),
        },
        "final_norm": P(None),
        "lm_head": P(FSDP, TP),
    }


def interleave_layers(params: Params, pp: int, v: int) -> Params:
    """Canonical -> rank-major layer order for
    ``pp_interleave_layout='rank_major'`` interleaved pipelines: apply
    once after init / after a checkpoint restore (the per-step gather
    the 'canonical' layout pays then disappears)."""
    from dlrover_tpu.parallel.pp_schedule import interleave_layer_perm

    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    perm = interleave_layer_perm(n_layers, pp, v)
    return {
        **params,
        "layers": jax.tree.map(lambda a: a[perm], params["layers"]),
    }


def deinterleave_layers(params: Params, pp: int, v: int) -> Params:
    """Rank-major -> canonical: apply before saving a portable
    checkpoint from a ``rank_major`` interleaved run."""
    import numpy as np

    from dlrover_tpu.parallel.pp_schedule import interleave_layer_perm

    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    inv = np.argsort(interleave_layer_perm(n_layers, pp, v))
    return {
        **params,
        "layers": jax.tree.map(lambda a: a[inv], params["layers"]),
    }


abstract_params = functools.partial(stack.abstract_params, init_params)
param_count = functools.partial(stack.param_count, init_params)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _attention(cfg: LlamaConfig, mesh: Optional[Mesh], q, k, v):
    impl = cfg.attn_impl
    sp_size = mesh.shape[SP] if mesh is not None and SP in mesh.shape else 1
    if impl == "auto":
        impl = "ring" if sp_size > 1 else "flash"
    if impl in ("ring", "ulysses") and sp_size > 1:
        assert mesh is not None
        from jax import shard_map

        if impl == "ulysses":
            from dlrover_tpu.ops.ulysses import ulysses_attention as sp_attn
        else:
            sp_attn = ring_attention
        qspec = P(BATCH_AXES, SP, TP, None)
        sharded = shard_map(
            functools.partial(sp_attn, axis_name=SP, causal=True),
            mesh=mesh,
            in_specs=(qspec, qspec, qspec),
            out_specs=qspec,
            check_vma=False,
        )
        return sharded(q, k, v)
    if impl == "reference":
        return mha_reference(q, k, v, causal=True)
    return flash_attention(q, k, v, causal=True, mesh=mesh)


# ---------------------------------------------------------------------------
# Explicit-collective building blocks for the full-manual pp stages
# ---------------------------------------------------------------------------
# The pp executors run full-manual shard_map over EVERY mesh axis, so
# nothing is partitioned automatically inside a stage: tp is megatron's
# recipe (local head/ffn shards between the column-parallel matmuls and
# a psum closing each row-parallel one), fsdp is ZeRO-3 (per-layer
# all-gather inside the remat boundary, transposed by AD into a
# reduce-scatter of the grads), and dp/ep reduce only at the loss/grad
# sums. Size-1 axes make every collective a no-op, so one code path
# serves every mesh.
#
# Two tp gradient disciplines coexist, picked by ``tp_mode``:
#
# - ``"native"`` (gpipe): the backward is shard_map's own transpose, so
#   the row-parallel psum is a plain ``lax.psum`` and jax's scaled-
#   partial cotangent discipline (transpose(psum)=psum, boundary psums
#   over unmentioned axes) produces exact grads with no help.
# - ``"marker"`` (1f1b / interleaved): the backward is hand-scheduled
#   (per-slab jax.vjp + explicit end-of-schedule psums) under the
#   convention that a tp-replicated tensor's cotangent IS the true
#   total on every tp rank. The megatron f/g custom_vjp pair keeps the
#   per-device vjps consistent with that convention: ``g`` (psum fwd /
#   identity bwd) hands the replicated total straight to each rank's
#   partial, ``f`` (identity fwd / psum bwd) sums the per-rank branch
#   partials back to a replicated total.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_in(x, axis):
    """Megatron ``f``: identity forward, psum backward."""
    return x


def _tp_region_in_fwd(x, axis):
    return x, None


def _tp_region_in_bwd(axis, _, g):
    return (lax.psum(g, axis),)


_tp_region_in.defvjp(_tp_region_in_fwd, _tp_region_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_out(x, axis):
    """Megatron ``g``: psum forward, identity backward."""
    return lax.psum(x, axis)


def _tp_region_out_fwd(x, axis):
    return lax.psum(x, axis), None


def _tp_region_out_bwd(axis, _, g):
    return (g,)


_tp_region_out.defvjp(_tp_region_out_fwd, _tp_region_out_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_downscale(x, scale):
    """Identity forward, ``g * scale`` backward (marker discipline
    only): where a tp-replicated total cotangent meets a native
    collective transpose that sums over tp (the lm_head gather's
    reduce-scatter), pre-scaling by 1/tp makes that sum exact."""
    return x


def _grad_downscale_fwd(x, scale):
    return x, None


def _grad_downscale_bwd(scale, _, g):
    return ((g * scale).astype(g.dtype),)


_grad_downscale.defvjp(_grad_downscale_fwd, _grad_downscale_bwd)


#: fsdp (ZeRO-3) all-gather dim per layer leaf, after the leading layer
#: axis is scanned away: column-parallel wq/wk/wv/w_gate/w_up are
#: (d, h)=P(FSDP, TP) -> gather dim 0; row-parallel wo/w_down are
#: (h, d)=P(TP, FSDP) -> gather dim 1. Norm leaves are fsdp-replicated.
_PP_FSDP_DIM = {
    "wq": 0, "wk": 0, "wv": 0, "w_gate": 0, "w_up": 0,
    "wo": 1, "w_down": 1,
}


def _gather_layer_params(lp, fsdp_size):
    """ZeRO-3 gather of one layer's fsdp-sharded matrices. Called inside
    the remat boundary so the backward re-gathers instead of saving the
    full matrices; AD transposes each gather into a reduce-scatter, which
    is exactly the grad layout the fsdp-sharded out_specs expect."""
    if fsdp_size <= 1:
        return lp
    return {
        k: (lax.all_gather(a, FSDP, axis=_PP_FSDP_DIM[k], tiled=True)
            if k in _PP_FSDP_DIM else a)
        for k, a in lp.items()
    }


def _gather_lm_head(lm_head, fsdp_size, tp_size, marker=False):
    """Full (d, vocab) lm_head from its P(FSDP, TP) shard for the stage
    head loss (the weight-gathered limit: no vocab-parallel CE yet).
    Under the marker discipline the head compute downstream carries
    tp-replicated TOTAL cotangents, so the tp gather's reduce-scatter
    transpose needs the 1/tp downscale to stay exact; native AD
    (gpipe) needs no correction."""
    if tp_size > 1:
        if marker:
            lm_head = _grad_downscale(lm_head, 1.0 / tp_size)
        lm_head = lax.all_gather(lm_head, TP, axis=1, tiled=True)
    if fsdp_size > 1:
        lm_head = lax.all_gather(lm_head, FSDP, axis=0, tiled=True)
    return lm_head


#: the names a block gives q, k and v where its attention's backward
#: begins to read them (after rotary; `moe._decoder_layer` with
#: ``qk_norm`` names q and k before their norm, whose backward reads its
#: input). With `attention.KEPT` they are all the flash backward kernels
#: read: a block that keeps both sets (`_maybe_remat`) recomputes neither
#: the forward kernel nor the three projections.
QKV_KEPT = ("attn_q", "attn_k", "attn_v")


def name_qkv(q, k, v):
    """q, k, v under `QKV_KEPT`. A name keeps the named copy and nothing
    else: what is to be spared must read what this returns."""
    return tuple(checkpoint_name(a, name)
                 for a, name in zip((q, k, v), QKV_KEPT))


def report_kept(name: str):
    """The ``recompute(kept=)`` callback of a block that keeps `QKV_KEPT`
    and `attention.KEPT`: the gauges ``attn.qkv_kept`` and
    ``attn.out_kept`` read 1 once a block's checkpoint has kept a q and
    a flash forward's output."""
    attn_ops.report_kept(name)
    if name == QKV_KEPT[0]:
        trace.gauge("attn.qkv_kept", 1)


def swiglu(y, w_gate, w_up, w_down, dt):
    """The dense feed-forward every family shares: ``(silu(y Wg) * (y
    Wu)) Wd``, its two wide activations named for the "mlp" remat
    policy."""
    gate = checkpoint_name(jax.nn.silu(y @ w_gate.astype(dt)), "ffn_gate")
    up = checkpoint_name(y @ w_up.astype(dt), "ffn_up")
    return (gate * up) @ w_down.astype(dt)


def _decoder_layer(cfg: LlamaConfig, mesh, inv_freq, positions, lp, x,
                   attn_fn=None, tp_size=1, tp_mode="native"):
    """One block: pre-norm attention + pre-norm swiglu, residual adds.
    ``attn_fn`` overrides the attention implementation — the pp stages
    pass a manual-axis ring/flash closure since they already sit inside a
    shard_map. ``tp_size > 1`` (full-manual pp stages only) runs the
    megatron tp recipe explicitly: local head/ffn shards with a psum
    closing each row-parallel matmul — native ``lax.psum`` or the f/g
    marker pair depending on the executor's gradient discipline
    (``tp_mode``, see the block comment above)."""
    dt = cfg.dtype
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    marker = tp_size > 1 and tp_mode == "marker"
    if tp_size > 1:
        h //= tp_size
        kvh //= tp_size

    def close_row_parallel(partial):
        if tp_size <= 1:
            return partial
        if marker:
            return _tp_region_out(partial, TP)
        return lax.psum(partial, TP)

    with trace.scope("norm"):
        y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if marker:
        y = _tp_region_in(y, TP)
    with trace.scope("attn_proj"):
        q = (y @ lp["wq"].astype(dt)).reshape(b, s, h, hd)
        k = (y @ lp["wk"].astype(dt)).reshape(b, s, kvh, hd)
        v = (y @ lp["wv"].astype(dt)).reshape(b, s, kvh, hd)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        # rotary is linear: its backward reads the positions alone
        q, k, v = name_qkv(q, k, v)
    if attn_fn is None:
        attn = _attention(cfg, mesh, q, k, v).reshape(b, s, h * hd)
    else:
        attn = attn_fn(q, k, v).reshape(b, s, h * hd)
    with trace.scope("attn_proj"):
        attn = close_row_parallel(attn @ lp["wo"].astype(dt))
    x = x + attn

    with trace.scope("norm"):
        y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if marker:
        y = _tp_region_in(y, TP)
    with trace.scope("dense_mlp"):
        y = close_row_parallel(
            swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"], dt))
    x = x + y

    if mesh is not None:
        from jax.sharding import NamedSharding

        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, SP, None))
        )
    return x


def _maybe_remat(cfg: LlamaConfig, layer_fn):
    """Apply the configured rematerialization policy (one place for the
    policy ladder: forward(), the pp schedule and ``models/moe.py`` must
    never diverge). The block is recomputed; what its attention backward
    reads stays: `QKV_KEPT`, and the flash pair where the attention is
    the flash op (the sp forms name none), tokens a chip x (2 h hd + 2
    kvh hd) x 2 B + tokens x h x 4 B a layer. Under "mlp" the
    feed-forward's two wide activations stay too."""
    keep = attn_ops.KEPT + QKV_KEPT + (
        ("ffn_gate", "ffn_up") if cfg.remat_policy == "mlp" else ())
    trace.gauge("attn.out_kept", 0)  # 1 once a block keeps one
    trace.gauge("attn.qkv_kept", 0)
    return stack.recompute(layer_fn, cfg.remat, keep, report_kept)


def validate_for_mesh(cfg: LlamaConfig, mesh: Mesh, seq_len: int = 0) -> None:
    """Fail fast (trace time) on model-shape / mesh-axis mismatches instead
    of a cryptic shard_map partition error deep in the stack."""
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.parallel.mesh import validate_divisibility

    shape = dict(mesh.shape)
    mc = MeshConfig(
        dp=shape.get("dp", 1), pp=shape.get("pp", 1),
        fsdp=shape.get("fsdp", 1), ep=shape.get("ep", 1),
        sp=shape.get("sp", 1), tp=shape.get("tp", 1),
    )
    validate_divisibility(
        mc,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        seq_len=seq_len or cfg.max_seq_len,
        vocab=cfg.vocab_size,
        n_layers=cfg.n_layers,
    )
    if mc.pp > 1 and mc.sp > 1 and cfg.pp_schedule == "1f1b":
        raise ValueError(
            "pp x sp requires pp_schedule='gpipe': 1f1b gates each tick's "
            "slab behind lax.cond with a pp-rank-dependent predicate, and "
            "ring attention's sp collectives inside a divergent cond "
            "deadlock on TPU (XLA cannot partition them); gpipe's ticks "
            "are unconditional, so sp composes there"
        )
    if (
        cfg.pp_schedule == "1f1b" and mc.pp > 2 and mc.tp > 1
        and mc.dp * mc.fsdp > 1
    ):
        # Empirical XLA limitation (r5 16/32-device stress dryruns): the
        # cond-gated 1f1b schedules at pp>=4 combined with tp plus a
        # second data axis hit a GSPMD partition-group CHECK crash
        # (spmd_partitioner_util.cc:495) while compiling the fused
        # fwd+bwd module — a hard process abort, structure-dependent.
        # gpipe composes fine on the same meshes (unconditional ticks),
        # as does 1f1b with tp folded into fsdp or pp<=2.
        raise ValueError(
            f"pp_schedule='1f1b' with pp={mc.pp}, tp={mc.tp} and "
            f"dp*fsdp={mc.dp * mc.fsdp} crashes XLA's SPMD partitioner "
            "(grouped-collective CHECK). Use pp_schedule='gpipe' for "
            "this mesh, or drop tp (shard those dims over fsdp instead)"
        )
    v = cfg.pp_virtual_stages
    if v > 1 and mc.pp > 1 and mc.sp > 1:
        raise ValueError(
            "interleaved 1f1b (pp_virtual_stages > 1) does not compose "
            "with sp yet; use plain gpipe for pp x sp long-context runs"
        )
    if v > 1 and mc.pp > 1:
        if cfg.n_layers % (mc.pp * v):
            raise ValueError(
                f"n_layers={cfg.n_layers} not divisible by pp*virtual_"
                f"stages={mc.pp * v} (interleaved 1f1b chunking)"
            )
        n_micro = cfg.pp_microbatches or mc.pp
        if n_micro % mc.pp:
            raise ValueError(
                f"interleaved 1f1b needs pp_microbatches % pp == 0 "
                f"(got {n_micro} % {mc.pp})"
            )


def forward_hidden(
    params: Params,
    tokens: jnp.ndarray,  # (b, s) int32
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Final-norm hidden states (b, s, dim) in compute dtype — everything
    up to (but not including) the unembed matmul, so the loss can fuse
    the lm-head into a chunked cross-entropy instead of materializing
    [b, s, vocab] f32 logits."""
    b, s = tokens.shape
    if mesh is not None:
        validate_for_mesh(cfg, mesh, seq_len=s)
    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)

    layer_fn = _maybe_remat(
        cfg, functools.partial(_decoder_layer, cfg, mesh, inv_freq, positions)
    )
    x, _ = lax.scan(
        lambda x, lp: (layer_fn(lp, x), None), x, params["layers"])
    with trace.scope("norm"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def unembed(x: jnp.ndarray, lm_head: jnp.ndarray) -> jnp.ndarray:
    """Dense logits (..., vocab) in f32: bf16 operands + f32 MXU
    accumulation — f32 logits for the loss at bf16 matmul throughput (a
    pure-f32 matmul runs off the MXU fast path)."""
    return lax.dot_general(
        x, lm_head.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def forward(
    params: Params,
    tokens: jnp.ndarray,  # (b, s) int32
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Logits (b, s, vocab) in float32."""
    return unembed(forward_hidden(params, tokens, cfg, mesh),
                   params["lm_head"])


def _ce_sums(logits: jnp.ndarray, tokens: jnp.ndarray):
    """(sum of next-token NLL, count of valid targets); pad tokens < 0
    are ignored. ``logits``/``tokens`` are (mb, s, vocab)/(mb, s)."""
    return _ce_sums_shifted(logits[:, :-1], tokens[:, 1:])


def _ce_sums_shifted(logits: jnp.ndarray, targets: jnp.ndarray):
    """CE sums against PRE-shifted targets (``_shift_targets``) — the form
    the pp stages use: with the sequence axis sharded (sp) the next-token
    shift must happen globally before sharding, not per-chunk."""
    valid = (targets >= 0).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.sum((logz - gold) * valid), jnp.sum(valid)


#: the pp stages' name for it (and `benchmarks/families/`'s)
_shift_targets = stack.shift_targets


def _record_sp_comm(cfg: LlamaConfig, mesh: Mesh, batch: int, seq: int,
                    n_layers: int = 0, calls_per_loss: int = 1):
    """Trace-time comm inventory (profiler/comm.py) for the sp-attention
    collectives: ring kv hops or ulysses all-to-alls. Recorded HERE —
    not inside the ops — because the layer body traces once under
    ``lax.scan``, so only the model knows the per-step multiplicity
    (layers x pipeline ticks). Byte counts are forward-pass volumes;
    the backward roughly doubles them (documented in the tutorial)."""
    sp = mesh.shape.get(SP, 1)
    if sp <= 1:
        return
    from dlrover_tpu.profiler.comm import record_collective

    impl = cfg.attn_impl
    if impl == "auto":
        impl = "ring"
    if impl not in ("ring", "ulysses"):
        return
    L = n_layers or cfg.n_layers
    itemsize = jnp.dtype(cfg.dtype).itemsize
    tp = mesh.shape.get(TP, 1)
    data = max(
        mesh.shape.get(DP, 1) * mesh.shape.get(FSDP, 1)
        * mesh.shape.get(EP, 1), 1,
    )
    bl = max(batch // data, 1)
    s_local = seq // sp
    hd = cfg.head_dim
    hkv_l = max(cfg.n_kv_heads // tp, 1)
    if impl == "ring":
        per_hop = 2 * bl * s_local * hkv_l * hd * itemsize  # K and V
        record_collective(
            "ring_attention.kv_hop", "ppermute", SP, per_hop,
            count=sp * L * calls_per_loss, per="loss_call",
        )
    else:
        h_l = max(cfg.n_heads // tp, 1)
        q_b = bl * s_local * h_l * hd * itemsize
        kv_b = bl * s_local * hkv_l * hd * itemsize
        # GQA below sp: ulysses_attention replicates kv heads by
        # sp/gcd(hkv, sp); the kv all-to-all volume grows accordingly
        rep = 1
        if hkv_l % sp:
            import math

            rep = sp // math.gcd(hkv_l, sp)
        record_collective(
            "ulysses.head_scatter", "all_to_all", SP,
            q_b + 2 * rep * kv_b,
            count=L * calls_per_loss, per="loss_call",
        )
        record_collective(
            "ulysses.head_gather", "all_to_all", SP, q_b,
            count=L * calls_per_loss, per="loss_call",
        )


def _record_tp_comm(cfg: LlamaConfig, mesh: Mesh, batch: int, seq: int,
                    n_layers: int = 0, calls_per_loss: int = 1):
    """Analytic tp inventory: row-parallel outputs (wo, w_down) each
    allreduce a full-size activation over tp, twice per layer. nbytes is
    the standard allreduce algorithm volume per rank (~activation size;
    ring sends 2(n-1)/n of it) — approximate, like NCCL busbw formulas."""
    tp = mesh.shape.get(TP, 1)
    if tp <= 1:
        return
    from dlrover_tpu.profiler.comm import record_collective

    data = max(
        mesh.shape.get(DP, 1) * mesh.shape.get(FSDP, 1)
        * mesh.shape.get(EP, 1), 1,
    )
    bl = max(batch // data, 1)
    s_local = seq // mesh.shape.get(SP, 1)
    act = bl * s_local * cfg.dim * jnp.dtype(cfg.dtype).itemsize
    record_collective(
        "tp.act_allreduce", "psum", TP, act,
        count=2 * (n_layers or cfg.n_layers) * calls_per_loss,
        per="loss_call",
    )


def loss_fn(
    params: Params,
    tokens: jnp.ndarray,  # (b, s) int32; next-token targets derived inside
    cfg: LlamaConfig,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy (pad tokens < 0 are ignored)."""
    if mesh is not None and mesh.shape.get(PP, 1) > 1:
        return _pp_loss(params, tokens, cfg, mesh)
    if mesh is not None:
        _record_sp_comm(cfg, mesh, tokens.shape[0], tokens.shape[1])
        _record_tp_comm(cfg, mesh, tokens.shape[0], tokens.shape[1])
    # the head on all b*s positions, as `forward` + `_ce_sums` does
    x = forward_hidden(params, tokens, cfg, mesh)
    return stack.next_token_loss(
        x, params["lm_head"], tokens, cfg.ce_chunk_size, mesh)


def _pp_loss(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    mesh: Mesh,
) -> jnp.ndarray:
    """Entry: the pp schedules use partial-manual shard_map, whose eager
    execution path is unsupported in current JAX when the mesh carries
    extra (auto) axes — always route through a (cached) jit; under the
    trainer's jit this is just an inlined call, and direct eager calls
    (tests, notebooks) keep working."""
    # comm inventory HERE, not inside the cached jit: a ledger.clear()
    # (new trainer) followed by a cache-hit trace would otherwise leave
    # the pp rows unrecorded; this entry runs per call and records are
    # idempotent
    _record_pp_comm(cfg, mesh, tokens.shape[0], tokens.shape[1])
    from dlrover_tpu.ops import fused_ce_enabled

    return _jitted_pp_loss(cfg, mesh, fused_ce_enabled())(params, tokens)


def _record_pp_comm(cfg: LlamaConfig, mesh: Mesh, b: int, s: int):
    from dlrover_tpu.profiler.comm import record_collective

    pp_size = mesh.shape[PP]
    sp_size = mesh.shape.get(SP, 1)
    n_micro = cfg.pp_microbatches or pp_size
    if b % n_micro:
        return  # the loss itself will raise with a clear message
    mb = b // n_micro
    s_local = s // sp_size
    act_bytes = mb * s_local * cfg.dim * jnp.dtype(cfg.dtype).itemsize
    if cfg.pp_schedule == "1f1b":
        if cfg.pp_virtual_stages > 1:
            from dlrover_tpu.parallel.pp_schedule import (
                build_interleaved_tables,
            )

            n_ticks = build_interleaved_tables(
                pp_size, cfg.pp_virtual_stages, n_micro
            ).T
        else:
            n_ticks = 2 * (n_micro + pp_size - 1)
        record_collective("pp.act_hop", "ppermute", PP, act_bytes,
                          count=n_ticks, per="loss_call")
        record_collective("pp.grad_hop", "ppermute", PP, act_bytes,
                          count=n_ticks, per="loss_call")
        # tp inside the stages: the 1f1b conds SKIP compute on bubble
        # ticks, so exactly n_micro forward + n_micro backward slab
        # passes run, each over the rank's L/pp layers. (No sp record:
        # validate_for_mesh rejects 1f1b x sp.)
        _record_tp_comm(
            cfg, mesh, mb, s, n_layers=cfg.n_layers // pp_size,
            calls_per_loss=2 * n_micro,
        )
        return
    n_ticks = n_micro + pp_size - 1
    record_collective("pp.act_hop", "ppermute", PP, act_bytes,
                      count=n_ticks, per="loss_call")
    # gpipe's backward is pure autodiff: AD transposes every ppermute
    # into a reverse hop of the same size, once per tick
    record_collective("pp.grad_hop", "ppermute", PP, act_bytes,
                      count=n_ticks, per="loss_call")
    if sp_size > 1:
        # gpipe x sp composition: each tick runs a slab of L/pp layers
        # with ring/ulysses attention inside
        _record_sp_comm(
            cfg, mesh, mb, s, n_layers=cfg.n_layers // pp_size,
            calls_per_loss=n_ticks,
        )
    # tp inside stages: n_ticks forward slabs + autodiff backward again.
    # Deliberately n_TICKS, not n_micro: gpipe's scan body is
    # unconditional (XLA-friendly), so bubble ticks execute masked slabs
    # and their collectives really run — unlike 1f1b's cond-gated ticks
    _record_tp_comm(
        cfg, mesh, mb, s, n_layers=cfg.n_layers // pp_size,
        calls_per_loss=2 * n_ticks,
    )


@functools.lru_cache(maxsize=32)
def _jitted_pp_loss(cfg: LlamaConfig, mesh: Mesh, fused_ce: bool = True):
    # ``fused_ce`` is part of the cache KEY only: cross_entropy_sums
    # re-reads the env var at trace time (which happens on the first
    # call for this key, when the env still matches), so toggling
    # DLROVER_TPU_FUSED_CE between calls retraces instead of silently
    # reusing the other path's cached program.
    return jax.jit(
        functools.partial(_pp_loss_impl, cfg=cfg, mesh=mesh)
    )


def _pp_loss_impl(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    mesh: Mesh,
) -> jnp.ndarray:
    """Pipeline parallelism over the ``pp`` mesh axis, TPU-native.

    The reference is only checkpoint-aware of PP (megatron_dist_ckpt.py:
    262,489 there — Megatron owns the schedule); here the schedule itself
    is built from JAX primitives: layer-stacked params are sharded
    ``P(pp)`` on the layer axis so each stage holds a contiguous slab, and
    a ``shard_map`` manual over EVERY mesh axis runs the schedule with
    explicit collectives — ``ppermute`` stage handoffs on pp, megatron
    tp psums, ZeRO-3 fsdp gathers — with nothing left to the automatic
    partitioner.

    Two schedules (``cfg.pp_schedule``):

    - **gpipe**: ``n_micro + pp - 1`` ticks of (run my slab) →
      (``ppermute`` the activation onward); autodiff through scan +
      ppermute yields the reverse pipeline. Simplest; activation memory
      grows with ``n_micro``.
    - **1f1b**: explicit fused forward+backward schedule (``_pp_1f1b``) —
      one-forward-one-backward in steady state, at most ``pp`` microbatch
      boundary activations live per stage.

    **sp composition**: with sp>1 the stages run manual over {pp, sp};
    the sequence axis is sharded and attention runs on the sp axis
    directly — ring (ppermute K/V hops) or ulysses (all-to-all head
    scatter) per ``attn_impl``; both are written to be called inside a
    manual region.
    """
    pp_size = mesh.shape[PP]
    sp_size = mesh.shape.get(SP, 1)
    n_micro = cfg.pp_microbatches or pp_size
    b, s = tokens.shape
    if b % n_micro:
        raise ValueError(f"batch={b} not divisible by pp_microbatches={n_micro}")
    mb = b // n_micro
    validate_for_mesh(cfg, mesh, seq_len=s)
    dp_size, fsdp_size, ep_size, _ = _pp_sizes(mesh)
    data_shards = dp_size * fsdp_size * ep_size
    if mb % data_shards:
        raise ValueError(
            f"microbatch rows={mb} not divisible by the data shards "
            f"dp*fsdp*ep={data_shards}"
        )
    s_local = s // sp_size

    from jax.sharding import NamedSharding

    x = embed_lookup(params["embed"], tokens, mesh, cfg.dtype)  # (b, s, d)
    # keep the data axes on the *per-microbatch* batch dim: if the reshape
    # left dp on the microbatch-index dim, every tick's dynamic_index
    # would gather across dp shards (and trip XLA's grouped-collective
    # partitioner under the manual pp axis)
    x_micro = lax.with_sharding_constraint(
        x.reshape(n_micro, mb, s, cfg.dim),
        NamedSharding(mesh, P(None, BATCH_AXES, SP, None)),
    )
    # next-token shift happens globally BEFORE any seq sharding
    tgt_micro = lax.with_sharding_constraint(
        _shift_targets(tokens).reshape(n_micro, mb, s),
        NamedSharding(mesh, P(None, BATCH_AXES, SP)),
    )
    if cfg.pp_schedule == "1f1b":
        static = _PPStatic(cfg, mesh, pp_size, sp_size, n_micro, mb, s_local)
        return _pp_1f1b_call(
            static, params["layers"], x_micro,
            params["final_norm"], params["lm_head"], tgt_micro,
        )
    return _pp_gpipe(
        cfg, mesh, pp_size, sp_size, n_micro, mb, s_local,
        params, x_micro, tgt_micro,
    )


#: full-manual in_specs for (x_micro, tgt_micro): microbatch index dim
#: replicated, per-microbatch batch dim over the data axes, seq over sp
_PP_X_SPEC = P(None, BATCH_AXES, SP, None)
_PP_T_SPEC = P(None, BATCH_AXES, SP)
#: loss-sum reduction axes: every mesh axis EXCEPT tp — the head compute
#: between the f/g markers is tp-replicated, so its sums are already
#: totals on each tp rank and a tp psum would overcount
_PP_LOSS_AXES = (DP, PP, FSDP, EP, SP)


def _pp_sizes(mesh: Mesh):
    """(dp, fsdp, ep, tp) sizes the full-manual stages collect over."""
    shape = dict(mesh.shape)
    return (shape.get(DP, 1), shape.get(FSDP, 1), shape.get(EP, 1),
            shape.get(TP, 1))


def _pp_layer_specs(cfg: LlamaConfig, pp_size: int):
    """Per-leaf in/out specs for the layer stack under full-manual pp:
    the param_specs tp/fsdp layout with the layer axis over pp."""
    return param_specs(cfg, pp=pp_size)["layers"]


def _stage_layer_fn(cfg: LlamaConfig, mb: int, s_local: int, sp_size: int,
                    fsdp_size: int = 1, tp_size: int = 1,
                    tp_mode: str = "native"):
    """Build the per-stage decoder-layer fn INSIDE the manual region:
    positions carry each sp rank's global sequence offset, attention is
    ring-on-sp (already inside the manual axes) or flash, fsdp matrices
    are ZeRO-3-gathered per layer inside the remat boundary, and tp runs
    the explicit megatron recipe (``_decoder_layer(tp_size=...)``).
    ``mb`` is the LOCAL per-data-shard microbatch rows."""
    inv_freq = rope_frequencies(cfg.head_dim, cfg.rope_theta)
    if sp_size > 1:
        offset = lax.axis_index(SP) * s_local
        if cfg.attn_impl == "ulysses":
            from dlrover_tpu.ops.ulysses import ulysses_attention as sp_attn
        else:
            sp_attn = ring_attention
        attn_fn = functools.partial(sp_attn, axis_name=SP, causal=True)
    else:
        offset = 0
        attn_fn = None  # _attention(mesh=None) -> flash
    positions = jnp.broadcast_to(
        jnp.arange(s_local, dtype=jnp.int32) + offset, (mb, s_local)
    )
    # mesh=None inside the manual region: NamedSharding constraints on
    # the concrete mesh clash with the Manual context mesh; tp/fsdp
    # placement inside stages is explicit (megatron markers + ZeRO-3
    # gathers), never left to the partitioner
    base = functools.partial(
        _decoder_layer, cfg, None, inv_freq, positions, attn_fn=attn_fn,
        tp_size=tp_size, tp_mode=tp_mode,
    )
    if fsdp_size > 1:
        def layer_fn(lp, x):
            return base(_gather_layer_params(lp, fsdp_size), x)
    else:
        layer_fn = base
    return _maybe_remat(cfg, layer_fn)


def _head_loss_sums(cfg: LlamaConfig, out, final_norm, lm_head, tgt):
    """(nll_sum, n_valid) of one microbatch's slab output. The chunked-CE
    op broadcasts over leading dims without reshapes, so it composes
    inside the pp shard_map manual regions (and under the jax.vjp /
    value_and_grad the 1f1b schedule takes through this function)."""
    h = rms_norm(out, final_norm, cfg.norm_eps)
    return cross_entropy_sums(h, lm_head, tgt, chunk_size=cfg.ce_chunk_size)


def _pp_gpipe(
    cfg, mesh, pp_size, sp_size, n_micro, mb, s_local, params,
    x_micro, tgt_micro,
) -> jnp.ndarray:
    """GPipe under full-manual shard_map: every mesh axis is manual, the
    stages run explicit tp/fsdp collectives (``_stage_layer_fn``), and
    the backward pipeline is pure autodiff — shard_map's transpose psums
    each input's cotangent over its unmentioned axes, which is exactly
    the dp/ep/fsdp/tp data reduction (``tp_mode="native"``: no markers,
    jax's scaled-partial cotangent discipline is exact on its own)."""
    from jax import shard_map

    dp_size, fsdp_size, ep_size, tp_size = _pp_sizes(mesh)
    mb_l = mb // (dp_size * fsdp_size * ep_size)
    n_ticks = n_micro + pp_size - 1
    fwd_perm = [(i, i + 1) for i in range(pp_size - 1)]

    def stage(layers_local, x_mb, tgt_mb, final_norm, lm_head):
        rank = lax.axis_index(PP)
        layer_fn = _stage_layer_fn(
            cfg, mb_l, s_local, sp_size, fsdp_size, tp_size,
            tp_mode="native",
        )

        def run_slab(h):
            def body(carry, lp):
                return layer_fn(lp, carry), None

            out, _ = lax.scan(body, h, layers_local)
            return out

        def tick(carry, t):
            recv, outs = carry
            mb_in = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(
                rank == 0,
                lax.dynamic_index_in_dim(x_mb, mb_in, keepdims=False),
                recv,
            )
            with trace.scope("stage_fwd"):
                out = run_slab(inp)
            with trace.scope("pp_send_recv"):
                recv_next = lax.ppermute(out, PP, fwd_perm)
            # collect finished microbatches (real only on the last stage;
            # early bubble writes land on index 0 and are overwritten by
            # the first valid tick)
            mb_out = jnp.clip(t - (pp_size - 1), 0, n_micro - 1)
            outs = lax.dynamic_update_index_in_dim(outs, out, mb_out, 0)
            return (recv_next, outs), None

        init = (
            jnp.zeros((mb_l, s_local, cfg.dim), cfg.dtype),
            jnp.zeros((n_micro, mb_l, s_local, cfg.dim), cfg.dtype),
        )
        (_, outs), _ = lax.scan(
            tick, init, jnp.arange(n_ticks, dtype=jnp.int32)
        )
        # head + loss: the collected activations are real only on the
        # last stage, but the lm_head matmul is ~10% of model FLOPs at
        # 8B scale — burning it on every rank and masking would waste
        # (pp-1)/pp of it. Instead psum_scatter hands each rank 1/pp of
        # the row axis (non-last ranks contribute zeros, so each chunk
        # IS the last stage's data), every rank computes the head for
        # its chunk, and the CE sums psum back together.
        rows = n_micro * mb_l
        pad = (-rows) % pp_size
        is_last = (rank == pp_size - 1).astype(outs.dtype)
        outs_flat = outs.reshape(rows, s_local, cfg.dim) * is_last
        tgts_flat = tgt_mb.reshape(rows, s_local)
        if pad:
            outs_flat = jnp.concatenate(
                [outs_flat, jnp.zeros((pad, s_local, cfg.dim), outs_flat.dtype)]
            )
            tgts_flat = jnp.concatenate(
                [tgts_flat, jnp.full((pad, s_local), -1, tgts_flat.dtype)]
            )
        chunk = (rows + pad) // pp_size
        my_rows = lax.psum_scatter(
            outs_flat, PP, scatter_dimension=0, tiled=True
        )
        my_tgts = lax.dynamic_slice_in_dim(tgts_flat, rank * chunk, chunk, 0)
        nll_sum, n_valid = _head_loss_sums(
            cfg, my_rows, final_norm,
            _gather_lm_head(lm_head, fsdp_size, tp_size), my_tgts,
        )
        nll_sum = lax.psum(nll_sum, _PP_LOSS_AXES)
        n_valid = lax.psum(n_valid, _PP_LOSS_AXES)
        return nll_sum / jnp.maximum(n_valid, 1.0)

    pipe = shard_map(
        stage,
        mesh=mesh,
        in_specs=(
            _pp_layer_specs(cfg, pp_size),
            _PP_X_SPEC, _PP_T_SPEC, P(), P(FSDP, TP),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return pipe(
        params["layers"], x_micro, tgt_micro,
        params["final_norm"], params["lm_head"],
    )


# ---------------------------------------------------------------------------
# 1F1B: fused forward+backward pipeline schedule
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PPStatic:
    """Hashable schedule geometry for the custom_vjp nondiff arg."""

    cfg: LlamaConfig
    mesh: Mesh
    pp: int
    sp: int
    n_micro: int
    mb: int
    s_local: int


def _pp_1f1b_run(static: _PPStatic, layers, x_micro, final_norm, lm_head,
                 tgt_micro):
    """One fused pass computing (loss, grads) under the 1F1B schedule.

    Timeline (half-step ticks, T = 2*(n_micro + pp - 1)): stage r runs the
    forward of microbatch i at tick ``r + 2i`` and its backward at tick
    ``(2*pp - 1 - r) + 2i`` — warmup of depth pp-r, then strict
    one-forward-one-backward alternation, then cooldown. Each stage keeps
    at most ``pp`` saved boundary activations (``act_buf``); the backward
    recomputes the slab interior from the saved input (the same remat
    policy as forward), exactly Megatron's memory profile.

    Gradients are produced manually inside the schedule (``jax.vjp`` per
    slab, head grads at the last stage's forward tick) because fwd and
    bwd of *different* microbatches must interleave within one scan —
    jax.grad over a forward-only schedule can only produce GPipe.
    """
    cfg, mesh = static.cfg, static.mesh
    pp_size, sp_size = static.pp, static.sp
    n_micro, mb, s_local = static.n_micro, static.mb, static.s_local
    from jax import shard_map

    if cfg.pp_virtual_stages > 1:
        return _pp_interleaved_run(
            static, layers, x_micro, final_norm, lm_head, tgt_micro
        )

    T = 2 * (n_micro + pp_size - 1)
    fwd_perm = [(i, i + 1) for i in range(pp_size - 1)]
    bwd_perm = [(i + 1, i) for i in range(pp_size - 1)]
    dp_size, fsdp_size, ep_size, tp_size = _pp_sizes(mesh)
    mb_l = mb // (dp_size * fsdp_size * ep_size)
    f32 = jnp.float32

    def stage(layers_local, x_mb, tgt_mb, final_norm, lm_head):
        rank = lax.axis_index(PP)
        is_first = rank == 0
        is_last = rank == pp_size - 1
        layer_fn = _stage_layer_fn(
            cfg, mb_l, s_local, sp_size, fsdp_size, tp_size,
            tp_mode="marker",
        )

        def run_slab(layers_, h):
            def body(carry, lp):
                return layer_fn(lp, carry), None

            out, _ = lax.scan(body, h, layers_)
            return out

        act_shape = (mb_l, s_local, cfg.dim)

        def head_grads(out, tgt):
            """Last stage only: loss sums + d(nll)/d(out, final_norm,
            lm_head) for one microbatch."""

            def nll_of(o, fn, lm):
                nll, nv = _head_loss_sums(
                    cfg, o, fn,
                    _gather_lm_head(lm, fsdp_size, tp_size, marker=True),
                    tgt,
                )
                return nll, nv

            (nll, nv), grads = jax.value_and_grad(
                nll_of, argnums=(0, 1, 2), has_aux=True
            )(out, final_norm, lm_head)
            return nll, nv, grads[0].astype(cfg.dtype), grads[1], grads[2]

        def zero_head(out, tgt):
            return (
                jnp.zeros((), f32), jnp.zeros((), f32),
                jnp.zeros(act_shape, cfg.dtype),
                jnp.zeros_like(final_norm), jnp.zeros_like(lm_head),
            )

        g_layers0 = jax.tree.map(jnp.zeros_like, layers_local)

        def tick(carry, t):
            (recv_act, recv_grad, act_buf, gin_buf,
             g_layers, g_fn, g_lm, g_x, nll, nv) = carry

            tf = t - rank
            do_fwd = (tf >= 0) & (tf < 2 * n_micro) & (tf % 2 == 0)
            i_f = jnp.clip(tf // 2, 0, n_micro - 1)
            tb = t - (2 * pp_size - 1 - rank)
            do_bwd = (tb >= 0) & (tb < 2 * n_micro) & (tb % 2 == 0)
            i_b = jnp.clip(tb // 2, 0, n_micro - 1)

            # ---- forward op (heavy compute only when scheduled) -------
            def fwd_branch(ops):
                act_buf, gin_buf, nll, nv, g_fn, g_lm = ops
                inp = jnp.where(
                    is_first,
                    lax.dynamic_index_in_dim(x_mb, i_f, keepdims=False),
                    recv_act,
                )
                with trace.scope("stage_fwd"):
                    out = run_slab(layers_local, inp)
                act_buf = lax.dynamic_update_index_in_dim(
                    act_buf, inp, i_f % pp_size, 0
                )
                tgt = lax.dynamic_index_in_dim(tgt_mb, i_f, keepdims=False)
                nll_i, nv_i, d_out, d_fn, d_lm = lax.cond(
                    is_last, head_grads, zero_head, out, tgt
                )
                gin_buf = lax.dynamic_update_index_in_dim(
                    gin_buf, d_out, i_f % pp_size, 0
                )
                return (act_buf, gin_buf, nll + nll_i, nv + nv_i,
                        jax.tree.map(jnp.add, g_fn, d_fn),
                        jax.tree.map(jnp.add, g_lm, d_lm)), out

            def fwd_skip(ops):
                return ops, jnp.zeros(act_shape, cfg.dtype)

            (act_buf, gin_buf, nll, nv, g_fn, g_lm), out = lax.cond(
                do_fwd, fwd_branch, fwd_skip,
                (act_buf, gin_buf, nll, nv, g_fn, g_lm),
            )
            # collective OUTSIDE the cond: every rank participates
            with trace.scope("pp_send_recv"):
                recv_act = lax.ppermute(out, PP, fwd_perm)

            # ---- backward op ------------------------------------------
            def bwd_branch(ops):
                g_layers, g_x = ops
                g_out = jnp.where(
                    is_last,
                    lax.dynamic_index_in_dim(
                        gin_buf, i_b % pp_size, keepdims=False
                    ),
                    recv_grad,
                )
                inp = lax.dynamic_index_in_dim(
                    act_buf, i_b % pp_size, keepdims=False
                )
                with trace.scope("stage_bwd"):
                    _, pull = jax.vjp(run_slab, layers_local, inp)
                    gl, gx = pull(g_out)
                g_layers = jax.tree.map(jnp.add, g_layers, gl)
                g_x = jnp.where(
                    is_first,
                    lax.dynamic_update_index_in_dim(
                        g_x, gx.astype(g_x.dtype), i_b, 0
                    ),
                    g_x,
                )
                return (g_layers, g_x), gx

            def bwd_skip(ops):
                return ops, jnp.zeros(act_shape, cfg.dtype)

            (g_layers, g_x), gx = lax.cond(
                do_bwd, bwd_branch, bwd_skip, (g_layers, g_x)
            )
            with trace.scope("pp_send_recv"):
                recv_grad = lax.ppermute(gx, PP, bwd_perm)

            return (recv_act, recv_grad, act_buf, gin_buf,
                    g_layers, g_fn, g_lm, g_x, nll, nv), None

        init = (
            jnp.zeros(act_shape, cfg.dtype),                    # recv_act
            jnp.zeros(act_shape, cfg.dtype),                    # recv_grad
            jnp.zeros((pp_size,) + act_shape, cfg.dtype),       # act_buf
            jnp.zeros((pp_size,) + act_shape, cfg.dtype),       # gin_buf
            g_layers0,
            jnp.zeros_like(final_norm),
            jnp.zeros_like(lm_head),
            jnp.zeros((n_micro,) + act_shape, cfg.dtype),       # g_x
            jnp.zeros((), f32),                                 # nll
            jnp.zeros((), f32),                                 # nv
        )
        (_, _, _, _, g_layers, g_fn, g_lm, g_x, nll, nv), _ = lax.scan(
            tick, init, jnp.arange(T, dtype=jnp.int32)
        )
        nll = lax.psum(nll, _PP_LOSS_AXES)
        nv = lax.psum(nv, _PP_LOSS_AXES)
        loss = nll / jnp.maximum(nv, 1.0)
        # d(mean)/d(sums): grads above are for nll_sum; scale to the mean
        scale = (1.0 / jnp.maximum(nv, 1.0)).astype(f32)
        g_layers = jax.tree.map(
            lambda a: (a.astype(f32) * scale).astype(a.dtype), g_layers
        )
        g_x = (g_x.astype(f32) * scale).astype(cfg.dtype)
        g_fn = g_fn * scale
        g_lm = (g_lm.astype(f32) * scale).astype(g_lm.dtype)
        # End-of-schedule reductions (the hand-scheduled backward never
        # crossed a shard_map boundary, so the data reductions native AD
        # would get from the transpose happen here explicitly):
        # - dp/ep/sp replicas each saw their own rows -> sum layer/head
        #   grads over the data axes
        # - fsdp: matrix-leaf grads were already reduce-scattered by the
        #   gather transpose inside the slab vjp; fsdp-replicated leaves
        #   (norms, final_norm) saw fsdp's share of the batch -> sum
        # - tp: the marker discipline keeps tp-replicated cotangents as
        #   true totals -> never sum over tp
        # - pp: head grads / g_x are real on one stage only -> replicate
        data_axes = (DP, EP, SP)
        g_layers = {
            k: lax.psum(
                a, data_axes if k in _PP_FSDP_DIM else data_axes + (FSDP,)
            )
            for k, a in g_layers.items()
        }
        g_fn = lax.psum(g_fn, data_axes + (FSDP, PP))
        g_lm = lax.psum(g_lm, data_axes + (PP,))
        g_x = lax.psum(g_x, PP)
        return loss, g_layers, g_x, g_fn, g_lm

    layer_specs = _pp_layer_specs(cfg, pp_size)
    pipe = shard_map(
        stage,
        mesh=mesh,
        in_specs=(layer_specs, _PP_X_SPEC, _PP_T_SPEC, P(), P(FSDP, TP)),
        out_specs=(P(), layer_specs, _PP_X_SPEC, P(), P(FSDP, TP)),
        check_vma=False,
    )
    loss, g_layers, g_x, g_fn, g_lm = pipe(
        layers, x_micro, tgt_micro, final_norm, lm_head
    )
    return loss, (g_layers, g_x, g_fn, g_lm)


def _pp_interleaved_run(static: _PPStatic, layers, x_micro, final_norm,
                        lm_head, tgt_micro):
    """Interleaved (virtual-stage) 1F1B: one fused pass computing
    (loss, grads) from the static op tables of
    ``parallel/pp_schedule.py``.

    The model's ``pp * v`` chunks are placed chunk ``c`` -> rank
    ``c % pp`` (Megatron layout), so every activation/grad hop is a
    uniform wrapping ring ``ppermute`` (+1 fwd, -1 bwd) and the bubble
    shrinks by the factor ``v`` the step-count model proves
    (``PPScheduleTables.bubble_ticks``). Each scan tick looks up its op
    in the tables: a forward of (microbatch ``f_i``, virtual stage
    ``f_u``) and/or a buffer store of the activation arriving on the
    wire. Buffers are ``(v, n_slots)`` slots keyed ``(u, i % n_slots)``
    — the builder proves slot liveness never overlaps.

    Layer params stay CANONICALLY ordered in the train state (so
    checkpoints are layout-independent); the rank-major gather needed by
    the ``P(pp)`` sharding happens here, and gradients are scattered
    back through the inverse permutation.

    Reference parity: the reference handles virtual PP stages only in
    its Megatron checkpoint integration
    (``megatron_dist_ckpt.py:262,489``); the schedule itself is this
    repo's TPU-native construction.
    """
    import numpy as np

    from dlrover_tpu.parallel.pp_schedule import (
        build_interleaved_tables,
        interleave_layer_perm,
    )

    cfg, mesh = static.cfg, static.mesh
    pp_size, sp_size = static.pp, static.sp
    n_micro, mb, s_local = static.n_micro, static.mb, static.s_local
    v = cfg.pp_virtual_stages
    if sp_size > 1:
        raise ValueError("interleaved 1f1b does not compose with sp yet")
    from jax import shard_map

    tables = build_interleaved_tables(pp_size, v, n_micro)
    dev_tables = {
        k: jnp.asarray(val) for k, val in tables.as_device_tables().items()
    }
    S = tables.n_slots
    Lc = cfg.n_layers // (pp_size * v)
    if cfg.pp_interleave_layout == "rank_major":
        # state already rank-major (interleave_layers): no per-step
        # cross-rank layer movement
        layers_rm = layers
        inv_perm = None
    else:
        perm = interleave_layer_perm(cfg.n_layers, pp_size, v)
        inv_perm = np.argsort(perm)
        layers_rm = jax.tree.map(lambda a: a[perm], layers)  # rank-major

    ring_fwd = [(i, (i + 1) % pp_size) for i in range(pp_size)]
    ring_bwd = [(i, (i - 1) % pp_size) for i in range(pp_size)]
    dp_size, fsdp_size, ep_size, tp_size = _pp_sizes(mesh)
    mb_l = mb // (dp_size * fsdp_size * ep_size)
    f32 = jnp.float32

    def stage(layers_local, x_mb, tgt_mb, final_norm, lm_head):
        rank = lax.axis_index(PP)
        is_last = rank == pp_size - 1
        layer_fn = _stage_layer_fn(
            cfg, mb_l, s_local, 1, fsdp_size, tp_size, tp_mode="marker"
        )
        act_shape = (mb_l, s_local, cfg.dim)

        def run_chunk(layers_, h):
            def body(carry, lp):
                return layer_fn(lp, carry), None

            out, _ = lax.scan(body, h, layers_)
            return out

        def chunk_params(u):
            return jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, u * Lc, Lc, 0),
                layers_local,
            )

        def b_get(buf, u, s):
            return lax.dynamic_slice(
                buf, (u, s, 0, 0, 0), (1, 1) + act_shape
            ).reshape(act_shape)

        def b_set(buf, val, u, s):
            return lax.dynamic_update_slice(
                buf, val[None, None], (u, s, 0, 0, 0)
            )

        def head_grads(out, tgt):
            def nll_of(o, fn, lm):
                nll, nv = _head_loss_sums(
                    cfg, o, fn,
                    _gather_lm_head(lm, fsdp_size, tp_size, marker=True),
                    tgt,
                )
                return nll, nv

            (nll, nv), grads = jax.value_and_grad(
                nll_of, argnums=(0, 1, 2), has_aux=True
            )(out, final_norm, lm_head)
            return nll, nv, grads[0].astype(cfg.dtype), grads[1], grads[2]

        def zero_head(out, tgt):
            return (
                jnp.zeros((), f32), jnp.zeros((), f32),
                jnp.zeros(act_shape, cfg.dtype),
                jnp.zeros_like(final_norm), jnp.zeros_like(lm_head),
            )

        def tick(carry, xs):
            (wire_f, wire_b, recv_act, recv_grad, act_saved,
             g_layers, g_fn, g_lm, g_x, nll, nv) = carry

            # -- ring delivery of the previous tick's outputs ----------
            with trace.scope("pp_send_recv"):
                win_f = lax.ppermute(wire_f, PP, ring_fwd)
                win_b = lax.ppermute(wire_b, PP, ring_bwd)

            def pick(name):
                return lax.dynamic_index_in_dim(
                    xs[name], rank, keepdims=False
                )

            recv_act = lax.cond(
                pick("rf_do"),
                lambda b: b_set(b, win_f, pick("rf_u"), pick("rf_s")),
                lambda b: b, recv_act,
            )
            recv_grad = lax.cond(
                pick("rb_do"),
                lambda b: b_set(b, win_b, pick("rb_u"), pick("rb_s")),
                lambda b: b, recv_grad,
            )

            f_i, f_u = pick("f_i"), pick("f_u")
            b_i, b_u = pick("b_i"), pick("b_u")

            # -- forward chunk op --------------------------------------
            def fwd_branch(ops):
                recv_act, act_saved, recv_grad, g_fn, g_lm, nll, nv = ops
                inp = jnp.where(
                    (rank == 0) & (f_u == 0),
                    lax.dynamic_index_in_dim(x_mb, f_i, keepdims=False),
                    b_get(recv_act, f_u, f_i % S),
                )
                with trace.scope("stage_fwd"):
                    out = run_chunk(chunk_params(f_u), inp)
                act_saved = b_set(act_saved, inp, f_u, f_i % S)
                is_lastc = is_last & (f_u == v - 1)
                tgt = lax.dynamic_index_in_dim(tgt_mb, f_i, keepdims=False)
                nll_i, nv_i, d_out, d_fn, d_lm = lax.cond(
                    is_lastc, head_grads, zero_head, out, tgt
                )
                recv_grad = lax.cond(
                    is_lastc,
                    lambda b: b_set(b, d_out, v - 1, f_i % S),
                    lambda b: b, recv_grad,
                )
                return (recv_act, act_saved, recv_grad, g_fn + d_fn,
                        g_lm + d_lm, nll + nll_i, nv + nv_i), out

            def fwd_skip(ops):
                return ops, jnp.zeros(act_shape, cfg.dtype)

            (recv_act, act_saved, recv_grad, g_fn, g_lm, nll, nv), wire_f = (
                lax.cond(
                    pick("f_do"), fwd_branch, fwd_skip,
                    (recv_act, act_saved, recv_grad, g_fn, g_lm, nll, nv),
                )
            )

            # -- backward chunk op -------------------------------------
            def bwd_branch(ops):
                g_layers, g_x = ops
                g_out = b_get(recv_grad, b_u, b_i % S)
                inp = b_get(act_saved, b_u, b_i % S)
                with trace.scope("stage_bwd"):
                    _, pull = jax.vjp(run_chunk, chunk_params(b_u), inp)
                    gl, gx = pull(g_out)

                def acc(dst, g):
                    cur = lax.dynamic_slice_in_dim(dst, b_u * Lc, Lc, 0)
                    return lax.dynamic_update_slice_in_dim(
                        dst, cur + g, b_u * Lc, 0
                    )

                g_layers = jax.tree.map(acc, g_layers, gl)
                g_x = jnp.where(
                    (rank == 0) & (b_u == 0),
                    lax.dynamic_update_index_in_dim(
                        g_x, gx.astype(g_x.dtype), b_i, 0
                    ),
                    g_x,
                )
                return (g_layers, g_x), gx

            def bwd_skip(ops):
                return ops, jnp.zeros(act_shape, cfg.dtype)

            (g_layers, g_x), wire_b = lax.cond(
                pick("b_do"), bwd_branch, bwd_skip, (g_layers, g_x)
            )

            return (wire_f, wire_b, recv_act, recv_grad, act_saved,
                    g_layers, g_fn, g_lm, g_x, nll, nv), None

        init = (
            jnp.zeros(act_shape, cfg.dtype),              # wire_f
            jnp.zeros(act_shape, cfg.dtype),              # wire_b
            jnp.zeros((v, S) + act_shape, cfg.dtype),     # recv_act
            jnp.zeros((v, S) + act_shape, cfg.dtype),     # recv_grad
            jnp.zeros((v, S) + act_shape, cfg.dtype),     # act_saved
            jax.tree.map(jnp.zeros_like, layers_local),
            jnp.zeros_like(final_norm),
            jnp.zeros_like(lm_head),
            jnp.zeros((n_micro,) + act_shape, cfg.dtype),  # g_x
            jnp.zeros((), f32),                            # nll
            jnp.zeros((), f32),                            # nv
        )
        carry, _ = lax.scan(tick, init, dev_tables)
        (_, _, _, _, _, g_layers, g_fn, g_lm, g_x, nll, nv) = carry
        nll = lax.psum(nll, _PP_LOSS_AXES)
        nv = lax.psum(nv, _PP_LOSS_AXES)
        loss = nll / jnp.maximum(nv, 1.0)
        scale = (1.0 / jnp.maximum(nv, 1.0)).astype(f32)
        g_layers = jax.tree.map(
            lambda a: (a.astype(f32) * scale).astype(a.dtype), g_layers
        )
        g_x = (g_x.astype(f32) * scale).astype(cfg.dtype)
        g_fn = g_fn * scale
        g_lm = (g_lm.astype(f32) * scale).astype(g_lm.dtype)
        # same explicit end-of-schedule reductions as plain 1f1b (see
        # there): data axes summed, fsdp already scattered for matrix
        # leaves, tp never summed (marker discipline), pp replicated
        data_axes = (DP, EP, SP)
        g_layers = {
            k: lax.psum(
                a, data_axes if k in _PP_FSDP_DIM else data_axes + (FSDP,)
            )
            for k, a in g_layers.items()
        }
        g_fn = lax.psum(g_fn, data_axes + (FSDP, PP))
        g_lm = lax.psum(g_lm, data_axes + (PP,))
        g_x = lax.psum(g_x, PP)
        return loss, g_layers, g_x, g_fn, g_lm

    layer_specs = _pp_layer_specs(cfg, pp_size)
    pipe = shard_map(
        stage,
        mesh=mesh,
        in_specs=(layer_specs, _PP_X_SPEC, _PP_T_SPEC, P(), P(FSDP, TP)),
        out_specs=(P(), layer_specs, _PP_X_SPEC, P(), P(FSDP, TP)),
        check_vma=False,
    )
    loss, g_layers_rm, g_x, g_fn, g_lm = pipe(
        layers_rm, x_micro, tgt_micro, final_norm, lm_head
    )
    if inv_perm is None:
        return loss, (g_layers_rm, g_x, g_fn, g_lm)
    # grads back to the canonical layer order of the train state
    g_layers = jax.tree.map(lambda a: a[inv_perm], g_layers_rm)
    return loss, (g_layers, g_x, g_fn, g_lm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pp_1f1b_call(static, layers, x_micro, final_norm, lm_head, tgt_micro):
    loss, _ = _pp_1f1b_run(
        static, layers, x_micro, final_norm, lm_head, tgt_micro
    )
    return loss


def _pp_1f1b_fwd(static, layers, x_micro, final_norm, lm_head, tgt_micro):
    loss, grads = _pp_1f1b_run(
        static, layers, x_micro, final_norm, lm_head, tgt_micro
    )
    return loss, grads


def _pp_1f1b_bwd(static, res, g):
    g_layers, g_x, g_fn, g_lm = res
    g = g.astype(jnp.float32)

    def scale(t):
        return jax.tree.map(
            lambda a: (a.astype(jnp.float32) * g).astype(a.dtype), t
        )

    import numpy as np

    # integer targets take a symbolic-zero cotangent (float0)
    tgt_zero = np.zeros(
        (static.n_micro, static.mb, static.s_local * static.sp),
        jax.dtypes.float0,
    )
    return scale(g_layers), scale(g_x), scale(g_fn), scale(g_lm), tgt_zero


_pp_1f1b_call.defvjp(_pp_1f1b_fwd, _pp_1f1b_bwd)
