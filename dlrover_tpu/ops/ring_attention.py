"""Ring attention: exact causal attention over a sequence-sharded axis.

Long-context capability the reference lacks entirely (SURVEY.md §2.8 — it
delegates context parallelism to Megatron/DeepSpeed). TPU-native design:

- the sequence dim is sharded over the mesh ``sp`` axis;
- each device holds one q/k/v chunk; kv chunks rotate around the ring with
  `lax.ppermute` (single-hop ICI neighbor exchange — the torus makes this
  free-ish);
- every ring step runs the **Pallas flash kernel on the local chunk pair**
  (`flash_attention_with_lse`) — O(chunk) memory, GQA resolved in the
  kernel's index_map (never materialized), the chunk×chunk logit matrix
  never exists;
- per-chunk results merge by the standard logsumexp combine
  ``out = Σ_i exp(lse_i - lse_total) · out_i`` — exact, and exactly
  differentiable because the kernel's ``lse`` output is differentiable
  (its cotangent folds into the flash backward's delta term).

Chunk-level causality: a kv chunk strictly *after* the query chunk
contributes nothing (skipped via a zero merge-weight); the *diagonal*
chunk uses the causal kernel; chunks strictly before use the full
(non-causal) kernel — `lax.cond` picks the branch per device at runtime.

Must be called inside `shard_map` with ``axis_name`` bound (see
`models/llama.py` for the wiring). Differentiable through `lax.scan` +
`ppermute`; each step is rematerialized under `jax.checkpoint` so the
backward does not keep every rotated kv copy.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.ops.attention import _NEG_INF, flash_attention_with_lse


def _one_head_width(q, v, who: str):
    """The sequence-parallel forms merge chunks of one head width at the
    softmax scale ``1 / sqrt(d)``; latent attention (q/k heads wider
    than v heads, its own scale) runs through ``flash_attention``."""
    if q.shape[-1] != v.shape[-1]:
        raise ValueError(
            f"{who}: q/k heads of {q.shape[-1]} against v heads of "
            f"{v.shape[-1]}: two head widths run only through "
            "flash_attention (no sequence parallelism)"
        )


def ring_attention(
    q: jnp.ndarray,  # (b, s_local, h, d)
    k: jnp.ndarray,  # (b, s_local, hkv, d)
    v: jnp.ndarray,  # (b, s_local, hkv, d)
    axis_name: str,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    b, s_local, h, d = q.shape
    _one_head_width(q, v, "ring_attention")
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    # NB: comm attribution for the ring hops is recorded at the MODEL
    # layer (models/llama.py), which knows the per-step multiplicity
    # (n_layers x microbatches); this body traces once inside lax.scan,
    # so a record here could not count executions.

    def chunk_attn(kc, vc, src):
        """(out (b,s,h,d) f32, lse (b,h,s) f32) for this kv chunk."""
        def diag(_):
            o, lse = flash_attention_with_lse(
                q, kc, vc, True, block_q, block_k
            )
            return o.astype(jnp.float32), lse

        def full(_):
            o, lse = flash_attention_with_lse(
                q, kc, vc, False, block_q, block_k
            )
            return o.astype(jnp.float32), lse

        def skip(_):
            return (
                jnp.zeros((b, s_local, h, d), jnp.float32),
                jnp.full((b, h, s_local), _NEG_INF, jnp.float32),
            )

        if not causal:
            return full(None)
        # src > my: every key is in the future of every query → skip
        return lax.cond(
            src > my_idx,
            skip,
            lambda _: lax.cond(src == my_idx, diag, full, None),
            None,
        )

    def step_fn(carry, _):
        o_acc, lse_acc, kc, vc, src = carry
        o_i, lse_i = chunk_attn(kc, vc, src)
        # logsumexp merge of two normalized partial softmaxes
        lse_new = jnp.logaddexp(lse_acc, lse_i)              # (b, h, s)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_i = jnp.exp(lse_i - lse_new)
        # (b,h,s) weights → (b,s,h,1) to scale (b,s,h,d) outputs
        o_acc = (
            o_acc * w_acc.transpose(0, 2, 1)[..., None]
            + o_i * w_i.transpose(0, 2, 1)[..., None]
        )
        # rotate kv to the next ring position (device i → i+1)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        src = (src - 1) % n
        return (o_acc, lse_new, kc, vc, src), None

    o0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    # finite "minus infinity": logaddexp(-1e30, x) == x for any real lse,
    # and the first merge weight exp(-1e30 - lse_new) underflows to 0
    lse0 = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    carry0 = (o0, lse0, k, v, my_idx)
    (o, lse, *_), _ = lax.scan(
        jax.checkpoint(step_fn), carry0, None, length=n
    )
    return o.astype(q.dtype)
