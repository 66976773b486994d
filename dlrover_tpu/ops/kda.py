"""Kimi Delta Attention (arXiv 2510.26692): a gated delta rule with a
decay per channel, in chunked form, and the short depthwise causal
convolution its inputs pass through. On the TPU two Pallas kernels
under one ``custom_vjp`` (forward, and a hand-written backward); off it
the same equations in XLA ops (matrix products, one triangular solve a
chunk, a ``lax.scan`` over the chunks), which are also the kernels'
oracle.

Beside it what the layer does around the rule, elementwise but for a
shift of rows and a sum over a head's lanes (``conv_silu_norm``: the
convolution, SiLU and the L2 norms of the inputs; ``norm_gate``: the
head norm and the gate of the output): on the TPU one Pallas pass a
direction each, off it XLA's ops (the section at the end).

The recurrence, one head, ``S`` in R^(dk x dv) float32, ``S_0 = 0``::

    S'  = Diag(a_t) S_(t-1)                 a_t = exp(g_t) in (0, 1)^dk
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

**Chunked** (chunk ``C``, rows ``i, j`` of a chunk, ``G_i = sum_(r<=i)
g_r`` the cumulative log-decay inside the chunk, ``S`` the state the
chunk starts from). With ``u_i = b_i (v_i - S'_i^T k_i)`` the state is
``S_i = Diag(e^G_i) S + sum_(j<=i) Diag(e^(G_i - G_j)) k_j u_j^T``, so::

    A_kk[i, j] = sum_c k_i[c] k_j[c] e^(G_i[c] - G_j[c])      j <  i
    A_qk[i, j] = sum_c q_i[c] k_j[c] e^(G_i[c] - G_j[c])      j <= i
    (I + Diag(b) A_kk) [W_v | W_k] = Diag(b) [V | K * e^G]    one solve
    U   = W_v - W_k S
    O   = (Q * e^G) S + A_qk U
    S_C = Diag(e^G_C) S + (K * e^(G_C - G))^T U

Everything but the last three lines does not depend on the state (the
*state-free part*); the last three carry the (dk, dv) state from chunk
to chunk, ``seq / C`` dependent steps.

**The decays.** ``e^(G_i - G_j)`` is a product over channels, so it has
to be split into a factor on row ``i`` and one on row ``j`` before it
can be a matrix product, and ``k_j e^(-G_j)`` alone overflows float32
once ``G`` passes -88. Every factor here is relative to a reference row
``r`` of the rows' own sub-block of ``SUB`` = 16 rows, ``e^(G_i - G_r)
e^(G_r - G_j)``:

- rows ``i`` of sub-block ``I`` against the rows ``j`` of an *earlier*
  sub-block: ``r`` is the last row before ``I``, so ``G_i - G_r <= 0``
  and ``G_r - G_j <= 0``: both factors are at most 1 whatever ``g`` is;
  where one underflows the product it stands for is below 1e-38 too.
- ``i`` and ``j`` in the *same* sub-block: ``r`` is its row 7, so the
  exponents lie within ``8 max|g|`` of zero on either side. **Exact (to
  float32 rounding) while ``8 max|g| <= 72``, ``|g| <= 9`` a token and
  channel** (a decay to e^-9 = 1.2e-4 a token): the small factor
  ``e^-72 x_c`` then stays a normal float32 for every component above
  1e-7 of the row's norm. Past it the smallest components flush to zero
  (a relative error of 1e-3 at ``|g| = 10``), and past ``|g| = 11`` the
  large factor overflows to inf. ``tests/test_kda.py`` holds both forms
  at ``g = -5`` a token (G = -320 over a chunk) and at the bound against
  the token-by-token recurrence.
- ``e^G_i``, ``e^(G_C - G_i)`` and ``e^G_C`` are at most 1 as they are.

Operands go to the MXU in the activations' dtype and accumulate in
float32; gates, cumulative decays, the solve, ``U``, the state and its
cotangent are float32 (the state enters its products in the
activations' dtype).

**The kernels** (docs/design/kernels.md 1e has the sizes and the
chip's times). Arrays stay ``(b, s, h d)``, a free reshape: a block is
``TILE`` = 128 tokens of ``HEADS`` = 4 heads' lanes, read where it
lies; nothing is transposed in HBM. A grid step of the forward forms a
tile's state-free part a head (``_prep_heads``: a (128, 128) matrix
holds the tile's ``128 / C`` chunks as diagonal blocks) and then walks
the tile's chunks through the state, which stays in VMEM scratch from
tile to tile, the four heads' chains side by side for the scheduler to
interleave. The solve is forward substitution: row by row inside the
eight 16 x 16 diagonal sub-blocks at once, then the sub-blocks under
the diagonal block row by block row; the inverse is formed once a tile
and applied by the MXU at float32 precision.

**The backward** is written by hand (``_bwd_kernel``, ``_prep_bwd_tile``):
under differentiation the forward also writes the state every chunk
started from (float32, 64 KiB a head and chunk), and the backward walks
the tiles from the last, carrying the state's cotangent in scratch: it
forms a tile's state-free part again, takes its chunks in reverse, and
differentiates the state-free part (the solve through ``dM = -T^T dW
W^T`` under the diagonal; every decay factor ``y = x e^E`` through ``dx
= dy e^E``, ``dE = dy y``; ``dg`` the reverse cumulative sum of ``dG``
inside the chunk). The forward rule names its output and those states
(`KEPT`, both forms): a block whose checkpoint keeps the two
(``models/stack.py recompute(keep=KEPT)``; ``kimi_linear``'s does,
``qwen3_next``'s has not the memory) recomputes the rule's inputs in its
backward pass and never the forward kernel. The XLA form's backward is
JAX's own, through rematerialised *segments* of 16 chunks (what autodiff
would keep of the whole sequence, 3.4 GiB a layer at 8192 tokens, is the
step's peak otherwise).

**The second form: one decay a head** (``chunk_gdn``; Gated DeltaNet,
Qwen3-Next). ``g_t`` is a number a value head, ``hv`` value heads in
groups of ``hv / hk`` over ``hk`` key heads: value head ``j`` reads
``q`` and ``k`` of key head ``j // (hv / hk)``. The decay no longer has
to be split into factors: it is a mask on a plain product,

    A_kk[i, j] = (k_i . k_j) e^(G_i - G_j)        j <  i
    A_qk[i, j] = (q_i . k_j) e^(G_i - G_j)        j <= i
    W_k = T Diag(b e^G) K,    T = (I + Diag(b) A_kk)^-1

with ``K K^T`` and ``Q K^T`` formed once a key head and masked a value
head, and ``q`` and ``k`` never repeated over the group. No sub-blocks
and no reference row: ``e^(G_i - G_j) <= 1`` for ``j <= i`` whatever
``g`` is (the exponent is never formed where it would be positive), so
**this form is exact (to float32 rounding) for any ``g <= 0``**, where
the channel form above holds to ``|g| <= 9`` a token; ``tests/
test_kda.py`` holds both of its forms at ``g`` down to -21 a token (the
public initialisation's reach) against the token-by-token recurrence.
Its kernels (``gdn_fwd``, ``gdn_bwd``) are the kernels above but for the
state-free part and its derivative (``_gdn_part``, ``_gdn_solve``,
``_gdn_prep_bwd``): the inverse (``_inverse``), the walk through the
state (``_walk_fwd``, ``_walk_bwd``), tiles and grid are shared; a grid
step takes ``HEADS / (hv / hk)`` key heads and their value heads' chains,
``G`` is summed in XLA before the call (``dg`` after it), ``dg`` is a
number a row, and a key head's ``dq, dk`` are summed over its value
heads before the products that apply them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.norms import rms_norm
from dlrover_tpu.parallel.mesh import BATCH_AXES

SUB = 16          # rows of a sub-block of a chunk
_MID = SUB // 2 - 1   # the reference row inside a sub-block
TILE = 128        # rows the kernels take at a time: 128 / chunk chunks
HEADS = 4         # heads a grid step: independent chains, side by side
KERNEL_CHUNKS = (16, 32, 64)   # the chunk sizes the kernels admit


def causal_conv(x: jnp.ndarray, weight: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal convolution over time. ``x (b, s, c)``,
    ``weight (c, w)``: ``y_t = sum_i weight[:, i] x_(t - w + 1 + i)``,
    zeros before the sequence (the last tap is the token's own)."""
    w = weight.shape[1]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    taps = weight.astype(jnp.float32)
    y = sum(
        padded[:, i:i + s].astype(jnp.float32) * taps[:, i]
        for i in range(w)
    )
    return y.astype(x.dtype)


def _decay_products(x, k, G, dtype, strict: bool):
    """``A[i, j] = sum_c x_i[c] k_j[c] e^(G_i[c] - G_j[c])`` for ``j <=
    i`` (``j < i`` where ``strict``), 0 elsewhere. ``x, k, G (..., C,
    d)`` float32 -> ``(..., C, C)`` float32, by sub-blocks of ``SUB``
    rows as the module docstring sets out."""
    *lead, C, d = x.shape
    n = C // SUB

    def blocks(a):
        return a.reshape(*lead, n, SUB, d)

    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    # same sub-block: both factors relative to its row _MID
    mid = Gb[..., _MID:_MID + 1, :]
    diag = jnp.einsum(
        "...id,...jd->...ij",
        (xb * jnp.exp(Gb - mid)).astype(dtype),
        (kb * jnp.exp(mid - Gb)).astype(dtype),
        preferred_element_type=jnp.float32,
    )
    i, j = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]
    diag = jnp.where(j < i if strict else j <= i, diag, 0.0)
    rows = []
    for I in range(n):
        parts = []
        if I:
            # earlier sub-blocks: relative to the last row before I
            ref = G[..., I * SUB - 1:I * SUB, :]
            lo = (xb[..., I, :, :] * jnp.exp(Gb[..., I, :, :] - ref))
            hi = k[..., :I * SUB, :] * jnp.exp(ref - G[..., :I * SUB, :])
            parts.append(jnp.einsum(
                "...id,...jd->...ij", lo.astype(dtype), hi.astype(dtype),
                preferred_element_type=jnp.float32))
        parts.append(diag[..., I, :, :])
        if I < n - 1:
            parts.append(jnp.zeros((*lead, SUB, C - (I + 1) * SUB),
                                   jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _whole_segments(arrays, chunk: int, segment: int):
    """``(b, s, ...)`` arrays padded to whole segments of ``segment``
    chunks (one shorter segment where the sequence has fewer chunks):
    the rows past the end have no decay and no step, and nothing of them
    is read back -> ``(arrays, chunks a segment, rows added)``."""
    s = arrays[0].shape[1]
    seg = min(segment, -(-s // chunk))
    pad = -s % (chunk * seg)
    if pad:
        arrays = tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays)
    return arrays, seg, pad


def _chunk_kda_xla(q, k, v, g, beta, *, chunk: int = 64, segment: int = 16):
    """The chunked gated delta rule in XLA ops. ``q, k (b, s, h, dk)`` (``q``
    already scaled, both already normalised), ``v (b, s, h, dv)``,
    ``g (b, s, h, dk)`` float32 log-decays (<= 0), ``beta (b, s, h)``
    float32 step sizes -> ``o (b, s, h, dv)`` in ``v``'s dtype, which is
    also the matmul operands'. ``chunk`` is a multiple of ``SUB``. The sequence is
    cut into segments of ``segment`` chunks (padded to whole segments;
    one shorter segment where it has fewer chunks): an outer scan over
    the segments carries the state and remats its body, whose batched
    part covers one segment's chunks and whose inner scan walks them.
    The state starts at zero and is not returned."""
    dtype = v.dtype
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if chunk % SUB:
        raise ValueError(f"chunk_kda: chunk {chunk} is no multiple of {SUB}")
    (q, k, v, g, beta), seg, pad = _whole_segments(
        (q, k, v, g, beta), chunk, segment)
    n_seg = (s + pad) // (chunk * seg)
    f32 = jnp.float32

    def chunks(a):
        """(b, s, h, ...) -> (n_seg, seg, b, h, C, ...): segments and
        chunks lead (the scans' axes), a head's rows and channels are
        the matrix."""
        a = a.reshape(b, n_seg, seg, chunk, h, *a.shape[3:])
        return jnp.moveaxis(a, (1, 2, 4), (0, 1, 3))

    def step(S, xs):
        w_v, w_k, q_in, a_qk, k_out, keep = xs
        Sd = S.astype(dtype)
        u = w_v - jnp.einsum("bhcd,bhde->bhce", w_k, Sd,
                             preferred_element_type=f32)
        o = jnp.einsum("bhcd,bhde->bhce", q_in, Sd,
                       preferred_element_type=f32)
        ud = u.astype(dtype)
        o = o + jnp.einsum("bhcj,bhje->bhce", a_qk, ud,
                           preferred_element_type=f32)
        S = keep * S + jnp.einsum("bhcd,bhce->bhde", k_out, ud,
                                  preferred_element_type=f32)
        return S, o.astype(dtype)

    @jax.checkpoint
    def one_segment(S, xs):
        qc, kc, vc, gc, bc = xs                     # (seg, b, h, C, ...)
        qc, kc, vc = (a.astype(f32) for a in (qc, kc, vc))
        G = jnp.cumsum(gc, axis=-2)
        bc = bc[..., None]
        a_kk = _decay_products(kc, kc, G, dtype, strict=True)
        a_qk = _decay_products(qc, kc, G, dtype, strict=False)
        decay = jnp.exp(G)
        w = lax.linalg.triangular_solve(
            jnp.eye(chunk, dtype=f32) + bc * a_kk,
            jnp.concatenate([bc * vc, bc * kc * decay], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
        end = G[..., -1:, :]
        return lax.scan(step, S, (
            w[..., :dv], w[..., dv:].astype(dtype),
            (qc * decay).astype(dtype), a_qk.astype(dtype),
            (kc * jnp.exp(end - G)).astype(dtype),
            jnp.exp(end)[..., 0, :, None],          # (seg, b, h, dk, 1)
        ))

    _, o = lax.scan(
        one_segment, jnp.zeros((b, h, dk, dv), f32),
        (chunks(q), chunks(k), chunks(v), chunks(g.astype(f32)),
         chunks(beta.astype(f32))))
    # (n_seg, seg, b, h, C, dv) -> (b, s, h, dv)
    o = jnp.moveaxis(o, (0, 1, 3), (1, 2, 4))
    return o.reshape(b, s + pad, h, dv)[:, :s]


GDN_SEGMENT = 16    # chunks a rematerialised segment of the per-head XLA form


def _chunk_gdn_xla(q, k, v, g, beta, *, chunk: int = 64):
    """The chunked gated delta rule **with one decay a head** in XLA ops
    (the module docstring's second form). ``q, k (b, s, hk, dk)`` (``q``
    already scaled, both already normalised), ``v (b, s, hv, dv)``, ``g,
    beta (b, s, hv)`` float32 (``g <= 0``, any size) -> ``o (b, s, hv,
    dv)`` in ``v``'s dtype, which is also the matmul operands'. Value
    head ``j`` reads key head ``j // (hv / hk)``: the two ``(C, C)``
    products are formed a key head and masked a value head, and ``q``
    and ``k`` enter the scan as they are, never repeated. Segments (of
    ``GDN_SEGMENT`` chunks), padding and the state as in
    ``_chunk_kda_xla``."""
    dtype = v.dtype
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    (q, k, v, g, beta), seg, pad = _whole_segments(
        (q, k, v, g, beta), chunk, GDN_SEGMENT)
    n_seg = (s + pad) // (chunk * seg)
    f32 = jnp.float32

    def chunks(a, head_axes):
        """(b, s, heads.., ...) -> (n_seg, seg, b, heads.., C, ...)."""
        a = a.reshape(b, n_seg, seg, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 0, 2), 3, 3 + head_axes)

    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    eye = jnp.eye(chunk, dtype=f32)

    def step(S, xs):
        qc, kc, w_v, t_k, a_qk, grown, to_end, keep = xs
        Sd = S.astype(dtype)
        ks = jnp.einsum("bhcd,bhrde->bhrce", kc, Sd,
                        preferred_element_type=f32)
        qs = jnp.einsum("bhcd,bhrde->bhrce", qc, Sd,
                        preferred_element_type=f32)
        u = w_v - jnp.einsum("bhrcj,bhrje->bhrce", t_k, ks.astype(dtype),
                             preferred_element_type=f32)
        o = grown * qs + jnp.einsum("bhrcj,bhrje->bhrce", a_qk,
                                    u.astype(dtype),
                                    preferred_element_type=f32)
        S = keep * S + jnp.einsum("bhcd,bhrce->bhrde", kc,
                                  (to_end * u).astype(dtype),
                                  preferred_element_type=f32)
        return S, o.astype(dtype)

    @jax.checkpoint
    def one_segment(S, xs):
        qc, kc, vc, gc, bc = xs          # (seg, b, hk, [r,] C[, d])
        G = jnp.cumsum(gc, axis=-1)                    # (seg, b, hk, r, C)
        # e^(G_i - G_j) for j <= i: at most 1 whatever g is; the
        # exponent is never formed where it would be positive
        diff = G[..., :, None] - G[..., None, :]
        decay = jnp.where(j <= i, jnp.exp(jnp.where(j <= i, diff, 0.0)), 0.0)
        kk = jnp.einsum("...id,...jd->...ij", kc, kc,
                        preferred_element_type=f32)[..., None, :, :]
        qk = jnp.einsum("...id,...jd->...ij", qc, kc,
                        preferred_element_type=f32)[..., None, :, :]
        bc = bc[..., None]                             # (.., C, 1)
        grown = jnp.exp(G)[..., None]
        # (I + Diag(b) A_kk) [W_v | T] = [Diag(b) V | I]: W_k S is
        # T Diag(b e^G) (K S), so no W_k a value head is ever formed
        w = lax.linalg.triangular_solve(
            eye + bc * jnp.where(j < i, kk * decay, 0.0),
            jnp.concatenate([bc * vc.astype(f32),
                             jnp.broadcast_to(eye, decay.shape)], axis=-1),
            left_side=True, lower=True, unit_diagonal=True)
        end = G[..., -1:]
        t_k = w[..., dv:] * jnp.swapaxes(bc * grown, -1, -2)
        return lax.scan(step, S, (
            qc, kc, w[..., :dv], t_k.astype(dtype),
            (qk * decay).astype(dtype), grown,
            jnp.exp(end - G)[..., None], jnp.exp(end)[..., None]))

    _, o = lax.scan(
        one_segment, jnp.zeros((b, hk, r, dk, dv), f32),
        (chunks(q, 1), chunks(k, 1),
         chunks(v.reshape(*v.shape[:2], hk, r, dv), 2),
         chunks(g.astype(f32).reshape(*g.shape[:2], hk, r), 2),
         chunks(beta.astype(f32).reshape(*beta.shape[:2], hk, r), 2)))
    # (n_seg, seg, b, hk, r, C, dv) -> (b, s, hv, dv)
    o = jnp.moveaxis(jnp.moveaxis(o, 5, 3), 2, 0)
    return o.reshape(b, s + pad, hv, dv)[:, :s]


# ---------------------------------------------------------------------------
# The Pallas kernels
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_F32 = jnp.float32


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dot(a, b, dims, exact: bool = False):
    """A product on the MXU, float32 out. ``exact``: float32 operands at
    float32 precision (the solve's products); else the operands as they
    come, which is the activations' dtype."""
    return lax.dot_general(
        a, b, dims, precision=lax.Precision.HIGHEST if exact else None,
        preferred_element_type=_F32)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _rows_of(x, starts, rows):
    """``x (TILE, d)``: row ``starts[i]`` repeated over ``rows`` rows,
    for every ``i``, stacked: the reference row of each group of rows."""
    return jnp.concatenate([
        jnp.broadcast_to(x[at:at + 1], (rows, x.shape[1])) for at in starts
    ], axis=0)


def _sel_matrix() -> np.ndarray:
    """``(TILE, (SUB - 1) TILE)`` of 0 / 1: column ``(i, s, c)`` takes
    row ``(s, i)``, for ``i`` in 1 .. SUB - 1: a product with it spreads
    lane ``i`` of every group of SUB lanes over the group."""
    sel = np.zeros((TILE, SUB - 1, TILE), np.float32)
    for i in range(1, SUB):
        for s in range(TILE // SUB):
            sel[s * SUB + i, i - 1, s * SUB:(s + 1) * SUB] = 1.0
    return sel.reshape(TILE, (SUB - 1) * TILE)


def _dot_pieces(a, b, dims):
    """A product of a float32 operand with one that bfloat16 holds
    exactly (a 0 / 1 matrix, an activation): the float32 one goes in
    three bfloat16 pieces that sum to it to 24 bits, so the result has
    float32 precision at three MXU passes, not ``exact``'s six."""
    split = a.dtype == _F32
    x, out = (a if split else b), None
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        x = x - piece.astype(_F32)
        part = _dot(piece, b, dims) if split else _dot(a, piece, dims)
        out = part if out is None else out + part
    return out


def _masks(chunk: int):
    """(TILE, TILE) masks of a tile's matrices: rows and columns in the
    same 16-row sub-block, in the same chunk, the sub-block's position
    in its chunk by row and by column, and the row and column indices."""
    r, c = _iota((TILE, TILE), 0), _iota((TILE, TILE), 1)
    shift = chunk.bit_length() - 1
    at = chunk // SUB - 1
    return ((r >> 4) == (c >> 4), (r >> shift) == (c >> shift),
            (r >> 4) & at, (c >> 4) & at, r, c)


@functools.partial(jax.jit, static_argnames=("chunk",), inline=True)
def _decay_part(q, k, v, g, b_col, *, chunk: int):
    """The state-free part of ``TILE`` rows of one head up to the solve:
    ``q, k (TILE, dk)``, ``v (TILE, dv)`` in the operands' dtype, ``g
    (TILE, dk)`` float32, ``b_col (TILE, 1)`` float32. The tile holds
    ``TILE / chunk`` chunks; a (TILE, TILE) matrix of it has their
    (chunk, chunk) blocks on its diagonal and zeros elsewhere.

    Returns what ``_solve_part`` and the backward read: ``a_kk`` under
    the diagonal sub-blocks, ``a_qk`` whole, ``G`` and the decay factors
    (``up, down`` inside a sub-block, ``below``: ``(lo, hi)`` a later
    sub-block position), and ``ltp (SUB, TILE)``: entry ``[j, 16 s +
    i]`` is ``b_i A_kk[i, j]`` of diagonal sub-block ``s`` (``j < i``),
    which the substitution takes its rows from."""
    dt = v.dtype
    same_sub, same_chunk, pos_r, pos_c, r, c = _masks(chunk)
    q32, k32 = q.astype(_F32), k.astype(_F32)

    # G: the cumulative log-decay inside each chunk
    tril = jnp.where(same_chunk & (c <= r), 1.0, 0.0).astype(jnp.bfloat16)
    G = _dot_pieces(tril, g, _NN)
    firsts = [i * chunk for i in range(TILE // chunk)]
    decay = jnp.exp(G)
    to_end = jnp.exp(_rows_of(G, [f + chunk - 1 for f in firsts], chunk) - G)

    # the diagonal sub-blocks, relative to each sub-block's row _MID
    rel = G - _rows_of(G, [s * SUB + _MID for s in range(TILE // SUB)], SUB)
    up, down = jnp.exp(rel), jnp.exp(-rel)
    k_down = (k32 * down).astype(dt)
    # transposed: [j, i] = b_i A_kk[i, j], i's sub-block = j's, j < i
    b_row = jnp.sum(jnp.where(r == c, b_col, 0.0), axis=0, keepdims=True)
    lt = jnp.where(
        same_sub & (c > r), _dot(k_down, (k32 * up).astype(dt), _NT), 0.0
    ) * b_row
    ltp = lt[:SUB]
    for s in range(1, TILE // SUB):
        ltp = ltp + lt[s * SUB:(s + 1) * SUB]
    a_qk = jnp.where(
        same_sub & (c <= r), _dot((q32 * up).astype(dt), k_down, _NT), 0.0)

    # sub-block p of each chunk against the chunk's earlier sub-blocks,
    # relative to the last row before p
    a_kk = jnp.zeros((TILE, TILE), _F32)
    below = []
    for p in range(1, chunk // SUB):
        ref = _rows_of(G, [f + p * SUB - 1 for f in firsts], chunk)
        lo = jnp.exp(jnp.minimum(G - ref, 0.0))
        hi = jnp.exp(jnp.minimum(ref - G, 0.0))
        k_hi = (k32 * hi).astype(dt)
        here = same_chunk & (pos_r == p) & (pos_c < p)
        a_kk = a_kk + jnp.where(
            here, _dot((k32 * lo).astype(dt), k_hi, _NT), 0.0)
        a_qk = a_qk + jnp.where(
            here, _dot((q32 * lo).astype(dt), k_hi, _NT), 0.0)
        below.append((lo, hi))
    return {"a_kk": a_kk, "a_qk": a_qk, "ltp": ltp, "G": G, "decay": decay,
            "to_end": to_end, "up": up, "down": down, "below": below,
            "b_row": b_row}


def _inverse(coef, under, b_col, chunk: int):
    """``t = (I + Diag(b) A_kk)^-1`` of a tile, ``(TILE, TILE)`` float32
    with the chunks' inverses on its diagonal. ``coef (SUB, (SUB - 1)
    TILE)``: the diagonal sub-blocks' entries spread by ``_sel_matrix``,
    ``coef[j, (i - 1, s, .)] = b_i A_kk[i, j]`` of sub-block ``s``;
    ``under``: ``A_kk`` under the diagonal sub-blocks (zeros in them)."""
    n = chunk // SUB
    # the inverse of the diagonal sub-blocks of I + Diag(b) A_kk, row by
    # row (forward substitution), all TILE / SUB of them at once: x[j,
    # (s, c)] is entry (j, c) of sub-block s's inverse; row i is e_i -
    # sum_(j < i) L[i, j] x[j]
    row = _iota((SUB, TILE), 0)
    x = jnp.where(row == (_iota((SUB, TILE), 1) & (SUB - 1)), 1.0, 0.0
                  ).astype(_F32)
    for i in range(1, SUB):
        ci = coef[:, (i - 1) * TILE:i * TILE]
        new = jnp.sum(ci * x, axis=0, keepdims=True)
        x = x - jnp.where(row == i, new, 0.0)
    t = jnp.where(_masks(chunk)[0],
                  jnp.concatenate([x] * (TILE // SUB), axis=0), 0.0)
    # the sub-blocks under the diagonal, block row by block row:
    # (I + D N)^-1 D with D N nilpotent of index n, in Horner's form
    if n > 1:
        d = t
        p_mat = _dot(d, b_col * under, _NN, exact=True)
        for _ in range(n - 1):
            t = d - _dot(p_mat, t, _NN, exact=True)
    return t


@functools.partial(jax.jit, static_argnames=("chunk",), inline=True)
def _solve_part(q, k, v, b_col, part, coef, *, chunk: int):
    """The solve and what the chunks read. ``part``: ``_decay_part``'s;
    ``coef (SUB, (SUB - 1) TILE)``: ``part["ltp"]`` spread by
    ``_sel_matrix``, ``coef[j, (i - 1, s, .)] = L_s[i, j]``.

    Returns ``part`` and: ``w_v`` float32, ``w_k``, ``a_qk``, ``q_in``,
    ``k_out`` in the operands' dtype, ``keep (TILE / chunk, dk)``
    float32, and for the backward the inverse ``t = (I + Diag(b)
    A_kk)^-1`` and ``w_k32``, ``W_k`` before its cast."""
    dt = v.dtype
    G, decay = part["G"], part["decay"]
    k32 = k.astype(_F32)
    t = _inverse(coef, part["a_kk"], b_col, chunk)
    if dt == jnp.bfloat16:
        # T Diag(b) V with V as it is: exact in three passes
        w_v = _dot_pieces(t * part["b_row"], v, _NN)
    else:
        w_v = _dot(t, b_col * v.astype(_F32), _NN, exact=True)
    w_k = _dot(t, b_col * (k32 * decay), _NN, exact=True)
    lasts = range(chunk - 1, TILE, chunk)
    return dict(
        part, t=t, w_v=w_v, w_k32=w_k, w_k=w_k.astype(dt),
        a_qk=part["a_qk"].astype(dt),
        q_in=(q.astype(_F32) * decay).astype(dt),
        k_out=(k32 * part["to_end"]).astype(dt),
        keep=jnp.concatenate([jnp.exp(G[at:at + 1]) for at in lasts], axis=0))


def _prep_heads(ins, sel, *, chunk: int):
    """``_decay_part`` and ``_solve_part`` of every head of a grid step
    (``ins``: ``[(q, k, v, g, b_col)]``), the heads' substitution
    coefficients spread by one product with ``sel``."""
    parts = [_decay_part(*x, chunk=chunk) for x in ins]
    coef = _dot_pieces(
        jnp.concatenate([p["ltp"] for p in parts], axis=0), sel, _NN)
    return [
        _solve_part(q, k, v, b_col, part, coef[hd * SUB:(hd + 1) * SUB],
                    chunk=chunk)
        for hd, ((q, k, v, _, b_col), part) in enumerate(zip(ins, parts))]


def _head_column(beta_ref, head):
    """``beta_ref (rows, h)``: column ``head`` as ``(rows, 1)``."""
    blk = beta_ref[...]
    return jnp.sum(jnp.where(_iota(blk.shape, 1) == head, blk, 0.0),
                   axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("chunk",), inline=True)
def _prep_bwd_tile(q, k, v, b_col, p, ct, *, chunk: int):
    """The derivative of a head's state-free part (``_decay_part`` and
    ``_solve_part``) by hand: its inputs, ``p``, what ``_solve_part``
    returned, and ``ct``, the float32 cotangents of ``w_v, w_k, a_qk,
    q_in, k_out, keep`` by name -> ``dq, dk, dv, dg`` float32 ``(TILE,
    .)`` and ``dbeta (TILE, 1)``.

    The solve: with ``W = T R``, ``dR = T^T dW`` and the cotangent of
    ``I + Diag(b) A_kk`` is ``-dR W^T`` under the diagonal. The decays:
    every factor ``y = x e^E`` gives ``dx = dy e^E`` and ``dE = dy y``,
    ``E`` being ``+-(G - G_ref)``; the reference rows get nothing (a
    product does not depend on its reference), ``dG`` collects the
    rest, and ``dg`` is its reverse cumulative sum inside the chunk."""
    dt = v.dtype
    same_sub, same_chunk, pos_r, pos_c, r, c = _masks(chunk)
    q32, k32, v32 = (a.astype(_F32) for a in (q, k, v))
    dw_v, dw_k, da_qk, dq_in, dk_out, dkeep = (
        ct[n] for n in ("w_v", "w_k", "a_qk", "q_in", "k_out", "keep"))
    G, decay, to_end, up, down = (
        p[n] for n in ("G", "decay", "to_end", "up", "down"))
    t, w_v, w_k = p["t"], p["w_v"], p["w_k32"]
    k_up, k_down, q_up = k32 * up, k32 * down, q32 * up
    diag_kk, diag_qk = same_sub & (c < r), same_sub & (c <= r)
    # A_kk whole: the forward formed its diagonal sub-blocks transposed
    a_kk = p["a_kk"] + jnp.where(
        diag_kk, _dot(k_up.astype(dt), k_down.astype(dt), _NT), 0.0)

    # the solve
    r_k = b_col * (k32 * decay)
    d_rv = _dot(t, dw_v, _TN, exact=True)
    d_rk = _dot(t, dw_k, _TN, exact=True)
    dl = jnp.where(
        same_chunk & (c < r),
        -(_dot(d_rv, w_v, _NT, exact=True) + _dot(d_rk, w_k, _NT, exact=True)),
        0.0)
    db = (jnp.sum(dl * a_kk, axis=1, keepdims=True)
          + jnp.sum(d_rv * v32, axis=1, keepdims=True)
          + jnp.sum(d_rk * (k32 * decay), axis=1, keepdims=True))
    da_kk = b_col * dl
    dv32 = b_col * d_rv
    dk32 = b_col * decay * d_rk
    dG = d_rk * r_k

    # what the scan reads: Q e^G, K e^(G_C - G), e^G_C
    dq32 = dq_in * decay
    dG = dG + dq_in * (q32 * decay)
    e = dk_out * (k32 * to_end)
    dk32 = dk32 + dk_out * to_end
    dG = dG - e
    row = _iota(G.shape, 0)
    for i, last in enumerate(range(chunk - 1, TILE, chunk)):
        total = (jnp.sum(e[last + 1 - chunk:last + 1], axis=0, keepdims=True)
                 + dkeep[i:i + 1] * jnp.exp(G[last:last + 1]))
        dG = dG + jnp.where(row == last, total, 0.0)

    # the decay products
    def pair(mask, x_k, x_q, y_k, fx, fy):
        """``A = mask(x y^T)`` for the k rows and the q rows against
        one ``y``: the cotangents of both land on x's factor ``fx`` and
        y's ``fy``."""
        nonlocal dk32, dq32, dG
        dak = jnp.where(mask[0], da_kk, 0.0).astype(dt)
        daq = jnp.where(mask[1], da_qk, 0.0).astype(dt)
        y = y_k.astype(dt)
        dxk, dxq = _dot(dak, y, _NN), _dot(daq, y, _NN)
        dy = _dot(dak, x_k.astype(dt), _TN) + _dot(daq, x_q.astype(dt), _TN)
        dk32 = dk32 + dxk * fx + dy * fy
        dq32 = dq32 + dxq * fx
        dG = dG + dxk * x_k + dxq * x_q - dy * y_k

    pair((diag_kk, diag_qk), k_up, q_up, k_down, up, down)
    for at, (lo, hi) in enumerate(p["below"], 1):
        here = same_chunk & (pos_r == at) & (pos_c < at)
        pair((here, here), k32 * lo, q32 * lo, k32 * hi, lo, hi)
    tril = jnp.where(same_chunk & (c <= r), 1.0, 0.0).astype(jnp.bfloat16)
    return dq32, dk32, dv32, _dot_pieces(tril, dG, _TN), db


_SCAN_IN = ("w_k", "q_in", "k_out", "a_qk", "w_v")


def _chunk_rows(x, j, chunk):
    return x[j * chunk:(j + 1) * chunk]


def _into_tile(x, j, chunk):
    """``x (chunk, d)`` as chunk ``j``'s rows of a tile of zeros: a row
    of ``a_qk`` holds the whole tile's columns."""
    parts = [jnp.zeros_like(x)] * (TILE // chunk)
    parts[j] = x
    return jnp.concatenate(parts, axis=0)


def _head_inputs(refs, beta_ref, heads, dk, dv):
    """A grid step's blocks, a head at a time: ``refs`` are q, k, v, g
    ``(TILE, heads d)`` -> ``[(q, k, v, g, b_col)]``."""
    out = []
    for hd in range(heads):
        kc, vc = slice(hd * dk, (hd + 1) * dk), slice(hd * dv, (hd + 1) * dv)
        q_ref, k_ref, v_ref, g_ref = refs
        out.append((q_ref[:, kc], k_ref[:, kc], v_ref[:, vc], g_ref[:, kc],
                    _head_column(beta_ref, pl.program_id(1) * heads + hd)))
    return out


def _walk_fwd(p, s_ref, states_ref, o_ref, *, chunk, heads, dv):
    """A tile's chunks through the state: ``p[hd]`` is what head ``hd``'s
    state-free part gave (``_SCAN_IN`` and ``keep``, a row a chunk), the
    state is held transposed, ``(dv, dk)`` (a chunk's decay scales its
    lanes, or all of it), in the scratch ``s_ref`` through a head's
    tiles; the heads' chains side by side for the scheduler to
    interleave. ``states_ref``: where each chunk writes the state it
    started from, or None."""
    dt = o_ref.dtype
    st = [s_ref[hd] for hd in range(heads)]
    out = [[] for _ in range(heads)]
    for j in range(TILE // chunk):
        for hd in range(heads):
            if states_ref is not None:
                states_ref[hd, j] = st[hd]
            sd = st[hd].astype(dt)
            w_k, q_in, k_out, a_qk, w_v = (
                _chunk_rows(p[hd][n], j, chunk) for n in _SCAN_IN)
            ud = (w_v - _dot(w_k, sd, _NT)).astype(dt)
            out[hd].append(_dot(q_in, sd, _NT) + _dot(
                a_qk, _into_tile(ud, j, chunk), _NN))
            st[hd] = p[hd]["keep"][j:j + 1] * st[hd] + _dot(ud, k_out, _TN)
    for hd in range(heads):
        o_ref[:, hd * dv:(hd + 1) * dv] = jnp.concatenate(
            out[hd], axis=0).astype(dt)
        s_ref[hd] = st[hd]


def _walk_bwd(p, do_ref, states_ref, ds_ref, *, chunk, heads, dv):
    """``_walk_fwd`` backwards: a tile's chunks from the last through
    the cotangent of the (transposed) state, each reading the state it
    started from and forming ``U`` again. Returns, a head, the float32
    cotangents of what the chunks read of the state-free part (by name,
    a list of the chunks' rows; ``keep``'s a row of ``dk`` lanes a chunk,
    not yet summed where the decay is one number), and the state's
    cotangent before the tile, which the caller stores."""
    dt = do_ref.dtype
    per = TILE // chunk
    ds = [ds_ref[hd] for hd in range(heads)]
    cts = [{n: [None] * per for n in _SCAN_IN + ("keep",)}
           for _ in range(heads)]
    for j in reversed(range(per)):
        for hd in range(heads):
            st = states_ref[hd, j]
            sd, dsd = st.astype(dt), ds[hd].astype(dt)
            w_k, q_in, k_out, a_qk, w_v = (
                _chunk_rows(p[hd][n], j, chunk) for n in _SCAN_IN)
            do = _chunk_rows(do_ref[:, hd * dv:(hd + 1) * dv], j, chunk)
            ud = (w_v - _dot(w_k, sd, _NT)).astype(dt)
            du = (_chunk_rows(_dot(a_qk, do, _TN), j, chunk)
                  + _dot(k_out, dsd, _NT))
            dud = du.astype(dt)
            ct = cts[hd]
            ct["w_v"][j] = du
            ct["w_k"][j] = -_dot(dud, sd, _NN)
            ct["a_qk"][j] = _dot(do, _into_tile(ud, j, chunk), _NT)
            ct["q_in"][j] = _dot(do, sd, _NN)
            ct["k_out"][j] = _dot(ud, dsd, _NN)
            ct["keep"][j] = jnp.sum(ds[hd] * st, axis=0, keepdims=True)
            ds[hd] = (p[hd]["keep"][j:j + 1] * ds[hd]
                      + _dot(do, q_in, _TN) - _dot(dud, w_k, _TN))
    return cts, ds


def _row(col):
    """``col (TILE, 1)`` as a row ``(1, TILE)``: lanes are its tokens."""
    eye = _iota((TILE, TILE), 0) == _iota((TILE, TILE), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sel_ref, o_ref, *rest,
                chunk, heads, dk, dv):
    """One tile of ``heads`` heads a grid step: the state-free part,
    then the tile's chunks through the state (``_walk_fwd``). ``rest``:
    the output of a state a chunk (what each chunk started from) where
    the backward will want it, then the scratch."""
    states_ref = rest[0] if len(rest) == 2 else None
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    ins = _head_inputs((q_ref, k_ref, v_ref, g_ref), beta_ref, heads, dk, dv)
    p = _prep_heads(ins, sel_ref[...], chunk=chunk)
    _walk_fwd(p, s_ref, states_ref, o_ref, chunk=chunk, heads=heads, dv=dv)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sel_ref, do_ref,
                states_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref,
                *, chunk, heads, dk, dv):
    """The forward kernel backwards: the grid walks the tiles from the
    last. A tile: its state-free part again (with the inverse), its
    chunks from the last (``_walk_bwd``), then the state-free part's
    derivative."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ins = _head_inputs((q_ref, k_ref, v_ref, g_ref), beta_ref, heads, dk, dv)
    p = _prep_heads(ins, sel_ref[...], chunk=chunk)
    cts, ds = _walk_bwd(p, do_ref, states_ref, ds_ref, chunk=chunk,
                        heads=heads, dv=dv)
    eye = _iota((TILE, TILE), 0) == _iota((TILE, TILE), 1)
    for hd in range(heads):
        ct = {n: jnp.concatenate(x, axis=0) for n, x in cts[hd].items()}
        q, k, v, _, b_col = ins[hd]
        dq, dk_, dv_, dg, db = _prep_bwd_tile(
            q, k, v, b_col, p[hd], ct, chunk=chunk)
        kc, vc = slice(hd * dk, (hd + 1) * dk), slice(hd * dv, (hd + 1) * dv)
        dq_ref[:, kc] = dq.astype(dq_ref.dtype)
        dk_ref[:, kc] = dk_.astype(dk_ref.dtype)
        dv_ref[:, vc] = dv_.astype(dv_ref.dtype)
        dg_ref[:, kc] = dg
        # a tile's steps as a row: lanes are its tokens
        dbeta_ref[hd, 0] = jnp.sum(jnp.where(eye, db, 0.0), axis=0,
                                   keepdims=True)
        ds_ref[hd] = ds[hd]


def _heads_a_step(hk: int, r: int = 1) -> int:
    """Key heads a grid step: their ``r`` value heads each (one in the
    channel form) are the step's chains, ``HEADS`` of them where ``r``
    divides that."""
    return max(d for d in range(1, hk + 1)
               if hk % d == 0 and d * r <= max(HEADS, r))


def _call(kernel, name, arrays, more_in, out, *, hk, chunk, backwards,
          interpret):
    """``arrays``: q, k ``(b, s, hk dk)``, v ``(b, s, hv dv)`` (free
    reshapes: a head's channels are the lanes of a block, its tokens lie
    where they lay, nothing is transposed in HBM), the decays (``g (b,
    s, hv dk)`` in the channel form, where ``hv = hk``; ``G (b, s, hv)``
    in the per-head form) and beta ``(b, s, hv)``, whole tiles.
    ``more_in`` and ``out``: ``(kind, array or its shape)``, ``kind``
    ``("k", d)`` or ``("v", d)`` for the ``d`` lanes a key or a value
    head has of a ``(b, s, .)`` array, or the block of a ``(b, hv, s /
    TILE, ., .)`` one. The grid is (batch, key heads / key heads a
    step, tiles), the last axis in order (from the end where
    ``backwards``): it carries the state."""
    b, s, hv = arrays[4].shape
    steps = s // TILE
    dk, dv, r = arrays[0].shape[-1] // hk, arrays[2].shape[-1] // hv, hv // hk
    heads = _heads_a_step(hk, r)
    sel = jnp.asarray(_sel_matrix(), jnp.bfloat16)

    def at(ti):
        return steps - 1 - ti if backwards else ti

    def spec(kind):
        if isinstance(kind[0], str):
            lanes = heads * kind[1] * (r if kind[0] == "v" else 1)
            return pl.BlockSpec((None, TILE, lanes),
                                lambda bi, hi, ti: (bi, at(ti), hi))
        return pl.BlockSpec((None, heads * r) + kind,
                            lambda bi, hi, ti: (bi, hi, at(ti), 0, 0))

    whole = pl.BlockSpec((None, TILE, hv), lambda bi, hi, ti: (bi, at(ti), 0))
    decays = whole if arrays[3].shape[-1] == hv else spec(("k", dk))
    in_specs = [spec(("k", dk)), spec(("k", dk)), spec(("v", dv)), decays,
                whole, pl.BlockSpec(sel.shape, lambda bi, hi, ti: (0, 0))]
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, heads=heads, dk=dk, dv=dv),
        grid=(b, hk // heads, steps),
        in_specs=in_specs + [spec(kind) for kind, _ in more_in],
        out_specs=[spec(kind) for kind, _ in out],
        out_shape=[shape for _, shape in out],
        scratch_shapes=[pltpu.VMEM((heads * r, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*arrays, sel, *(x for _, x in more_in))


def _tiles(arrays, s):
    """(b, s, h, d) -> (b, s, h d), free; whole tiles: the rows past the
    end have no decay and no step, and nothing of them is read back."""
    pad = ((0, 0), (0, -s % TILE))
    return [jnp.pad(a.reshape(*a.shape[:2], -1) if a.ndim == 4 else a,
                    pad + ((0, 0),)) for a in arrays]


# Both calls are jitted (and inlined where they are called): a kernel's
# body is some thousand operations to trace, the step calls each kernel
# once a run of like layers, and jit's cache traces it once a shape.
# They serve both forms of the rule, which ``g`` tells apart: a decay a
# channel ``(b, s, h, dk)`` (``kda_fwd``, ``kda_bwd`` under the scope
# ``kda_chunk``), or a decay a head ``(b, s, hv)`` over ``hv / hk`` value
# heads a key head (``gdn_fwd``, ``gdn_bwd`` under ``gdn_chunk``), which
# the kernels read summed inside each chunk (``_chunk_sums``; their
# ``dG`` is summed back the other way).

def _form(g):
    """``(forward kernel, backward kernel, their names' stem)``."""
    if g.ndim == 3:
        return _gdn_fwd_kernel, _gdn_bwd_kernel, "gdn"
    return _fwd_kernel, _bwd_kernel, "kda"


def _chunk_sums(x, chunk: int, reverse: bool = False):
    """``x (b, s, h)``: the cumulative sums inside each chunk of rows."""
    b, s, h = x.shape
    return lax.cumsum(x.reshape(b, s // chunk, chunk, h), axis=2,
                      reverse=reverse).reshape(b, s, h)


def _kernel_operands(arrays, s, chunk):
    """``[q, k, v, g, beta, ...]`` as the kernels read them (``_tiles``)."""
    per_head = arrays[3].ndim == 3
    arrays = _tiles([a.astype(_F32) if i in (3, 4) else a
                     for i, a in enumerate(arrays)], s)
    if per_head:
        arrays[3] = _chunk_sums(arrays[3], chunk)
    return arrays


@functools.partial(jax.jit, static_argnums=(5, 6, 7), inline=True)
def _rule_forward(q, k, v, g, beta, chunk, interpret, states: bool):
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    kernel, _, stem = _form(g)
    arrays = _kernel_operands([q, k, v, g, beta], s, chunk)
    padded = arrays[0].shape[1]
    out = [(("v", dv), jax.ShapeDtypeStruct((b, padded, hv * dv), v.dtype))]
    if states:
        out.append(((TILE // chunk, dv, dk), jax.ShapeDtypeStruct(
            (b, hv, padded // chunk, dv, dk), _F32)))
    o, *kept = _call(kernel, stem + "_fwd", arrays, (), out, hk=hk,
                     chunk=chunk, backwards=False, interpret=interpret)
    return (o[:, :s].reshape(b, s, hv, dv), *kept)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _rule_backward(chunk, interpret, res, do):
    q, k, v, g, beta, states = res
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    _, kernel, stem = _form(g)
    # a custom_vjp's backward is traced outside the caller's scopes: the
    # device metrics find the op by this one
    with trace.scope(stem + "_chunk"):
        *arrays, do = _kernel_operands([q, k, v, g, beta, do], s, chunk)
        padded = do.shape[1]

        def flat(side, d, heads, dtype):
            return (side, d), jax.ShapeDtypeStruct((b, padded, heads * d),
                                                   dtype)

        row = ((1, 1, TILE), jax.ShapeDtypeStruct(
            (b, hv, padded // TILE, 1, TILE), _F32))
        dq, dk_, dv_, dg, dbeta = _call(
            kernel, stem + "_bwd", arrays,
            [(("v", dv), do), ((TILE // chunk, dv, dk), states)],
            [flat("k", dk, hk, q.dtype), flat("k", dk, hk, k.dtype),
             flat("v", dv, hv, v.dtype),
             row if g.ndim == 3 else flat("k", dk, hk, _F32), row],
            hk=hk, chunk=chunk, backwards=True, interpret=interpret)
        dbeta = jnp.swapaxes(dbeta.reshape(b, hv, padded), 1, 2)
        if g.ndim == 3:
            dg = _chunk_sums(jnp.swapaxes(dg.reshape(b, hv, padded), 1, 2),
                             chunk, reverse=True)
    return (dq[:, :s].reshape(q.shape), dk_[:, :s].reshape(k.shape),
            dv_[:, :s].reshape(v.shape),
            dg[:, :s].reshape(g.shape).astype(g.dtype),
            dbeta[:, :s].astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_kernels(q, k, v, g, beta, chunk, interpret):
    return _rule_forward(q, k, v, g, beta, chunk, interpret, False)[0]


#: the names the forward rule gives the rule's output and the states its
#: chunks started from (both forms). With q, k, v, g, beta they are all
#: the backward kernel reads, and the output is what ``norm_gate``'s
#: backward reads: a checkpoint policy that keeps the two spares the
#: recomputed forward the forward kernel (``models/stack.py
#: recompute(keep=KEPT)``). Under ``nothing_saveable``, and outside a
#: checkpoint, a name is an identity.
KEPT = ("delta_out", "delta_states")


def report_kept(name: str):
    """A ``recompute(kept=)`` callback: the gauge ``kda.state_kept`` reads
    1 once a block's checkpoint has met a forward's states and kept them."""
    if name == KEPT[1]:
        trace.gauge("kda.state_kept", 1)


def _rule_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    o, states = _rule_forward(q, k, v, g, beta, chunk, interpret, True)
    o, states = checkpoint_name(o, KEPT[0]), checkpoint_name(states, KEPT[1])
    return o, (q, k, v, g, beta, states)


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_backward)


def _over_batch_rows(fn, mesh, args, replicated, out_specs):
    """``fn(*args, *replicated)``; over a mesh of more than one device
    under ``shard_map`` on each device's batch rows (the compiler does
    not partition a Mosaic kernel), ``replicated`` whole on each."""
    if mesh is None or mesh.size == 1:
        return fn(*args, *replicated)

    def rows(tree):
        return jax.tree.map(
            lambda a: P(BATCH_AXES, *(None,) * (a.ndim - 1)), tree)

    return shard_map(
        fn, mesh=mesh,
        in_specs=(*rows(args), *jax.tree.map(lambda _: P(), replicated)),
        out_specs=out_specs, check_vma=False,
    )(*args, *replicated)


def chunk_kda(q, k, v, g, beta, *, chunk: int = 64, segment: int = 16,
              interpret: bool = False, mesh: Optional[Mesh] = None):
    """The chunked gated delta rule. ``q, k (b, s, h, dk)`` (``q``
    already scaled, both already normalised), ``v (b, s, h, dv)``,
    ``g (b, s, h, dk)`` float32 log-decays (<= 0), ``beta (b, s, h)``
    float32 step sizes -> ``o (b, s, h, dv)`` in ``v``'s dtype, which
    is also the matmul operands'. The state starts at zero and is not
    returned. ``chunk`` is a multiple of ``SUB``.

    On the TPU (or with ``interpret``, for the CPU's numerics tests),
    and for a chunk of ``KERNEL_CHUNKS``, the Pallas kernels under one
    ``custom_vjp``; anything else the XLA form, whose rematerialised
    segments are ``segment`` chunks long. ``mesh``: the mesh the
    caller's jit partitions over; the compiler does not partition a
    Mosaic kernel, so over more than one device the kernels run under
    ``shard_map`` on each device's batch rows, every sequence and every
    head whole."""
    if chunk % SUB:
        raise ValueError(f"chunk_kda: chunk {chunk} is no multiple of {SUB}")
    if not ((interpret or _on_tpu()) and chunk in KERNEL_CHUNKS):
        trace.gauge("kda.kernel", 0)
        return _chunk_kda_xla(q, k, v, g, beta, chunk=chunk, segment=segment)

    trace.gauge("kda.kernel", 1)
    trace.gauge("kda.heads_per_step", _heads_a_step(q.shape[2]))
    trace.gauge("kda.chunks_per_step", TILE // chunk)

    def kernels(*args):
        return _rule_kernels(*args, chunk, interpret)

    return _over_batch_rows(kernels, mesh, (q, k, v, g, beta), (),
                            P(BATCH_AXES, None, None, None))


# ---------------------------------------------------------------------------
# The same rule with one decay a head (Gated DeltaNet): the module
# docstring's second form. The walk through the state, the inverse, the
# grid and the calls' wrappers (``_rule_forward``, ``_rule_backward``)
# are the kernels' above; the state-free part and its derivative are
# these.
# ---------------------------------------------------------------------------

def _gdn_part(products, G, b_col, *, chunk: int):
    """A value head's state-free part up to the solve. ``products``:
    ``kk = K K^T`` and ``qk = Q K^T (TILE, TILE)`` float32 of its key
    head, formed once a key head; ``G (TILE, 1)`` float32: the
    cumulative log-decay inside each chunk; ``b_col (TILE, 1)``. Here
    the products meet the mask ``e^(G_i - G_j)``, at most 1 whatever
    ``g`` is."""
    same_sub, same_chunk, _, _, r, c = _masks(chunk)
    kk, qk = products["kk"], products["qk"]
    G_row, b_row = _row(G), _row(b_col)
    decay = jnp.exp(G)
    mask = jnp.where(same_chunk & (c <= r),
                     jnp.exp(jnp.minimum(G - G_row, 0.0)), 0.0)
    a_kk = jnp.where(c < r, kk * mask, 0.0)
    # transposed, as the substitution takes its rows: [j, i] = b_i
    # A_kk[i, j], i's sub-block = j's, j < i (kk is symmetric)
    lt = jnp.where(same_sub & (c > r),
                   kk * jnp.exp(jnp.minimum(G_row - G, 0.0)), 0.0) * b_row
    ltp = lt[:SUB]
    for s in range(1, TILE // SUB):
        ltp = ltp + lt[s * SUB:(s + 1) * SUB]
    lasts = range(chunk - 1, TILE, chunk)
    return {"a_kk": a_kk, "a_qk32": qk * mask, "mask": mask, "ltp": ltp,
            "G": G, "decay": decay, "b_row": b_row,
            "decay_row": jnp.exp(G_row),
            "to_end": jnp.exp(_rows_of(G, lasts, chunk) - G)}


def _gdn_solve(q, k, v, b_col, part, coef, *, chunk: int):
    """The solve and what the chunks read, as ``_solve_part`` returns
    them: ``W_k = T Diag(b e^G) K``, the decays a number a row."""
    dt = v.dtype
    same_sub = _masks(chunk)[0]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    t = _inverse(coef, jnp.where(same_sub, 0.0, part["a_kk"]), b_col, chunk)
    t_v, t_k = t * part["b_row"], t * (part["b_row"] * part["decay_row"])
    if dt == jnp.bfloat16:
        # the float32 factor in three pieces, V and K as they are
        w_v, w_k = _dot_pieces(t_v, v, _NN), _dot_pieces(t_k, k, _NN)
    else:
        w_v = _dot(t_v, v.astype(_F32), _NN, exact=True)
        w_k = _dot(t_k, k32, _NN, exact=True)
    return dict(
        part, t=t, w_v=w_v, w_k32=w_k, w_k=w_k.astype(dt),
        a_qk=part["a_qk32"].astype(dt),
        q_in=(q32 * part["decay"]).astype(dt),
        k_out=(k32 * part["to_end"]).astype(dt),
        # a chunk's whole decay, over the state's lanes
        keep=jnp.concatenate([
            jnp.broadcast_to(part["decay"][at:at + 1], (1, k.shape[1]))
            for at in range(chunk - 1, TILE, chunk)], axis=0))


def _gdn_prep(q_ref, k_ref, v_ref, G_ref, beta_ref, sel, *, chunk, heads, r,
              dk, dv):
    """A grid step's state-free parts: ``heads`` key heads, ``r`` value
    heads each, in the value heads' order. Returns ``(ins, p)``: a value
    head's ``(q, k, v, G, b_col, products)`` and what ``_walk_fwd``
    reads of it."""
    first = pl.program_id(1) * heads * r
    ins = []
    for kh in range(heads):
        q, k = (ref[:, kh * dk:(kh + 1) * dk] for ref in (q_ref, k_ref))
        products = {"kk": _dot(k, k, _NT), "qk": _dot(q, k, _NT)}
        for j in range(kh * r, (kh + 1) * r):
            ins.append((q, k, v_ref[:, j * dv:(j + 1) * dv],
                        _head_column(G_ref, first + j),
                        _head_column(beta_ref, first + j), products))
    parts = [_gdn_part(products, G, b_col, chunk=chunk)
             for _, _, _, G, b_col, products in ins]
    coef = _dot_pieces(
        jnp.concatenate([p["ltp"] for p in parts], axis=0), sel, _NN)
    return ins, [
        _gdn_solve(q, k, v, b_col, part, coef[j * SUB:(j + 1) * SUB],
                   chunk=chunk)
        for j, ((q, k, v, _, b_col, _), part) in enumerate(zip(ins, parts))]


def _gdn_prep_bwd(q, k, v, b_col, p, ct, *, chunk: int):
    """The derivative of a value head's state-free part: its inputs,
    ``p`` (``_gdn_solve``'s), ``ct`` as ``_prep_bwd_tile`` takes them ->
    ``dv`` float32, the rows ``dG, dbeta (1, TILE)``, and what its key
    head sums over its value heads before one set of products: the
    cotangents of ``kk`` and ``qk`` and the direct parts of ``dq, dk``.

    The solve as in ``_prep_bwd_tile``. The mask ``D[i, j] = e^(G_i -
    G_j)`` gives ``dG_i += sum_j M[i, j]`` and ``dG_j -= sum_i M[i,
    j]``, ``M = dA_kk A_kk + dA_qk A_qk``."""
    _, same_chunk, _, _, r, c = _masks(chunk)
    q32, k32, v32 = (a.astype(_F32) for a in (q, k, v))
    dw_v, dw_k, da_qk, dq_in, dk_out, dkeep = (
        ct[n] for n in ("w_v", "w_k", "a_qk", "q_in", "k_out", "keep"))
    G, decay, to_end, mask = (p[n] for n in ("G", "decay", "to_end", "mask"))
    t, w_v, w_k, a_kk = p["t"], p["w_v"], p["w_k32"], p["a_kk"]

    def lanes(x):
        return jnp.sum(x, axis=1, keepdims=True)

    # the solve
    d_rv = _dot(t, dw_v, _TN, exact=True)
    d_rk = _dot(t, dw_k, _TN, exact=True)
    dl = jnp.where(
        same_chunk & (c < r),
        -(_dot(d_rv, w_v, _NT, exact=True) + _dot(d_rk, w_k, _NT, exact=True)),
        0.0)
    rk = lanes(d_rk * k32) * decay
    db = lanes(dl * a_kk) + lanes(d_rv * v32) + rk
    da_kk = b_col * dl
    dv32 = b_col * d_rv
    dk32 = (b_col * decay) * d_rk
    dG = b_col * rk

    # what the scan reads: Q e^G, K e^(G_C - G), e^G_C
    dq32 = dq_in * decay
    dG = dG + lanes(dq_in * q32) * decay
    e = lanes(dk_out * k32) * to_end
    dk32 = dk32 + dk_out * to_end
    dG = dG - e
    row = _iota(G.shape, 0)
    for i, last in enumerate(range(chunk - 1, TILE, chunk)):
        total = (jnp.sum(e[last + 1 - chunk:last + 1], axis=0, keepdims=True)
                 + lanes(dkeep[i:i + 1]) * jnp.exp(G[last:last + 1]))
        dG = dG + jnp.where(row == last, total, 0.0)

    # the masked products
    m = da_kk * a_kk + da_qk * p["a_qk32"]
    dG_row = _row(dG + lanes(m)) - jnp.sum(m, axis=0, keepdims=True)
    return (dq32, dk32, dv32, dG_row, _row(db), da_kk * mask, da_qk * mask)


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, sel_ref, o_ref,
                    *rest, chunk, heads, dk, dv):
    """``_fwd_kernel`` for the per-head form: a tile of ``heads`` key
    heads and their ``heads r`` value heads' chains a grid step."""
    states_ref = rest[0] if len(rest) == 2 else None
    s_ref = rest[-1]
    r = s_ref.shape[0] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    _, p = _gdn_prep(q_ref, k_ref, v_ref, G_ref, beta_ref, sel_ref[...],
                     chunk=chunk, heads=heads, r=r, dk=dk, dv=dv)
    _walk_fwd(p, s_ref, states_ref, o_ref, chunk=chunk, heads=heads * r,
              dv=dv)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, G_ref, beta_ref, sel_ref, do_ref,
                    states_ref, dq_ref, dk_ref, dv_ref, dG_ref, dbeta_ref,
                    ds_ref, *, chunk, heads, dk, dv):
    """``_bwd_kernel`` for the per-head form. A key head's ``dq, dk``
    are the sums over its value heads: the cotangents of its two
    products are summed first, then applied once."""
    dt = do_ref.dtype
    r = ds_ref.shape[0] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    ins, p = _gdn_prep(q_ref, k_ref, v_ref, G_ref, beta_ref, sel_ref[...],
                       chunk=chunk, heads=heads, r=r, dk=dk, dv=dv)
    cts, ds = _walk_bwd(p, do_ref, states_ref, ds_ref, chunk=chunk,
                        heads=heads * r, dv=dv)
    for kh in range(heads):
        total = None
        for j in range(kh * r, (kh + 1) * r):
            ct = {n: jnp.concatenate(x, axis=0) for n, x in cts[j].items()}
            q, k, v, _, b_col, _ = ins[j]
            dq, dk_, dv_, dG, db, dkk, dqk = _gdn_prep_bwd(
                q, k, v, b_col, p[j], ct, chunk=chunk)
            dv_ref[:, j * dv:(j + 1) * dv] = dv_.astype(dv_ref.dtype)
            dG_ref[j, 0] = dG
            dbeta_ref[j, 0] = db
            ds_ref[j] = ds[j]
            mine = (dq, dk_, dkk, dqk)
            total = mine if total is None else tuple(
                a + b for a, b in zip(total, mine))
        dq, dk_, dkk, dqk = total
        dkk, dqk = dkk.astype(dt), dqk.astype(dt)
        kc = slice(kh * dk, (kh + 1) * dk)
        dq_ref[:, kc] = (dq + _dot(dqk, k, _NN)).astype(dq_ref.dtype)
        dk_ref[:, kc] = (dk_ + _dot(dkk, k, _NN) + _dot(dkk, k, _TN)
                         + _dot(dqk, q, _TN)).astype(dk_ref.dtype)


def chunk_gdn(q, k, v, g, beta, *, chunk: int = 64,
              interpret: bool = False, mesh: Optional[Mesh] = None):
    """The chunked gated delta rule with **one decay a head** over
    grouped value heads (Gated DeltaNet): ``q, k (b, s, hk, dk)`` (``q``
    already scaled, both already normalised), ``v (b, s, hv, dv)``, ``g,
    beta (b, s, hv)`` float32 -> ``o (b, s, hv, dv)`` in ``v``'s dtype;
    value head ``j`` reads key head ``j // (hv / hk)``. Exact for any
    ``g <= 0``. The choice of form, ``interpret`` and
    ``mesh`` as in ``chunk_kda`` (kernels ``gdn_fwd``, ``gdn_bwd``); the
    gauge ``attn.gdn_kernel`` says which form the traced step took."""
    if v.shape[2] % q.shape[2]:
        raise ValueError(f"chunk_gdn: {v.shape[2]} value heads over "
                         f"{q.shape[2]} key heads")
    if not ((interpret or _on_tpu()) and chunk in KERNEL_CHUNKS):
        trace.gauge("attn.gdn_kernel", 0)
        return _chunk_gdn_xla(q, k, v, g, beta, chunk=chunk)
    trace.gauge("attn.gdn_kernel", 1)

    def kernels(*args):
        return _rule_kernels(*args, chunk, interpret)

    return _over_batch_rows(kernels, mesh, (q, k, v, g, beta), (),
                            P(BATCH_AXES, None, None, None))


# ---------------------------------------------------------------------------
# The elementwise passes around the kernels (docs/design/kernels.md 1e):
# what the layer does to the projections before the delta rule and to
# its output after it, each direction one Pallas pass that reads every
# operand once and writes every result once, float32 from the load to
# the store, on blocks of the ``(b, s, h d)`` arrays where they lie.
# ---------------------------------------------------------------------------

IO_ROWS = 256     # tokens a grid step of a pass, of HEADS heads' lanes
HALO = 16         # rows of the small block before (after) a tile: the
                  # convolution's w - 1 rows, in whole bf16 sublane tiles
_L2_EPS = 1e-6


def _l2_norm(x32):
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                           + _L2_EPS)


def _conv_silu_norm_xla(xs, taps, heads, scales):
    out = []
    for x, w, scale in zip(xs, taps, scales):
        b, s, _ = x.shape
        z = jax.nn.silu(causal_conv(x, w)).reshape(b, s, heads, -1)
        if scale is not None:
            z = (_l2_norm(z.astype(_F32)) * scale).astype(x.dtype)
        out.append(z)
    return tuple(out)


_GATES = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


def _norm_gate_xla(o, gate, weight, eps, act="sigmoid"):
    b, s = o.shape[:2]
    o = rms_norm(o, weight, eps)
    o = (o.astype(_F32) * _GATES[act](gate.astype(_F32))).astype(o.dtype)
    return o.reshape(b, s, -1)


def _rows_after(x, before, j):
    """``x (rows, L)``, ``before (HALO, L)`` float32: row ``t`` of the
    result is row ``t - j`` of ``[before; x]`` (``j <= HALO``)."""
    if not j:
        return x
    rolled = pltpu.roll(x, j, 0)
    head = jnp.where(_iota(before.shape, 0) < j, pltpu.roll(before, j, 0),
                     rolled[:HALO])
    if x.shape[0] == HALO:
        return head
    return jnp.concatenate([head, rolled[HALO:]], axis=0)


def _rows_before(x, after, j):
    """Row ``t`` of the result is row ``t + j`` of ``[x; after]``."""
    if not j:
        return x
    rolled = pltpu.roll(x, x.shape[0] - j, 0)
    tail = jnp.where(_iota(after.shape, 0) >= HALO - j,
                     pltpu.roll(after, HALO - j, 0), rolled[-HALO:])
    return jnp.concatenate([rolled[:-HALO], tail], axis=0)


def _conv_rows(x, before, w):
    """The causal convolution of a tile: ``w (taps, L)``, the last tap
    the token's own -> ``y`` and the shifted tiles it summed, a tap
    each."""
    n = w.shape[0]
    shifted = [_rows_after(x, before, n - 1 - i) for i in range(n)]
    y = w[0:1] * shifted[0]
    for i in range(1, n):
        y = y + w[i:i + 1] * shifted[i]
    return y, shifted


def _by_head(fn, d, *arrays):
    """``fn`` on the ``d`` lanes of each head of ``(rows, heads d)``
    arrays."""
    width = arrays[0].shape[1]
    return jnp.concatenate(
        [fn(*(a[:, at:at + d] for a in arrays)) for at in range(0, width, d)],
        axis=1)


def _sigmoid(x):
    # through tanh: one transcendental and no division, which the vector
    # unit would do in software (the input pass 14 % shorter on the chip)
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _silu_norm(y, scale, d):
    """``SiLU``, then (``scale`` not None) the L2 norm a head times
    ``scale``."""
    z = y * _sigmoid(y)
    if scale is None:
        return z
    return _by_head(
        lambda zh: zh * (lax.rsqrt(_lane_sum(zh * zh) + _L2_EPS) * scale),
        d, z)


def _silu_norm_bwd(y, dout, scale, d):
    """The cotangent of ``y`` from that of ``_silu_norm``'s result."""
    sig = _sigmoid(y)
    if scale is not None:
        def head(zh, dh):
            r = lax.rsqrt(_lane_sum(zh * zh) + _L2_EPS)
            back = _lane_sum(dh * zh) * (r * r)
            return (scale * r) * (dh - zh * back)
        dout = _by_head(head, d, y * sig, dout)
    return dout * (sig * (1.0 + y * (1.0 - sig)))


def _in_fwd_kernel(*refs, scales, d):
    """A tile of each projection: ``refs`` are (tile, the ``HALO`` rows
    before it) an array, the taps ``(arrays, taps, L)``, then the
    outputs."""
    n = len(scales)
    taps_ref, outs = refs[2 * n], refs[2 * n + 1:]
    first = pl.program_id(2) == 0
    for i, scale in enumerate(scales):
        x = refs[2 * i][...].astype(_F32)
        # zeros before the sequence
        before = jnp.where(first, 0.0, refs[2 * i + 1][...].astype(_F32))
        y, _ = _conv_rows(x, before, taps_ref[i])
        outs[i][...] = _silu_norm(y, scale, d).astype(outs[i].dtype)


def _in_bwd_kernel(*refs, scales, d):
    """``refs``: an array (its tile, the ``HALO`` rows before and after
    it, the cotangent's tile and the rows after it), the taps, then the
    projections' gradients and the taps' (float32, summed over the
    tiles). The pre-activation is formed again, of the tile and of the
    rows after it: the convolution's transpose reads the cotangent of
    ``y`` up to ``taps - 1`` rows past the tile."""
    n = len(scales)
    taps_ref, dxs, dtaps_ref = refs[5 * n], refs[5 * n + 1:-1], refs[-1]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for i, scale in enumerate(scales):
        x, before, after, dout, dout_after = (
            r[...].astype(_F32) for r in refs[5 * i:5 * i + 5])
        before = jnp.where(first, 0.0, before)
        # nothing past the sequence reads a token
        dout_after = jnp.where(last, 0.0, dout_after)
        w = taps_ref[i]
        taps = w.shape[0]
        y, shifted = _conv_rows(x, before, w)
        y_after, _ = _conv_rows(after, x[-HALO:], w)
        dy = _silu_norm_bwd(y, dout, scale, d)
        dy_after = _silu_norm_bwd(y_after, dout_after, scale, d)
        dx = w[taps - 1:taps] * dy
        for j in range(1, taps):
            dx = dx + w[taps - 1 - j:taps - j] * _rows_before(dy, dy_after, j)
        dxs[i][...] = dx.astype(dxs[i].dtype)
        for k in range(taps):
            dtaps_ref[i, k:k + 1, :] += jnp.sum(
                dy * shifted[k], axis=0, keepdims=True)


def _io_call(kernel, name, ins, outs, *, heads, interpret):
    """A pass over ``(b, s, h d)`` arrays of whole tiles. ``ins`` and
    ``outs``: ``(kind, array or its shape)``, ``kind`` the block a grid
    step takes: "tile", ``IO_ROWS`` tokens; "before" and "after", the
    ``HALO`` rows that end where the tile starts and start where it ends
    (the sequence's own first and last where there are none: the kernel
    masks them); "lanes", the step's lanes of a small ``(..., h d)``
    array (taps, a norm's weight); "sums", the same of a ``(b, ..., h
    d)`` output the kernel adds to tile by tile. Grid (batch, lanes,
    tiles), the tiles in order."""
    b, s, width = next(x.shape for kind, x in ins if kind == "tile")
    lanes = _heads_a_step(heads) * (width // heads)
    per = IO_ROWS // HALO

    def spec(kind, x):
        if kind == "tile":
            return pl.BlockSpec((None, IO_ROWS, lanes),
                                lambda bi, li, ti: (bi, ti, li))
        if kind == "before":
            return pl.BlockSpec(
                (None, HALO, lanes),
                lambda bi, li, ti: (bi, jnp.maximum(ti * per - 1, 0), li))
        if kind == "after":
            return pl.BlockSpec(
                (None, HALO, lanes),
                lambda bi, li, ti: (
                    bi, jnp.minimum((ti + 1) * per, s // HALO - 1), li))
        if kind == "lanes":
            lead = x.shape[:-1]
            return pl.BlockSpec(
                lead + (lanes,), lambda bi, li, ti: (0,) * len(lead) + (li,))
        lead = x.shape[1:-1]
        return pl.BlockSpec(
            (None,) + lead + (lanes,),
            lambda bi, li, ti: (bi,) + (0,) * len(lead) + (li,))

    return pl.pallas_call(
        kernel,
        grid=(b, width // lanes, s // IO_ROWS),
        in_specs=[spec(*x) for x in ins],
        out_specs=[spec(*x) for x in outs],
        out_shape=[x for _, x in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*(x for _, x in ins))


def _io_tiles(arrays):
    """``(b, s, ...)`` -> ``(b, s, h d)``, whole tiles of ``IO_ROWS``."""
    s = arrays[0].shape[1]
    pad = ((0, 0), (0, -s % IO_ROWS), (0, 0))
    return [jnp.pad(a.reshape(*a.shape[:2], -1), pad) for a in arrays]


def _like(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def _taps_rows(taps):
    """``[(h d, taps)]`` -> ``(arrays, taps, h d)`` float32: a tap is a
    row of lanes."""
    return jnp.stack([w.astype(_F32).T for w in taps])


@functools.partial(jax.jit, static_argnums=(2, 3, 4), inline=True)
def _in_forward(xs, taps, heads, scales, interpret):
    b, s, width = xs[0].shape
    tiled = _io_tiles(xs)
    out = _io_call(
        functools.partial(_in_fwd_kernel, scales=scales, d=width // heads),
        "kda_in_fwd",
        [(kind, x) for x in tiled for kind in ("tile", "before")]
        + [("lanes", _taps_rows(taps))],
        [("tile", _like(x)) for x in tiled],
        heads=heads, interpret=interpret)
    return tuple(o[:, :s].reshape(b, s, heads, -1) for o in out)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _in_backward(heads, scales, interpret, scope, res, cts):
    xs, taps = res
    b, s, width = xs[0].shape
    # a custom_vjp's backward is traced outside the caller's scopes
    with trace.scope(scope):
        rows, tiled = _taps_rows(taps), _io_tiles(xs)
        ins = []
        for x, dout in zip(tiled, _io_tiles(cts)):
            ins += [(kind, x) for kind in ("tile", "before", "after")]
            ins += [(kind, dout) for kind in ("tile", "after")]
        *dxs, dtaps = _io_call(
            functools.partial(_in_bwd_kernel, scales=scales, d=width // heads),
            "kda_in_bwd", ins + [("lanes", rows)],
            [("tile", _like(x)) for x in tiled]
            + [("sums", jax.ShapeDtypeStruct((b,) + rows.shape, _F32))],
            heads=heads, interpret=interpret)
        dtaps = jnp.sum(dtaps, axis=0)
    return (tuple(dx[:, :s] for dx in dxs),
            tuple(dw.T.astype(w.dtype) for dw, w in zip(dtaps, taps)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _in_pass(xs, taps, heads, scales, interpret, scope):
    return _in_forward(xs, taps, heads, scales, interpret)


def _in_pass_fwd(xs, taps, heads, scales, interpret, scope):
    return _in_forward(xs, taps, heads, scales, interpret), (xs, taps)


_in_pass.defvjp(_in_pass_fwd, _in_backward)


def _gate_act(gate, sig, act: str):
    """The output gate from ``sig = sigmoid(gate)``: ``sigmoid`` (KDA)
    or ``silu`` (Gated DeltaNet)."""
    return sig if act == "sigmoid" else gate * sig


def _gate_slope(gate, sig, act: str):
    """Its derivative."""
    if act == "sigmoid":
        return sig * (1.0 - sig)
    return sig * (1.0 + gate * (1.0 - sig))


def _out_fwd_kernel(o_ref, gate_ref, w_ref, out_ref, *, d, eps, act):
    for at in range(0, o_ref.shape[1], d):
        o, gate, w = (r[:, at:at + d].astype(_F32)
                      for r in (o_ref, gate_ref, w_ref))
        r = lax.rsqrt(_lane_sum(o * o) * (1.0 / d) + eps)
        out_ref[:, at:at + d] = (
            o * r * w * _gate_act(gate, _sigmoid(gate), act)).astype(
                out_ref.dtype)


def _out_bwd_kernel(dout_ref, o_ref, gate_ref, w_ref, do_ref, dgate_ref,
                    dw_ref, *, d, eps, act):
    """The norm is formed again from ``o``; the weight's gradient
    (float32) is summed over the tiles, a head's lanes each."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for at in range(0, o_ref.shape[1], d):
        dout, o, gate, w = (r[:, at:at + d].astype(_F32)
                            for r in (dout_ref, o_ref, gate_ref, w_ref))
        r = lax.rsqrt(_lane_sum(o * o) * (1.0 / d) + eps)
        n, sig = o * r, _sigmoid(gate)
        gated = _gate_act(gate, sig, act)
        dn_w = dout * n                    # the cotangent of w act(gate)
        dgate_ref[:, at:at + d] = (
            dn_w * w * _gate_slope(gate, sig, act)).astype(dgate_ref.dtype)
        dw_ref[:, at:at + d] += jnp.sum(dn_w * gated, axis=0, keepdims=True)
        dn = dout * (w * gated)
        do_ref[:, at:at + d] = (r * (
            dn - n * (_lane_sum(dn * n) * (1.0 / d)))
        ).astype(do_ref.dtype)


def _weight_row(weight, heads):
    """``(d,)`` -> ``(1, h d)`` float32: the norm's weight, a head each."""
    return jnp.tile(weight.astype(_F32), heads)[None]


@functools.partial(jax.jit, static_argnums=(3, 4, 5), inline=True)
def _out_forward(o, gate, weight, eps, interpret, act):
    b, s, heads, d = o.shape
    o, gate = _io_tiles([o, gate])
    out, = _io_call(
        functools.partial(_out_fwd_kernel, d=d, eps=eps, act=act),
        "kda_out_fwd",
        [("tile", o), ("tile", gate), ("lanes", _weight_row(weight, heads))],
        [("tile", _like(o))], heads=heads, interpret=interpret)
    return out[:, :s]


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), inline=True)
def _out_backward(eps, interpret, act, scope, res, dout):
    o, gate, weight = res
    b, s, heads, d = o.shape
    with trace.scope(scope):
        tiled = _io_tiles([dout, o, gate])
        do, dgate, dw = _io_call(
            functools.partial(_out_bwd_kernel, d=d, eps=eps, act=act),
            "kda_out_bwd",
            [("tile", x) for x in tiled]
            + [("lanes", _weight_row(weight, heads))],
            [("tile", _like(tiled[1])), ("tile", _like(tiled[2])),
             ("sums", jax.ShapeDtypeStruct((b, 1, heads * d), _F32))],
            heads=heads, interpret=interpret)
        dw = jnp.sum(dw.reshape(b * heads, d), axis=0)
    return (do[:, :s].reshape(o.shape), dgate[:, :s].reshape(gate.shape),
            dw.astype(weight.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _out_pass(o, gate, weight, eps, interpret, act, scope):
    return _out_forward(o, gate, weight, eps, interpret, act)


def _out_pass_fwd(o, gate, weight, eps, interpret, act, scope):
    return (_out_forward(o, gate, weight, eps, interpret, act),
            (o, gate, weight))


_out_pass.defvjp(_out_pass_fwd, _out_backward)


def _io_fused(interpret: bool, d: int) -> bool:
    """Whether the passes run: on the TPU where a head's channels are
    whole lanes (or in interpret mode, for the CPU's numerics tests)."""
    fused = interpret or (_on_tpu() and d % 128 == 0)
    trace.gauge("kda.io_fused", int(fused))
    return fused


def conv_silu_norm(xs, taps, *, heads: int, scales,
                   scope: str = "kda_conv", interpret: bool = False,
                   mesh: Optional[Mesh] = None):
    """What a KDA layer does to its projections before the delta rule.
    ``xs``: the projections, ``(b, s, h d)`` each; ``taps``: each one's
    depthwise causal convolution, ``(h d, w)``; ``scales``: for each,
    the factor on its L2 norm a head, or None for no norm -> ``SiLU(conv
    (x))``, normed a head and scaled, ``(b, s, h, d)`` each in ``x``'s
    dtype.

    On the TPU with heads of whole lanes (or with ``interpret``) one
    Pallas pass each way: float32 from the load to the one store, the
    backward forms the pre-activation again from the projections. Else
    XLA's ops, which round to ``x``'s dtype after the convolution and
    after the SiLU. ``mesh`` as in ``chunk_kda``. ``scope``: the named
    scope the caller holds this under, which the hand-written backward
    opens again (it is traced outside the caller's). Arrays of one call
    have one width: a layer whose v has more heads than its q and k (a
    Gated DeltaNet) makes two calls, v's with ``scales=(None,)``."""
    xs, taps, scales = tuple(xs), tuple(taps), tuple(scales)
    if not _io_fused(interpret, xs[0].shape[-1] // heads):
        return _conv_silu_norm_xla(xs, taps, heads, scales)
    wide = P(BATCH_AXES, None, None, None)
    return _over_batch_rows(
        lambda xs, taps: _in_pass(xs, taps, heads, scales, interpret,
                                  scope),
        mesh, (xs,), (taps,), (wide,) * len(xs))


def norm_gate(o, gate, weight, eps: float, *, act: str = "sigmoid",
              scope: str = "kda_out", interpret: bool = False,
              mesh: Optional[Mesh] = None):
    """What the layer does to the delta rule's output: ``o, gate (b, s,
    h, d)``, ``weight (d,)`` -> ``RMSNorm_d(o) weight act(gate)`` as
    ``(b, s, h d)`` in ``o``'s dtype; ``act`` is ``"sigmoid"`` (KDA) or
    ``"silu"`` (Gated DeltaNet); ``scope`` as in ``conv_silu_norm``. The
    same choice of form as ``conv_silu_norm``; the XLA form rounds the
    norm to ``o``'s dtype before the gate."""
    if act not in _GATES:
        raise ValueError(f"norm_gate: act={act!r}: one of {sorted(_GATES)}")
    if not _io_fused(interpret, o.shape[-1]):
        return _norm_gate_xla(o, gate, weight, eps, act)
    return _over_batch_rows(
        lambda o, gate, weight: _out_pass(
            o, gate, weight, eps, interpret, act, scope),
        mesh, (o, gate), (weight,), P(BATCH_AXES, None, None))
