"""Kimi Delta Attention (arXiv 2510.26692): a gated delta rule with a
decay per channel, in chunked form, and the short depthwise causal
convolution its inputs pass through. Plain XLA ops: matrix products on
the MXU, one triangular solve a chunk, a ``lax.scan`` over the chunks.

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

Everything but the last three lines is formed for all chunks at once
(batched products); the last three are the scan's body, ``seq / C``
steps carrying the (dk, dv) state.

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
  large factor overflows to inf. ``tests/test_kda.py`` holds the form at
  ``g = -5`` a token (G = -320 over a chunk) and at the bound against
  the token-by-token recurrence.
- ``e^G_i``, ``e^(G_C - G_i)`` and ``e^G_C`` are at most 1 as they are.

Operands go to the MXU in the activations' dtype and accumulate in
float32; gates, cumulative decays, the solve, ``U`` and the state are
float32 (the state enters its products in the activations' dtype).

**The backward** is JAX's own through the batched part, the solve and
the scans. What autodiff keeps of the chunked form (about twenty
(tokens, heads x 128) arrays and a state a chunk: 3.4 GiB a layer at
8192 tokens) would be the step's peak, so the sequence is walked in
*segments* of 16 chunks by an outer scan whose body is rematerialised:
the op keeps its five inputs and a state a segment, and the backward
forms one segment's intermediates at a time. docs/design/kernels.md 1e
has the sizes and why no ``custom_vjp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

SUB = 16          # rows of a sub-block of a chunk
_MID = SUB // 2 - 1   # the reference row inside a sub-block


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


def chunk_kda(q, k, v, g, beta, *, chunk: int = 64, segment: int = 16):
    """The chunked gated delta rule. ``q, k (b, s, h, dk)`` (``q``
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
    seg = min(segment, -(-s // chunk))
    pad = -s % (chunk * seg)
    if pad:
        # rows past the end: no decay, no step, nothing read back
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
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
