"""Lightning attention: linear attention whose state decays by a constant
of the head, ``S_t = lambda_h S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``,
``lambda_h = exp(-s_h)`` with the slope ``s_h >= 0`` a number the model
states and never trains (MiniMax-01's and MiniCPM-SALA's
``lightning-attn`` layers).

Chunked, with ``C`` tokens a chunk and ``S`` the state before it::

    o_i  = sum_(j <= i) exp(-s (i - j)) (q_i . k_j) v_j      within
         + exp(-s (i + 1)) q_i S                             across
    S'   = exp(-s C) S + sum_j exp(-s (C - 1 - j)) k_j^T v_j

Every factor is ``exp`` of something at most 0, so no slope overflows and
none needs a bound; there is no ``(I - beta k k^T)`` correction and so no
inverse, which is why this is a module of its own beside ``ops/kda.py``'s
delta rules and not a third form of their walk (docs/design/kernels.md).

- `lightning_attention`: on the TPU (and under ``interpret``) two Pallas
  kernels under one ``custom_vjp``, ``lightning_fwd`` and
  ``lightning_bwd``: a grid step is one chunk of one head, read where it
  lies in the ``(b, s, h d)`` arrays (a head's channels are a block's
  lanes; nothing is transposed in HBM), the ``(d, d)`` float32 state in
  VMEM scratch along the grid's last axis (from the end in the backward,
  which carries the state's cotangent and reads the forward's states a
  chunk). The decay tables (``exp(-s (i - j))`` under the diagonal, the
  two row factors) are made once a call by XLA from the slopes and stay
  resident a head. Operands go to the MXU in the dtype they arrive in,
  products and the state are float32.
- Off the TPU the same chunked equations in XLA's ops (`_chunked_xla`),
  differentiated by JAX: the kernels' oracle.

The forward names its output and states (`KEPT`; both forms do), all its
backward reads beside q, k and v: a block whose checkpoint keeps them
never runs ``lightning_fwd`` twice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.ops.kda import _NN, _NT, _TN, _dot, _over_batch_rows
from dlrover_tpu.parallel.mesh import BATCH_AXES

_F32 = jnp.float32

#: the forward's output and its states a chunk, by name
KEPT = ("la_out", "la_states")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def report_kept(name: str):
    """A ``recompute(kept=)`` callback: ``la.state_kept`` reads 1 once a
    block's checkpoint has met the forward's states and kept them."""
    if name == KEPT[1]:
        trace.gauge("la.state_kept", 1)


def decay_tables(slopes, chunk: int):
    """``slopes (h,)`` -> ``(D (h, C, C), q_decay (h, C), k_decay (h, C))``
    float32: ``D[i, j] = exp(-s (i - j))`` for ``i >= j`` and 0 above,
    ``exp(-s (i + 1))`` and ``exp(-s (C - 1 - j))``."""
    s = slopes.astype(_F32)[:, None]
    pos = jnp.arange(chunk, dtype=_F32)
    gap = pos[:, None] - pos[None, :]
    D = jnp.where(gap >= 0, jnp.exp(-s[..., None] * jnp.maximum(gap, 0.0)),
                  0.0)
    return D, jnp.exp(-s * (pos + 1.0)), jnp.exp(-s * (chunk - 1.0 - pos))


def _chunked_xla(q, k, v, slopes, chunk: int):
    """The chunked equations as they stand; every chunk's state exists at
    once (``(b, s / C, h, d, d)`` float32)."""
    b, s, h, d = q.shape
    n, dt = s // chunk, v.dtype
    D, q_decay, k_decay = decay_tables(slopes, chunk)
    qc, kc, vc = (a.reshape(b, n, chunk, h, a.shape[-1]) for a in (q, k, v))
    within = jnp.einsum("bnihd,bnjhd->bnhij", qc, kc,
                        preferred_element_type=_F32) * D
    o = jnp.einsum("bnhij,bnjhe->bnihe", within.astype(dt), vc,
                   preferred_element_type=_F32)
    kd = (kc.astype(_F32) * k_decay.T[:, :, None]).astype(dt)
    added = jnp.einsum("bnjhd,bnjhe->nbhde", kd, vc,
                       preferred_element_type=_F32)
    whole = jnp.exp(-slopes.astype(_F32) * chunk)[None, :, None, None]

    def step(S, add):
        return whole * S + add, S

    _, states = lax.scan(step, jnp.zeros_like(added[0]), added)
    states = checkpoint_name(states, KEPT[1])
    qd = (qc.astype(_F32) * q_decay.T[:, :, None]).astype(dt)
    o = o + jnp.einsum("bnihd,nbhde->bnihe", qd, states.astype(dt),
                       preferred_element_type=_F32)
    return checkpoint_name(o.reshape(b, s, h, v.shape[-1]).astype(dt),
                           KEPT[0])


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _decayed(x, decay):
    """``x (C, d)`` times a row factor ``(C, d)`` float32, in ``x``'s
    dtype."""
    return (x.astype(_F32) * decay).astype(x.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, D_ref, qd_ref, kd_ref, o_ref, *rest,
                states: bool):
    st_ref, S = rest if states else (None,) + rest
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        S[...] = jnp.zeros_like(S)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]                     # (C, d)
    before = S[...]
    if states:
        st_ref[0, 0, 0] = before
    within = _dot(q, k, _NT) * D_ref[0]                        # (C, C)
    o = _dot(within.astype(v.dtype), v, _NN) + _dot(
        _decayed(q, qd_ref[0]), before.astype(q.dtype), _NN)
    o_ref[0] = o.astype(o_ref.dtype)
    whole = qd_ref[0, chunk - 1:chunk, :]                      # exp(-s C)
    S[...] = before * whole + _dot(_decayed(k, kd_ref[0]), v, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, st_ref, D_ref, qd_ref, kd_ref,
                dq_ref, dk_ref, dv_ref, dS):
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dS[...] = jnp.zeros_like(dS)

    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    dt = q.dtype
    D, q_decay, k_decay = D_ref[0], qd_ref[0], kd_ref[0]
    before = st_ref[0, 0, 0].astype(dt)        # the state this chunk read
    after = dS[...]                            # d of the state it wrote
    within = (_dot(q, k, _NT) * D).astype(dt)                  # [i, j]
    d_within = (_dot(do, v, _NT) * D).astype(dt)
    dq_ref[0] = (_dot(d_within, k, _NN)
                 + _dot(do, before, _NT) * q_decay).astype(dq_ref.dtype)
    dk_ref[0] = (_dot(d_within, q, _TN)
                 + _dot(v, after.astype(dt), _NT) * k_decay
                 ).astype(dk_ref.dtype)
    dv_ref[0] = (_dot(within, do, _TN)
                 + _dot(k, after.astype(dt), _NN) * k_decay
                 ).astype(dv_ref.dtype)
    whole = qd_ref[0, chunk - 1:chunk, :]
    dS[...] = after * whole + _dot(_decayed(q, q_decay), do, _TN)


def _call(kernel, name, arrays, states_in, out_shapes, *, h, chunk, slopes,
          backwards, interpret, states_out=False):
    """``arrays``: ``(b, s, h d)`` each, a head's chunk a block; the grid
    is (batch, heads, chunks), the last axis in order (from the end where
    ``backwards``): it carries the state."""
    b, s, width = arrays[0].shape
    d, n = width // h, s // chunk
    D, q_decay, k_decay = decay_tables(slopes, chunk)
    rows = [jnp.broadcast_to(a[:, :, None], (h, chunk, d))
            for a in (q_decay, k_decay)]

    def at(ci):
        return n - 1 - ci if backwards else ci

    tokens = pl.BlockSpec((1, chunk, d), lambda bi, hi, ci: (bi, at(ci), hi))
    state = pl.BlockSpec((1, 1, 1, d, d),
                         lambda bi, hi, ci: (bi, hi, at(ci), 0, 0))
    a_head = [pl.BlockSpec((1, chunk, lanes), lambda bi, hi, ci: (hi, 0, 0))
              for lanes in (chunk, d, d)]
    return pl.pallas_call(
        kernel,
        grid=(b, h, n),
        in_specs=[tokens] * len(arrays) + [state] * len(states_in) + a_head,
        out_specs=[tokens] * len(out_shapes) + [state] * states_out,
        out_shape=list(out_shapes) + [
            jax.ShapeDtypeStruct((b, h, n, d, d), _F32)] * states_out,
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*arrays, *states_in, D, *rows)


def _wide(x):
    return x.reshape(*x.shape[:2], -1)


@functools.partial(jax.jit, static_argnums=(4, 5, 6), inline=True)
def _forward(q, k, v, slopes, chunk, interpret, states: bool):
    b, s, h, d = q.shape
    out = _call(
        functools.partial(_fwd_kernel, states=states), "lightning_fwd",
        [_wide(a) for a in (q, k, v)], (),
        [jax.ShapeDtypeStruct((b, s, h * d), v.dtype)], h=h, chunk=chunk,
        slopes=slopes, backwards=False, interpret=interpret,
        states_out=states)
    return (out[0].reshape(b, s, h, d),) + tuple(out[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _kernels(q, k, v, slopes, chunk, interpret):
    return _forward(q, k, v, slopes, chunk, interpret, False)[0]


def _kernels_fwd(q, k, v, slopes, chunk, interpret):
    o, states = _forward(q, k, v, slopes, chunk, interpret, True)
    o = checkpoint_name(o, KEPT[0])
    return o, (q, k, v, slopes, checkpoint_name(states, KEPT[1]))


def _kernels_bwd(chunk, interpret, res, do):
    q, k, v, slopes, states = res
    b, s, h, d = q.shape
    with trace.scope("la_chunk"):
        grads = _call(
            _bwd_kernel, "lightning_bwd",
            [_wide(a) for a in (q, k, v, do.astype(v.dtype))], (states,),
            [jax.ShapeDtypeStruct((b, s, h * d), a.dtype) for a in (q, k, v)],
            h=h, chunk=chunk, slopes=slopes, backwards=True,
            interpret=interpret)
    return (*(g.reshape(b, s, h, d) for g in grads), jnp.zeros_like(slopes))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def lightning_attention(q, k, v, slopes, *, chunk: int = 256,
                        interpret: bool = False,
                        mesh: Optional[Mesh] = None):
    """``q, k, v (b, s, h, d)`` (``q`` already scaled), ``slopes (h,)``
    float32, the decay's ``s_h >= 0`` -> ``o (b, s, h, d)`` in ``v``'s
    dtype, differentiable in q, k and v (a slope is a constant: its
    cotangent is zero). ``chunk`` must divide the sequence. ``mesh``: over
    more than one device the kernels run under ``shard_map`` on each
    device's batch rows. The gauges ``la.kernel`` and ``la.chunk`` say
    which form the traced step took."""
    s = q.shape[1]
    if s % chunk:
        raise ValueError(f"lightning_attention: chunk {chunk} does not "
                         f"divide the sequence {s}")
    trace.gauge("la.chunk", chunk)
    if not (interpret or _on_tpu()):
        trace.gauge("la.kernel", 0)
        return _chunked_xla(q, k, v, slopes, chunk)
    trace.gauge("la.kernel", 1)
    return _over_batch_rows(
        lambda q, k, v, slopes: _kernels(q, k, v, slopes, chunk, interpret),
        mesh, (q, k, v), (slopes,), P(BATCH_AXES, None, None, None))


def recurrence(q, k, v, slopes):
    """The definition, a token a step (tests): float32."""
    decay = jnp.exp(-slopes.astype(_F32))[None, :, None, None]

    def step(S, x):
        q, k, v = x
        S = decay * S + k[..., :, None] * v[..., None, :]
        return S, jnp.einsum("bhd,bhde->bhe", q, S)

    b, _, h, d = q.shape
    _, o = lax.scan(step, jnp.zeros((b, h, d, v.shape[-1]), _F32), tuple(
        jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v)))
    return jnp.moveaxis(o, 0, 1)
