"""What every family decides alike about a stack of layers, decided once:
how a block is recomputed (`recompute`), where a layer's parameters live
(`Part`, `periodic`, `runs`, `locate`, `layer_params`), how the layers are
walked (`walk`) and how a hidden state becomes the loss (`shift_targets`,
`next_token_loss`), with the two readers of a tree's shapes. A family's
shapes, initial values, partition and block stay in its own file.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.ops import cross_entropy_sums


def recompute(fn: Callable, remat: bool, keep: Sequence[str] = (),
              kept: Optional[Callable[[str], None]] = None) -> Callable:
    """``fn`` as the backward pass will have it: itself where ``remat`` is
    off; else recomputed whole from its arguments, but for the residuals
    it named (``checkpoint_name``) with one of ``keep``, which stay.
    ``kept(name)`` is called, while the backward is traced, each time the
    policy keeps one. The only ``jax.checkpoint`` under ``models/``."""
    if not remat:
        return fn
    policy = jax.checkpoint_policies.nothing_saveable
    if keep:
        named = jax.checkpoint_policies.save_only_these_names(*keep)

        def policy(prim, *avals, **params):
            keeps = named(prim, *avals, **params)
            if keeps and kept is not None:
                kept(params["name"])
            return keeps

    return jax.checkpoint(fn, policy=policy)


@dataclasses.dataclass(frozen=True)
class Part:
    """Consecutive layers whose parameters are one tree: one layer with its
    own tree (``repeats`` None), or ``repeats`` periods of ``len(kinds)``
    positions, a tree a position, its leaves stacked on a leading axis of
    ``repeats`` rows. A kind is whatever the family's block is static in."""
    kinds: Tuple[Any, ...]
    repeats: Optional[int] = None

    @property
    def n_layers(self) -> int:
        return len(self.kinds) * (1 if self.repeats is None else self.repeats)


def shortest_period(kinds: Sequence, whole: bool = False) -> int:
    """The shortest ``p`` with ``kinds[i] == kinds[i % p]`` throughout
    (``whole``: that also divides the depth); 1 for no layers."""
    n = len(kinds)
    return next((p for p in range(1, n + 1) if not (whole and n % p) and all(
        kinds[i] == kinds[i % p] for i in range(n))), 1)


def periodic(kinds: Sequence, head: int = 0, whole: bool = False
             ) -> Tuple[Part, ...]:
    """``head`` layers of their own, the rest's whole shortest periods as
    one stacked part, what is left (nothing under ``whole``) on its own."""
    kinds = tuple(kinds)
    body = kinds[head:]
    period = shortest_period(body, whole)
    repeats = len(body) // period
    return (tuple(Part((k,)) for k in kinds[:head])
            + ((Part(body[:period], repeats),) if repeats else ())
            + tuple(Part((k,)) for k in body[repeats * period:]))


def runs(kinds: Sequence) -> Tuple[Part, ...]:
    """Each run of like layers as a stacked part of one position."""
    return tuple(Part((kind,), len(list(run)))
                 for kind, run in itertools.groupby(kinds))


def locate(parts: Sequence[Part], layer: int) -> Tuple[int, int, int]:
    """``(part, position in its period, row of its stack)`` of ``layer``."""
    for i, part in enumerate(parts):
        if layer < part.n_layers:
            row, position = divmod(layer, len(part.kinds))
            return i, position, row
        layer -= part.n_layers
    raise IndexError(f"{layer} layers past the last of {len(parts)} parts")


def layer_params(parts: Sequence[Part], trees: Sequence, layer: int):
    """Layer ``layer``'s own leaves. ``trees``: a tree a part, for a
    stacked part the sequence of its positions' trees."""
    i, position, row = locate(parts, layer)
    if parts[i].repeats is None:
        return trees[i]
    return jax.tree.map(lambda a: a[row], trees[i][position])


def walk(x, parts: Sequence[Part], trees: Sequence, each: Callable):
    """``each(kind, the layer's params, x) -> (x, out)`` on every layer,
    first to last: a layer of its own in line, a stacked part under one
    ``lax.scan`` whose body runs a period's positions in order. Returns
    ``(x, the outs stacked a layer)``; None outs come back as None."""
    outs = []
    for part, tree in zip(parts, trees):
        if part.repeats is None:
            x, out = each(part.kinds[0], tree, x)
            outs.append(None if out is None else out[None])
            continue

        def one_period(x, slabs, kinds=part.kinds):
            period_outs = []
            for kind, lp in zip(kinds, slabs):
                x, out = each(kind, lp, x)
                period_outs.append(out)
            return x, (None if out is None else jnp.stack(period_outs))

        x, out = lax.scan(one_period, x, tuple(tree))
        outs.append(None if out is None else out.reshape(
            (part.n_layers,) + out.shape[2:]))
    if not outs or outs[0] is None:
        return x, None
    return x, outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def shift_targets(tokens: jnp.ndarray) -> jnp.ndarray:
    """targets[i] = tokens[i+1], last position padded invalid (-1). Slice
    + ``lax.pad``, NOT ``jnp.concatenate``, on purpose: inside jit on a
    mesh with BOTH a data axis and sp > 1, jaxlib 0.4.36's GSPMD
    partitioner miscompiled a concatenate along the sp-sharded axis into
    an unreduced replica sum (every target id times the data-axis size,
    the pad -1 -> -2: test_sharded_loss's ring configs read ~0.25% off)."""
    return lax.pad(tokens[..., 1:], jnp.asarray(-1, tokens.dtype),
                   [(0, 0, 0)] * (tokens.ndim - 1) + [(0, 1, 0)])


def next_token_loss(x, lm_head, tokens, chunk_size: int, mesh=None):
    """Mean next-token cross-entropy of final-normed hidden states ``x (b,
    s, d)`` through the head (pad tokens < 0 ignored): the fused lm-head +
    CE (the Pallas kernel on the TPU, the chunked scan elsewhere) on all
    ``b * s`` positions, never a ``[b, s, vocab]`` of logits."""
    nll_sum, n_valid = cross_entropy_sums(
        x, lm_head, shift_targets(tokens), chunk_size=chunk_size, mesh=mesh)
    return nll_sum / jnp.maximum(n_valid, 1.0)


def abstract_params(init_params: Callable, cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def param_count(init_params: Callable, cfg) -> int:
    return sum(math.prod(leaf.shape) for leaf
               in jax.tree.leaves(abstract_params(init_params, cfg)))
