"""Token embedding lookup: a row lookup where the vocabulary is whole on
a device, a one-hot contraction where tp shards it.

**tp > 1** (the table's vocab axis split over tp). A plain
``embed[tokens]`` gather over a sharded vocab axis forces XLA's SPMD
partitioner into "involuntary full rematerialization": it all-gathers
the table, gathers, replicates the result, then re-partitions to the
activation sharding. There the form is ``one_hot(tokens) @ embed``: a
matmul with the vocab axis as the contraction dim partitions like every
other matmul (partial products + psum over tp), and its transpose (the
embedding gradient) is a matmul too. XLA fuses the iota/compare one-hot
generation into the operand read, so the (b, s, vocab) operand never
reaches HBM.

**tp == 1** (every cell of the benchmark, and one chip). The two
vocabulary-wide products are work nobody asked for: 2 x 2 x V x d FLOPs
a token, 17.7 ms of OLMoE's 303 ms step, for rows copied out of a
table. There the forward gathers the token rows (bit-equal to the
product: 1.0 x one row under f32 accumulation) and the backward is a
``custom_vjp`` that adds no rows by scatter, which would want an f32
table of V rows to add them in (`_row_sums`): sort the ids, gather dY
in that order, sum each run of equal ids in f32 by one small product a
block of sorted rows, and write each run's sum once into a table of
zeros. Over a mesh the table's ``dim`` may be split over fsdp:
the table is all-gathered over fsdp and the gradient reduce-scattered
back and all-reduced over the other axes that split the tokens, as
fsdp treats every other weight, stated in a ``shard_map`` and not left
to the partitioner, with the row gather and the row sums local to a
device on its own tokens.

What one-hot gave for free, both forms keep: a token id outside [0, V)
reads a zero row and leaves no gradient.

The form is chosen from the mesh's tp size alone, and said in the
gauges ``embed.gather`` (1 lookup / 0 one-hot) and ``embed.vocab`` when
a step is traced; every operation carries the scope ``embed_lookup``.

Green-field relative to the reference (it owns no model code,
SURVEY.md §2.8).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.observability import trace
from dlrover_tpu.parallel.mesh import BATCH_AXES, DP, EP, FSDP, SP, TP

#: sorted rows a run-sum product covers: its FLOPs grow with the block
#: (2 x block x d a row), its cross-block carries shrink with it
_RUN_BLOCK = 512


def embed_lookup(
    embed: jnp.ndarray,   # (vocab, dim), typically P(TP, FSDP)
    tokens: jnp.ndarray,  # (b, s) int32
    mesh: Optional[Mesh] = None,
    dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Token embedding lookup → (b, s, dim) activations sharded
    P(batch, sp, None). A row lookup unless the mesh shards the
    vocabulary over tp, where it is the one-hot matmul (module
    docstring)."""
    table = embed.astype(dtype)
    vocab = embed.shape[0]
    gathers = mesh is None or mesh.shape[TP] == 1
    trace.gauge("embed.gather", int(gathers))
    trace.gauge("embed.vocab", vocab)
    with trace.scope("embed_lookup"):
        if gathers:
            return _lookup(table, tokens, mesh, vocab)
        one_hot = jax.nn.one_hot(tokens, vocab, dtype=dtype)
        one_hot = lax.with_sharding_constraint(
            one_hot, NamedSharding(mesh, P(BATCH_AXES, SP, TP))
        )
        x = jnp.einsum("bsv,vd->bsd", one_hot, table)
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(BATCH_AXES, SP, None))
        )


def _one_device(mesh: Optional[Mesh]) -> bool:
    return mesh is None or mesh.size == 1


def _rows(table, tokens, mesh):
    """``table`` (vocab, d) at P(None, FSDP), ``tokens`` (b, s) at
    P(batch, sp) → their rows (b, s, d) at P(batch, sp, None)."""
    if _one_device(mesh):
        return _gather_rows(table, tokens)

    def local(table, tokens):
        if mesh.shape[FSDP] > 1:
            table = lax.all_gather(table, FSDP, axis=1, tiled=True)
        return _gather_rows(table, tokens)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(None, FSDP), P(BATCH_AXES, SP)),
        out_specs=P(BATCH_AXES, SP, None),
        check_vma=False,
    )(table, tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, tokens, mesh, vocab):
    return _rows(table, tokens, mesh)


def _lookup_fwd(table, tokens, mesh, vocab):
    return _rows(table, tokens, mesh), tokens


def _lookup_bwd(mesh, vocab, tokens, dy):
    dim = dy.shape[-1]

    def local(tokens, dy):
        return _row_sums(tokens.reshape(-1), dy.reshape(-1, dim), vocab)

    with trace.scope("embed_lookup"):
        if _one_device(mesh):
            return local(tokens, dy), None

        def local_reduced(tokens, dy):
            # each device summed its own tokens' rows: the gradient is
            # their sum over the axes that split the tokens (one
            # all-reduce an axis, as the partitioner reduced the one-hot
            # product's), and a device keeps its slice of the table's dim
            g = local(tokens, dy)
            if mesh.shape[FSDP] > 1:
                g = lax.psum_scatter(g, FSDP, scatter_dimension=1, tiled=True)
            for axis in (DP, EP, SP):
                if mesh.shape[axis] > 1:
                    g = lax.psum(g, axis)
            return g

        return shard_map(
            local_reduced, mesh=mesh,
            in_specs=(P(BATCH_AXES, SP), P(BATCH_AXES, SP, None)),
            out_specs=P(None, FSDP),
            check_vma=False,
        )(tokens, dy), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def _ids(tokens, vocab: int):
    """``tokens`` as int32, every id outside [0, vocab) sent to
    ``vocab``: one past the table, where no row is read or written."""
    inside = (tokens >= 0) & (tokens < vocab)
    return jnp.where(inside, tokens, vocab).astype(jnp.int32)


def _gather_rows(table, tokens):
    """Row ``tokens[...]`` of ``table``; a zero row for an id outside
    [0, vocab), as a one-hot product gives (``table[-1]`` would wrap)."""
    return jnp.take(table, _ids(tokens, table.shape[0]), axis=0,
                    mode="fill", fill_value=0)


def _row_sums(tokens, dy, vocab: int):
    """``one_hot(tokens, vocab)^T @ dy`` without the product and without
    a scatter-add: (vocab, d) in ``dy``'s dtype, row v the f32 sum of
    the rows of ``dy`` (t, d) whose token is v, zero where none is.

    The ids are sorted and ``dy`` gathered in that order, so the rows of
    one id are one run. A block of `_RUN_BLOCK` sorted rows sums its
    runs by a 0/1 product (block x block) @ (block x d) into the run's
    first row of the block, f32 as the one-hot product's accumulator; a
    run that goes on through later blocks (a token repeated thousands of
    times) collects their first rows' sums by a second product over the
    blocks. Each run's sum is then written once into a table of zeros:
    a scatter of at most t rows that adds nothing, so it needs no f32
    table, and nothing of size (vocab, d) but the result exists. (On the
    v5e that write is 0.5-0.9 ms faster than reading the V rows back by
    ``searchsorted`` and a gather, PERF.md section 6, PR 28.)"""
    t, d = dy.shape
    f32 = jnp.float32
    # f32 rows must not be rounded to bf16 by the MXU's default pass
    exact = lax.Precision.HIGHEST if dy.dtype == f32 else None
    block = min(_RUN_BLOCK, t)
    n_blocks = -(-t // block)
    padded = n_blocks * block
    # an id outside the table, and the padding, sort last under an id
    # that is no row of the table
    ids = jnp.pad(_ids(tokens, vocab), (0, padded - t),
                  constant_values=vocab)
    ids, order = lax.sort_key_val(
        ids, jnp.arange(padded, dtype=jnp.int32) % t)
    rows = dy[order].reshape(n_blocks, block, d)

    ids2 = ids.reshape(n_blocks, block)
    new_run = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
    starts = (new_run.reshape(n_blocks, block)
              | (jnp.arange(block) == 0)[None, :])
    member = (ids2[:, :, None] == ids2[:, None, :]) & starts[:, :, None]
    sums = jnp.einsum("nij,njd->nid", member.astype(dy.dtype), rows,
                      preferred_element_type=f32, precision=exact)
    if n_blocks > 1:
        # block m's last run goes on in every later block that starts
        # with its id: their first rows hold what it still lacks
        first, last = ids2[:, 0], ids2[:, -1]
        later = jnp.arange(n_blocks)[None, :] > jnp.arange(n_blocks)[:, None]
        goes_on = later & (first[None, :] == last[:, None])
        carry = jnp.einsum("mn,nd->md", goes_on.astype(f32), sums[:, 0],
                           precision=lax.Precision.HIGHEST)
        last_start = jnp.argmax(ids2 == last[:, None], axis=1)
        takes = jnp.arange(block)[None, :] == last_start[:, None]
        sums = sums + jnp.where(takes[:, :, None], carry[:, None, :], 0.0)
    sums = sums.astype(dy.dtype).reshape(padded, d)
    # a run's first row holds its sum: it alone is written, each row of
    # the table at most once; the rest aim outside and are dropped
    return jnp.zeros((vocab, d), dy.dtype).at[
        jnp.where(new_run, ids, vocab)].set(sums, mode="drop")
