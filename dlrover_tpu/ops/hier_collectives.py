"""Hierarchical DCN-aware collectives for multislice meshes.

The multislice mesh (``parallel/mesh.py``) guarantees that only the
``dp`` axis spans DCN — and then the gradient reduction runs as ONE
flat collective over the full dp axis, so every hop of the ring treats
the slow inter-slice link like ICI and the DCN cut carries the whole
gradient. FlexLink (arXiv:2510.15882, PAPERS.md) shows hierarchy- and
link-aware collective scheduling recovering double-digit bandwidth on
exactly this topology shape. This module is that strategy, TPU-native:

1. decompose the cross-slice ``dp`` axis into ``(slice, dp_in)`` —
   legal because the multislice layout is **slice-major** over dp
   (``_build_multislice_mesh``: dp index ``d`` lives on slice
   ``d // dp_in``), so reshaping the mesh's dp dimension into
   ``(n_slices, dp_in)`` preserves every device's position;
2. run the gradient reduction as **ICI reduce-scatter within each
   slice** (over ``dp_in``) → **DCN exchange of only the slice-local
   1/dp_in shard** (over ``slice``) → ICI all-gather to rebuild the
   full reduced gradient;
3. composed with zero-1 (``train/zero1.py``): in scatter mode the DCN
   leg is itself a reduce-scatter, so the DCN cut carries only the
   owned moment shard and the trailing all-gather is the existing
   param gather — no extra pass.

Like zero-1's scatter strategy, the engines here run the loss+backward
inside a **full-manual** ``shard_map`` — so they need the factory form
of the loss (``loss_factory(None)`` is the single-device local loss)
and a mesh where every non-dp axis is trivial. The shard_map binds a
*derived* mesh (:func:`hier_mesh`) over the SAME devices in the SAME
flat order, with dp split into the two named axes; base-mesh
``NamedSharding``s on the jit boundary and derived-mesh out_specs
describe identical placements, so GSPMD inserts no resharding between
them (pinned by tests/test_hier_collectives.py on the lowered HLO).

Zero-1 composition needs one local permutation: scattering first over
``dp_in`` then over ``slice`` would leave the dim sharded in
``(dp_in, slice)`` order, while the zero-1 layout (``P(..., "dp")``,
slice-major) is ``(slice, dp_in)``. The engine pre-permutes the
scatter dim — ``(n_slices, dp_in, rest) → (dp_in, n_slices, rest)`` —
so the two chained reduce-scatters land each rank exactly on its
zero-1 shard, bitwise contiguous (tests pin parity vs the flat
``psum_scatter``).

Strategy selection (:func:`mode_for`) is per-mesh, driven by
``TrainConfig.hier_collectives`` / ``overlap_collectives``; the flat
path is the fallback.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from dlrover_tpu.common.log import logger

PyTree = Any

#: derived-mesh axis names the hier engines introduce. "slice" is the
#: DCN axis (outermost), "dp_in" the within-slice ICI remainder of dp.
SLICE_AXIS = "slice"
DP_IN_AXIS = "dp_in"

#: size bound (MiB) of one overlap bucket — each bucket is one fused
#: DCN collective in the exchange half of the pipeline
DEFAULT_BUCKET_MB = 4

__all__ = [
    "SLICE_AXIS",
    "DP_IN_AXIS",
    "DEFAULT_BUCKET_MB",
    "mode_for",
    "hier_mesh",
    "split_spec",
    "hier_value_and_grad",
    "overlap_value_and_grad",
    "hier_param_gather",
]


#: one-time latch for the mixed-mesh silent-fallback warning (the
#: documented mode_for gap): warn the first time a genuinely multislice
#: mixed mesh falls back to flat, naming the flag, then stay quiet
_warned_mixed_flat = False


def mode_for(
    mesh,
    n_slices: int,
    train_config,
    has_factory: bool,
    zero1_mode: str = "off",
) -> str:
    """``"flat"`` | ``"hier"`` | ``"overlap"`` for this build.

    ``hier`` needs: >1 slice; a dp axis that actually decomposes
    (``dp % n_slices == 0`` with a non-trivial within-slice remainder —
    when ``dp_in == 1`` the dp axis IS the DCN axis and there is
    nothing to reduce on ICI first); every non-dp axis trivial and the
    factory form of the loss (the engines go full-manual, same
    constraint as zero-1's scatter strategy); and a zero-1 mode the
    manual engine composes with (``off`` or ``scatter`` — ``gspmd``
    zero-1 only arises on mixed meshes, which already fail the
    trivial-axes test, or without a factory).

    ``overlap`` is ``hier`` plus the latency-hiding bucketed schedule
    (:func:`overlap_value_and_grad`): same eligibility, gated by
    ``TrainConfig.overlap_collectives``. It is a schedule of the SAME
    reduction — every ``mode != "flat"`` check treats the two alike."""
    global _warned_mixed_flat
    if not train_config.hier_collectives or n_slices <= 1:
        return "flat"
    shape = dict(mesh.shape)
    dp = shape.get("dp", 1)
    if dp % n_slices or dp // n_slices <= 1:
        return "flat"
    if not has_factory:
        return "flat"
    if any(s > 1 for a, s in shape.items() if a != "dp"):
        # the body is single-device model code; a non-trivial model
        # axis would need its own manual handling (or a GSPMD-level
        # schedule — docs/design/hier_collectives.md "limits" explains
        # why that stays out on this jax). Loud, once: an operator of
        # a mixed multislice world would otherwise pay full-gradient
        # DCN with no hint why.
        if not _warned_mixed_flat:
            _warned_mixed_flat = True
            nontrivial = {
                a: s for a, s in shape.items() if a != "dp" and s > 1
            }
            logger.warning(
                "hier collectives: multislice mesh has non-trivial "
                "model axes %s — the manual ICI-first engine needs a "
                "pure-dp mesh, running the FLAT dp reduction (full "
                "gradient on the DCN cut). TrainConfig.hier_collectives"
                " cannot force hier here; see docs/design/"
                "hier_collectives.md (limits).", nontrivial,
            )
        return "flat"
    if zero1_mode == "gspmd":
        return "flat"
    if SLICE_AXIS in shape or DP_IN_AXIS in shape:
        logger.warning(
            "hier collectives: mesh already has a %r/%r axis; flat path",
            SLICE_AXIS, DP_IN_AXIS,
        )
        return "flat"
    return "overlap" if train_config.overlap_collectives else "hier"


def hier_mesh(mesh, n_slices: int):
    """The derived mesh: same devices, same flat order, with the dp
    axis split into ``(slice, dp_in)``. Because the multislice layout
    is slice-major over dp, this is a pure C-order reshape — a value
    sharded over ``dp`` on the base mesh is *identically placed* when
    sharded over ``("slice", "dp_in")`` here."""
    from jax.sharding import Mesh

    shape = dict(mesh.shape)
    dp = shape.get("dp", 1)
    if dp % n_slices:
        raise ValueError(
            f"dp={dp} not divisible by n_slices={n_slices}"
        )
    dp_in = dp // n_slices
    names, dims = [], []
    for ax in mesh.axis_names:
        if ax == "dp":
            names += [SLICE_AXIS, DP_IN_AXIS]
            dims += [n_slices, dp_in]
        else:
            names.append(ax)
            dims.append(shape[ax])
    return Mesh(mesh.devices.reshape(tuple(dims)), tuple(names))


def split_spec(spec):
    """Translate a base-mesh PartitionSpec for the derived mesh:
    every ``"dp"`` entry becomes the ``("slice", "dp_in")`` pair in
    place (order preserved inside tuple entries — slice-major, the
    same placement)."""
    from jax.sharding import PartitionSpec as P

    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        new = []
        for a in axes:
            if a == "dp":
                new += [SLICE_AXIS, DP_IN_AXIS]
            else:
                new.append(a)
        out.append(tuple(new) if len(new) > 1 else new[0])
    return P(*out)


def _first_divisible_dim(shape, k: int) -> Optional[int]:
    """Leading dim whose extent divides by ``k`` (for picking the ICI
    reduce-scatter dim of a replicated-output leaf)."""
    for dim, extent in enumerate(shape):
        if extent > 0 and extent % k == 0:
            return dim
    return None


def hier_value_and_grad(
    local_loss, mesh, n_slices: int, p_specs, params,
    zero1_scatter: bool = False,
):
    """The hierarchical grad engine: a full-manual ``shard_map`` over
    :func:`hier_mesh` whose body runs the *local* loss+backward and
    reduces each grad leaf ICI-first. Returns ``fn(params, micro) ->
    (loss, grads)`` with ``loss`` the global-mean scalar.

    ``zero1_scatter=False`` (replicated weight update): each grad leaf
    comes back FULL and replicated over dp — reduce-scatter over
    ``dp_in`` (ICI), psum over ``slice`` (DCN carries the 1/dp_in
    shard), all-gather over ``dp_in`` (ICI). Leaves with no
    dp_in-divisible dim fall back to a flat psum over both axes (DCN
    carries the whole leaf — scalars and tiny odd shapes only).

    ``zero1_scatter=True``: grads land directly in the zero-1 layout
    (``zero1.partition_spec``) — reduce-scatter over ``dp_in`` (ICI)
    then reduce-scatter over ``slice`` (the DCN cut carries only the
    slice-local 1/dp_in shard and emits the owned 1/dp moment shard);
    the trailing all-gather is the step's existing param gather. The
    scatter dim is pre-permuted ``(slice, dp_in) → (dp_in, slice)`` so
    the chained scatters land each rank on its slice-major zero-1
    shard (see module docstring). Non-divisible leaves take the
    replicated hierarchical reduce, exactly like zero-1's flat psum
    fallback.

    ``params`` may be live arrays, tracers or avatars: only ``.shape``
    is read.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from dlrover_tpu.parallel.sharding import batch_spec
    from dlrover_tpu.train import zero1

    hmesh = hier_mesh(mesh, n_slices)
    axis_sizes = dict(mesh.shape)
    dp = axis_sizes["dp"]
    dp_in = dp // n_slices
    inv_dp = 1.0 / dp
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    if zero1_scatter:
        dims = jax.tree.map(
            lambda s, leaf: zero1.scatter_dim(s, leaf.shape, axis_sizes),
            p_specs, params, is_leaf=is_spec,
        )
        out_grad_specs = jax.tree.map(
            lambda s, leaf: split_spec(
                zero1.partition_spec(s, leaf.shape, axis_sizes) or s
            ),
            p_specs, params, is_leaf=is_spec,
        )
    else:
        dims = jax.tree.map(lambda s: None, p_specs, is_leaf=is_spec)
        out_grad_specs = jax.tree.map(split_spec, p_specs, is_leaf=is_spec)

    def reduce_replicated(leaf):
        """full grad, replicated over dp: RS(ici) → psum(dcn) → AG(ici)."""
        d = _first_divisible_dim(leaf.shape, dp_in)
        if d is None:
            # scalars / odd tiny shapes: flat psum (whole leaf on DCN)
            return lax.psum(leaf, (DP_IN_AXIS, SLICE_AXIS)) * inv_dp
        part = lax.psum_scatter(
            leaf, DP_IN_AXIS, scatter_dimension=d, tiled=True
        )
        part = lax.psum(part, SLICE_AXIS)
        return lax.all_gather(
            part, DP_IN_AXIS, axis=d, tiled=True
        ) * inv_dp

    def reduce_scattered(d, leaf):
        """zero-1 shard, slice-major: permute → RS(ici) → RS(dcn)."""
        shp = leaf.shape
        gg = leaf.reshape(
            shp[:d] + (n_slices, dp_in, shp[d] // dp) + shp[d + 1:]
        )
        gg = jnp.swapaxes(gg, d, d + 1).reshape(shp)
        part = lax.psum_scatter(
            gg, DP_IN_AXIS, scatter_dimension=d, tiled=True
        )
        return lax.psum_scatter(
            part, SLICE_AXIS, scatter_dimension=d, tiled=True
        ) * inv_dp

    def body(p, micro):
        loss, g = jax.value_and_grad(local_loss)(p, micro)

        def reduce_leaf(dim, leaf):
            if zero1_scatter and dim is not None:
                return reduce_scattered(dim, leaf)
            return reduce_replicated(leaf)

        g = jax.tree.map(
            reduce_leaf, dims, g,
            is_leaf=lambda x: x is None or isinstance(x, int),
        )
        # global batch mean = mean of equal-sized local means (scalar:
        # the DCN half of this psum moves 4 bytes)
        return lax.psum(loss, (DP_IN_AXIS, SLICE_AXIS)) * inv_dp, g

    split_p_specs = jax.tree.map(split_spec, p_specs, is_leaf=is_spec)

    def fn(p, micro):
        micro_specs = jax.tree.map(
            lambda _: split_spec(batch_spec()), micro
        )
        return shard_map(
            body, mesh=hmesh,
            in_specs=(split_p_specs, micro_specs),
            out_specs=(P(), out_grad_specs),
            check_vma=False,
        )(p, micro)

    return fn


def _partition_buckets(items, sizes, bound: int):
    """Greedy size-bounded partition of ``items`` (kept in order) into
    buckets whose summed ``sizes`` stay under ``bound`` — an oversized
    item gets a bucket of its own. Deterministic in (items, sizes,
    bound): the bucket layout is part of the program identity."""
    buckets, cur, cur_bytes = [], [], 0
    for item, size in zip(items, sizes):
        if cur and cur_bytes + size > bound:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(item)
        cur_bytes += size
    if cur:
        buckets.append(cur)
    return buckets


def overlap_value_and_grad(
    local_loss, mesh, n_slices: int, p_specs, params,
    zero1_scatter: bool = False,
    bucket_bytes: Optional[int] = None,
):
    """The latency-hiding split of :func:`hier_value_and_grad` —
    FlexLink's second half: the same ICI-first hierarchical reduction,
    cut into a ``compute`` half and an ``exchange`` half so the trainer
    can carry the DCN leg of microbatch N through the accumulation scan
    and hide it behind the backward of microbatch N+1.

    Returns ``(compute_fn, exchange_fn)``:

    - ``compute_fn(params, micro) -> (loss, pending)`` runs the local
      loss+backward and ONLY the eager ICI leg per grad leaf
      (reduce-scatter over ``dp_in``; zero-1 leaves pre-permuted
      slice-major first, exactly like the fused engine; non-divisible
      leaves psum over ``dp_in``). ``pending`` is a flat list of
      slice-local partials — every leaf carried with a leading
      ``(slice, dp_in)``-sharded stacking axis, so it crosses the
      shard_map boundary as a global array and rides a ``lax.scan``
      carry untouched.
    - ``exchange_fn(pending) -> grads`` runs the deferred DCN leg —
      partials are grouped into size-bounded buckets
      (``bucket_bytes``, else :data:`DEFAULT_BUCKET_MB`) and each
      bucket is ONE fused DCN collective: a single ``psum`` over
      ``slice`` of the bucket's concatenated partials (replicated
      update + non-divisible leaves),
      or a single ``psum_scatter`` over ``slice`` straight into the
      owned zero-1 shards — then the trailing ICI all-gather per
      replicated leaf. Because the exchange consumes only the CARRIED
      pending (data-independent of the current iteration's backward),
      the scheduler is free to run the DCN transfer under compute; the
      shardcheck overlap dimension proves it from the lowered HLO.

    Addition order per element is identical to the fused engine's —
    compute+exchange back-to-back IS ``hier_value_and_grad`` (the
    bucket concat only batches independent elements through one op) —
    which is what makes the flat↔hier↔overlap parity suite tight.

    ``params`` may be live arrays, tracers or avatars: only ``.shape``
    and ``.dtype`` are read.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from dlrover_tpu.parallel.sharding import batch_spec
    from dlrover_tpu.train import zero1

    hmesh = hier_mesh(mesh, n_slices)
    axis_sizes = dict(mesh.shape)
    dp = axis_sizes["dp"]
    dp_in = dp // n_slices
    inv_dp = 1.0 / dp
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_MB << 20
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    # flatten once; the pending list and every bucket layout follow
    # this leaf order (deterministic: part of the program identity)
    spec_leaves, treedef = jax.tree.flatten(p_specs, is_leaf=is_spec)
    param_leaves = treedef.flatten_up_to(params)

    # per-leaf plan: ("scatter", d) lands in the zero-1 layout via a
    # slice psum_scatter; ("repl", d) rebuilds the full leaf via slice
    # psum + dp_in all-gather; ("residual", None) has no dp_in- (or
    # dp-) divisible dim — eager psum(dp_in), deferred psum(slice)
    plans = []
    for spec, leaf in zip(spec_leaves, param_leaves):
        if zero1_scatter:
            d = zero1.scatter_dim(spec, leaf.shape, axis_sizes)
            plans.append(("scatter", d) if d is not None
                         else ("residual", None))
        else:
            d = _first_divisible_dim(leaf.shape, dp_in)
            plans.append(("repl", d) if d is not None
                         else ("residual", None))

    def _block_shape(kind, d, shape):
        if kind == "residual":
            return tuple(shape)
        return tuple(shape[:d]) + (shape[d] // dp_in,) + tuple(
            shape[d + 1:]
        )

    block_bytes = [
        int(np.prod(_block_shape(k, d, leaf.shape), dtype=np.int64)
            or 1) * np.dtype(leaf.dtype).itemsize
        for (k, d), leaf in zip(plans, param_leaves)
    ]
    # two bucket streams: psum-kind (repl + residual share the fused
    # slice psum; they differ only in ICI post-processing) and
    # scatter-kind (the fused op is a slice psum_scatter)
    psum_idx = [i for i, (k, _) in enumerate(plans) if k != "scatter"]
    scat_idx = [i for i, (k, _) in enumerate(plans) if k == "scatter"]
    psum_buckets = _partition_buckets(
        psum_idx, [block_bytes[i] for i in psum_idx], bucket_bytes
    )
    scat_buckets = _partition_buckets(
        scat_idx, [block_bytes[i] for i in scat_idx], bucket_bytes
    )

    if zero1_scatter:
        out_grad_specs = [
            split_spec(
                zero1.partition_spec(s, leaf.shape, axis_sizes) or s
            )
            for s, leaf in zip(spec_leaves, param_leaves)
        ]
    else:
        out_grad_specs = [split_spec(s) for s in spec_leaves]
    split_p_specs = jax.tree.map(split_spec, p_specs, is_leaf=is_spec)
    # pending leaves stack the per-slice partials on a leading axis
    # sharded over the WHOLE decomposed dp — one block per device, a
    # plain global array between the two shard_maps and in the carry
    pending_spec = P((SLICE_AXIS, DP_IN_AXIS))

    def compute_body(p, micro):
        loss, g = jax.value_and_grad(local_loss)(p, micro)
        g_leaves = treedef.flatten_up_to(g)
        pending = []
        for (kind, d), leaf in zip(plans, g_leaves):
            if kind == "residual":
                part = lax.psum(leaf, DP_IN_AXIS)
            elif kind == "scatter":
                shp = leaf.shape
                gg = leaf.reshape(
                    shp[:d] + (n_slices, dp_in, shp[d] // dp)
                    + shp[d + 1:]
                )
                gg = jnp.swapaxes(gg, d, d + 1).reshape(shp)
                part = lax.psum_scatter(
                    gg, DP_IN_AXIS, scatter_dimension=d, tiled=True
                )
            else:  # repl
                part = lax.psum_scatter(
                    leaf, DP_IN_AXIS, scatter_dimension=d, tiled=True
                )
            pending.append(part[None])  # leading (slice, dp_in) axis
        # global batch mean, reduced eagerly (4 DCN bytes — the grad
        # payload is what the pipeline defers)
        loss = lax.psum(loss, (DP_IN_AXIS, SLICE_AXIS)) * inv_dp
        return loss, pending

    def exchange_body(pending):
        blocks = [x[0] for x in pending]
        out = [None] * len(blocks)
        for bucket in psum_buckets:
            flat = jnp.concatenate(
                [blocks[i].reshape(-1) for i in bucket]
            )
            flat = lax.psum(flat, SLICE_AXIS)  # ONE fused DCN leg
            off = 0
            for i in bucket:
                size = int(np.prod(blocks[i].shape, dtype=np.int64)
                           or 1)
                piece = flat[off:off + size].reshape(blocks[i].shape)
                off += size
                kind, d = plans[i]
                if kind == "repl":
                    piece = lax.all_gather(
                        piece, DP_IN_AXIS, axis=d, tiled=True
                    )
                out[i] = piece * inv_dp
        for bucket in scat_buckets:
            rows = []
            for i in bucket:
                d = plans[i][1]
                b = blocks[i]
                pre, post = b.shape[:d], b.shape[d + 1:]
                shard = b.shape[d] // n_slices
                x = b.reshape(pre + (n_slices, shard) + post)
                x = jnp.moveaxis(x, len(pre), 0)
                rows.append(x.reshape(n_slices, -1))
            cat = jnp.concatenate(rows, axis=1)
            red = lax.psum_scatter(  # ONE fused DCN leg → owned shards
                cat, SLICE_AXIS, scatter_dimension=0, tiled=True
            )
            off = 0
            for i in bucket:
                d = plans[i][1]
                b = blocks[i]
                pre, post = b.shape[:d], b.shape[d + 1:]
                shard = b.shape[d] // n_slices
                size = int(np.prod(
                    pre + (shard,) + post, dtype=np.int64) or 1)
                piece = red[0, off:off + size].reshape(
                    pre + (shard,) + post
                )
                off += size
                out[i] = piece * inv_dp
        return out

    def compute_fn(p, micro):
        micro_specs = jax.tree.map(
            lambda _: split_spec(batch_spec()), micro
        )
        return shard_map(
            compute_body, mesh=hmesh,
            in_specs=(split_p_specs, micro_specs),
            out_specs=(P(), [pending_spec] * len(plans)),
            check_vma=False,
        )(p, micro)

    def exchange_fn(pending):
        leaves = shard_map(
            exchange_body, mesh=hmesh,
            in_specs=([pending_spec] * len(plans),),
            out_specs=out_grad_specs,
            check_vma=False,
        )(pending)
        return jax.tree.unflatten(treedef, leaves)

    return compute_fn, exchange_fn


def hier_param_gather(mesh, n_slices: int, p_specs, params):
    """Hierarchize the zero-1 trailing param all-gather on a multislice
    pure-dp mesh: instead of the flat GSPMD gather over the whole dp
    axis (whose DCN cut carries ``param_bytes × (1 − 1/s)``), gather
    the owned 1/dp shard over ``slice`` FIRST — the DCN leg moves only
    the slice-local ``1/dp_in`` of the params — then over ``dp_in`` on
    ICI, then undo the ``(dp_in, slice)`` block interleave locally (the
    zero-1 layout is slice-major; gathering slice-first brings the
    blocks back dp_in-major). Pure data movement: bitwise identical to
    the flat gather.

    Returns ``fn(params) -> params`` taking leaves in the zero-1 layout
    (``zero1.partition_spec``) and returning them in their base layout;
    leaves the sharding rule left replicated pass through untouched.
    ``params`` may be live arrays, tracers or avatars (only ``.shape``
    is read)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from dlrover_tpu.train import zero1

    hmesh = hier_mesh(mesh, n_slices)
    axis_sizes = dict(mesh.shape)
    dp = axis_sizes["dp"]
    dp_in = dp // n_slices
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    dims = jax.tree.map(
        lambda s, leaf: zero1.scatter_dim(s, leaf.shape, axis_sizes),
        p_specs, params, is_leaf=is_spec,
    )
    in_specs = jax.tree.map(
        lambda s, leaf: split_spec(
            zero1.partition_spec(s, leaf.shape, axis_sizes) or s
        ),
        p_specs, params, is_leaf=is_spec,
    )
    out_specs = jax.tree.map(split_spec, p_specs, is_leaf=is_spec)

    def body(p):
        def gather_leaf(d, leaf):
            if d is None:
                return leaf  # replicated fallback: nothing to gather
            x = lax.all_gather(leaf, SLICE_AXIS, axis=d, tiled=True)
            x = lax.all_gather(x, DP_IN_AXIS, axis=d, tiled=True)
            shp = x.shape
            xx = x.reshape(
                shp[:d] + (dp_in, n_slices, shp[d] // dp) + shp[d + 1:]
            )
            return jnp.swapaxes(xx, d, d + 1).reshape(shp)

        return jax.tree.map(
            gather_leaf, dims, p,
            is_leaf=lambda x: x is None or isinstance(x, int),
        )

    def fn(p):
        return shard_map(
            body, mesh=hmesh,
            in_specs=(in_specs,),
            out_specs=out_specs,
            check_vma=False,
        )(p)

    return fn
