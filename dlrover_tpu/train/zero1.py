"""ZeRO-1: weight-update sharding across the dp axis.

The trainer's optimizer state (adam mu/nu) is born with the *params'*
shardings (``init_state`` eager ``zeros_like``), which is exactly right
under fsdp — and exactly wrong under pure dp or small-fsdp meshes: the
moments replicate across every dp rank, 2x param bytes of HBM per rank
spent holding copies that are never read by anyone else. Xu et al.
(arXiv:2004.13336) showed the weight update can be cross-replica
sharded — reduce-scatter the gradients, update only your shard of the
state, all-gather the updated params — at zero convergence cost.

This module is the sharding brain of that move; the trainer's
``_build_step``/``init_state`` consume it. Two lowering strategies,
chosen per mesh by :func:`mode_for`:

- ``"scatter"`` (pure-dp meshes, loss factory available): the
  per-microbatch loss+grad runs inside a **full-manual** ``shard_map``
  over the mesh — every non-dp axis is trivial, so the body is plain
  single-device model code (``loss_factory(None)``) — and the dp grad
  reduction is an explicit ``lax.psum_scatter`` straight into the
  zero-1 layout. This lowers to a *real* ``reduce-scatter`` op in the
  post-GSPMD HLO on every backend (the shardcheck dp4+zero1 contract
  pins it), replacing the full grad all-reduce.
- ``"gspmd"`` (mixed meshes — fsdp/sp/tp/ep alongside dp): the grads /
  moments / updates carry zero-1 sharding *constraints* and GSPMD
  partitions the update. The moments shard and the param all-gather is
  real on every backend; whether the grad reduction lowers as a true
  reduce-scatter is the backend's allreduce-rewrite pass (XLA:TPU has
  it — Xu et al. *is* that pass; this image's CPU jaxlib lowers it as
  all-reduce + local slice, which the mixed-mesh zero-1 contracts
  record honestly).

The sharding rule (:func:`partition_spec`): partition along each
leaf's leading dim whose per-shard extent divides by dp — appending
``dp`` after any axes already sharding that dim, so an fsdp-sharded
dim becomes the fused ``("fsdp", "dp")`` tiling. Leaves with no
divisible dim **fall back to replicated** (their moments stay exactly
as today); scalars never shard. The rule is deterministic in (spec,
shape, mesh axis sizes) — the trainer re-derives it against any target
mesh, which is what keeps warm-compile AOT signatures, live-reshard
transfer targets and checkpoint restore placements in agreement across
resizes and zero-on/off transitions.

``TrainConfig.zero1`` alone turns it on; a flip at a resize boundary
replaces ``trainer.tc`` before ``remesh()``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from dlrover_tpu.common.log import logger

PyTree = Any

#: the axis the weight update shards over (fsdp already shards state
#: by construction; zero-1 exists for the dp replicas)
ZERO1_AXIS = "dp"

__all__ = [
    "ZERO1_AXIS",
    "mode_for",
    "spec_has_dp",
    "strip_spec",
    "partition_spec",
    "scatter_dim",
    "sharded_value_and_grad",
]


def spec_has_dp(spec) -> bool:
    """Whether any entry of a PartitionSpec names the dp axis — i.e.
    the leaf carries a zero-1 layout."""
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        if ZERO1_AXIS in axes:
            return True
    return False


def mode_for(mesh, train_config, has_factory: bool) -> str:
    """``"off"`` | ``"scatter"`` | ``"gspmd"`` for this build.

    ``scatter`` needs every non-dp axis trivial (the whole mesh goes
    manual, so the body must be single-device model code) and the
    factory form of the loss (``loss_factory(None)`` is the
    constraint-free local loss). pp is excluded entirely: its loss
    already runs its own shard_map schedule and the pipeline grads
    never meet a plain dp psum this rule could rewrite."""
    if not train_config.zero1:
        return "off"
    shape = dict(mesh.shape)
    if shape.get(ZERO1_AXIS, 1) <= 1:
        return "off"
    if shape.get("pp", 1) > 1:
        logger.warning(
            "zero-1 requested but pp>1: weight-update sharding does not "
            "compose with the pipeline schedules yet; running replicated"
        )
        return "off"
    pure_dp = all(
        s <= 1 for a, s in shape.items() if a != ZERO1_AXIS
    )
    if pure_dp and has_factory:
        return "scatter"
    return "gspmd"


def strip_spec(spec) -> Any:
    """Remove ``dp`` from every entry of a PartitionSpec — the inverse
    of :func:`partition_spec`, so a zero-1 spec round-trips back to the
    params' base spec (params themselves never shard over dp; dp only
    ever enters a state spec through this module)."""
    from jax.sharding import PartitionSpec as P

    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a != ZERO1_AXIS)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def partition_spec(
    spec, shape, axis_sizes: Dict[str, int]
) -> Optional[Any]:
    """The zero-1 spec for one state leaf: ``spec`` with ``dp``
    appended to the leading dim whose per-shard extent divides by dp.
    Returns None when no dim qualifies (the replicated fallback) or
    the leaf is a scalar. Idempotent: a spec already carrying dp is
    returned unchanged."""
    from jax.sharding import PartitionSpec as P

    dp = axis_sizes.get(ZERO1_AXIS, 1)
    if dp <= 1 or not shape:
        return None
    if spec_has_dp(spec):
        return spec  # idempotent: already a zero-1 layout
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for dim, entry in enumerate(entries):
        axes = () if entry is None else (
            entry if isinstance(entry, tuple) else (entry,)
        )
        div = 1
        for a in axes:
            div *= axis_sizes.get(a, 1)
        # per-shard extent shape[dim]/div must split dp ways exactly;
        # the >0 guard keeps zero-sized dims out (0 % n == 0)
        if shape[dim] > 0 and shape[dim] % (div * dp) == 0:
            new_axes = axes + (ZERO1_AXIS,)
            entries[dim] = (
                new_axes if len(new_axes) > 1 else new_axes[0]
            )
            # canonical form: no trailing Nones (P(x, None) and P(x)
            # place identically but compare unequal — and these specs
            # feed NamedSharding equality in the AOT signature)
            while entries and entries[-1] is None:
                entries.pop()
            return P(*entries)
    return None


def scatter_dim(spec, shape, axis_sizes: Dict[str, int]) -> Optional[int]:
    """Which dim :func:`partition_spec` would put ``dp`` on — the
    ``psum_scatter`` scatter_dimension for the manual strategy. None
    when the leaf falls back to replicated."""
    z = partition_spec(spec, shape, axis_sizes)
    if z is None:
        return None
    for dim, entry in enumerate(z):
        axes = () if entry is None else (
            entry if isinstance(entry, tuple) else (entry,)
        )
        if ZERO1_AXIS in axes:
            return dim
    return None


def sharded_value_and_grad(local_loss, mesh, p_specs, params):
    """The ``scatter`` strategy's grad engine: a full-manual shard_map
    whose body runs the *local* loss+backward on this rank's batch rows
    and explicitly ``psum_scatter``s each grad leaf into the zero-1
    layout (mean over dp). Returns ``fn(params, micro) -> (loss,
    grads)`` where ``loss`` is the global-mean scalar and ``grads``
    are global arrays sharded per :func:`partition_spec` (replicated
    for non-divisible leaves).

    Only valid on meshes where every non-dp axis is trivial — the body
    is single-device code and the manual axes besides dp are size 1.
    ``params`` may be live arrays, tracers or avatars: only ``.shape``
    is read.
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from dlrover_tpu.parallel.sharding import batch_spec

    axis_sizes = dict(mesh.shape)
    dp = axis_sizes[ZERO1_AXIS]
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    dims = jax.tree.map(
        lambda s, leaf: scatter_dim(s, leaf.shape, axis_sizes),
        p_specs, params, is_leaf=is_spec,
    )
    out_grad_specs = jax.tree.map(
        lambda s, leaf: (
            partition_spec(s, leaf.shape, axis_sizes) or s
        ),
        p_specs, params, is_leaf=is_spec,
    )
    inv_dp = 1.0 / dp

    def body(p, micro):
        loss, g = jax.value_and_grad(local_loss)(p, micro)

        def reduce_leaf(dim, leaf):
            if dim is None:
                # non-divisible fallback: full psum, stays replicated
                return lax.psum(leaf, ZERO1_AXIS) * inv_dp
            return lax.psum_scatter(
                leaf, ZERO1_AXIS, scatter_dimension=dim, tiled=True
            ) * inv_dp

        g = jax.tree.map(
            reduce_leaf, dims, g,
            is_leaf=lambda x: x is None or isinstance(x, int),
        )
        # the global batch mean is the mean of equal-sized local means
        return lax.psum(loss, ZERO1_AXIS) * inv_dp, g

    def fn(p, micro):
        micro_specs = jax.tree.map(lambda _: batch_spec(), micro)
        return shard_map(
            body, mesh=mesh,
            in_specs=(p_specs, micro_specs),
            out_specs=(P(), out_grad_specs),
            check_vma=False,
        )(p, micro)

    return fn
