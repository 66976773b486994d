"""Device-mesh construction for elastic TPU training.

The reference (DLRover) never owns a parallelism mesh — it manages
torch.distributed worlds formed by NCCL (SURVEY.md §2.8). TPU-native, the
mesh IS the world: every parallel strategy (dp / fsdp / sp / tp / ep) is an
axis of one `jax.sharding.Mesh`, XLA inserts the collectives, and an elastic
membership change means *re-building the mesh* and resharding state.

Axis convention (outermost → innermost):

    dp    pure data parallelism (gradient psum; rides DCN across slices)
    pp    pipeline parallelism (layer stages; point-to-point ppermute)
    fsdp  data parallelism with parameter/optimizer sharding (ZeRO-3 style)
    ep    expert parallelism for MoE layers (experts split across this axis)
    sp    sequence/context parallelism (ring attention over this axis)
    tp    tensor parallelism (innermost — highest-bandwidth ICI neighbors)

Innermost axes map to physically adjacent TPU cores (JAX device order is
torus-major), so tp/sp collectives ride single-hop ICI while dp gradient
reductions tolerate DCN latency. This mirrors the reference's ASW/PSW
topology sort (`net_topology.py:22-79` there) at mesh-construction time
instead of rendezvous time.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis names, outermost first.
DP = "dp"
PP = "pp"
FSDP = "fsdp"
EP = "ep"
SP = "sp"
TP = "tp"
AXIS_ORDER = (DP, PP, FSDP, EP, SP, TP)

# Axes over which a data batch is split (sharding of the batch dimension).
BATCH_AXES = (DP, FSDP, EP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. ``-1`` for dp means "absorb remaining devices"
    so the same config survives elastic resizes: tp/sp/ep/fsdp are model
    properties, dp is whatever the current world provides."""

    dp: int = -1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.pp * self.fsdp * self.ep * self.sp * self.tp
        if self.dp == -1:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"pp*fsdp*ep*sp*tp={fixed}"
                )
            return dataclasses.replace(self, dp=n_devices // fixed)
        if self.dp * fixed != n_devices:
            raise ValueError(
                f"mesh {self.shape()} wants {self.dp * fixed} devices, "
                f"got {n_devices}"
            )
        return self

    def shape(self) -> dict:
        return {
            DP: self.dp,
            PP: self.pp,
            FSDP: self.fsdp,
            EP: self.ep,
            SP: self.sp,
            TP: self.tp,
        }

    @property
    def data_parallel_size(self) -> int:
        """Number of independent batch shards (for global-batch math)."""
        return self.dp * self.fsdp * self.ep

    @staticmethod
    def auto(
        n_devices: int,
        tp: int = 1,
        sp: int = 1,
        ep: int = 1,
        pp: int = 1,
        prefer_fsdp: bool = True,
    ) -> "MeshConfig":
        """Pick a mesh for ``n_devices``: model axes given, the data axes
        inferred. With ``prefer_fsdp`` the whole data dimension is fsdp
        (ZeRO-style, the usual choice for large models); otherwise pure dp."""
        model = tp * sp * ep * pp
        if n_devices % model:
            raise ValueError(
                f"{n_devices} devices not divisible by tp*sp*ep*pp={model}"
            )
        data = n_devices // model
        if prefer_fsdp:
            return MeshConfig(dp=1, pp=pp, fsdp=data, ep=ep, sp=sp, tp=tp)
        return MeshConfig(dp=data, pp=pp, fsdp=1, ep=ep, sp=sp, tp=tp)


def build_mesh(
    config: MeshConfig,
    devices: Optional[Sequence[jax.Device]] = None,
    n_slices: int = 1,
) -> Mesh:
    """Build the Mesh. Uses `mesh_utils.create_device_mesh` when the whole
    process's device set is used (it knows TPU torus topology); falls back
    to a plain reshape for explicit device subsets.

    ``n_slices > 1`` builds a **multislice** mesh: the outermost slab of
    the ``dp`` axis spans slices, so only pure-data-parallel gradient
    reductions cross DCN while every other collective (fsdp gathers, tp/sp/
    ep) stays on a single slice's ICI — the layout
    ``mesh_utils.create_hybrid_device_mesh`` produces on real multislice
    TPU, reproduced manually for virtual/partial device sets. Devices are
    grouped by their ``slice_index`` attribute when present (real TPU
    multislice), else split into ``n_slices`` equal contiguous chunks
    (CPU dryruns)."""
    if devices is None:
        devices = jax.devices()
    config = config.resolve(len(devices))
    shape = tuple(config.shape()[a] for a in AXIS_ORDER)
    if n_slices > 1:
        return _build_multislice_mesh(config, list(devices), n_slices)
    try:
        from jax.experimental import mesh_utils

        if len(devices) == len(jax.devices()):
            arr = mesh_utils.create_device_mesh(shape, devices=list(devices))
        else:
            arr = np.array(list(devices)).reshape(shape)
    except Exception:
        arr = np.array(list(devices)).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def _build_multislice_mesh(
    config: MeshConfig, devices: list, n_slices: int
) -> Mesh:
    n = len(devices)
    if n % n_slices:
        raise ValueError(f"{n} devices not divisible by {n_slices} slices")
    per_slice = n // n_slices
    # canonical DCN placement (WorldDescriptor.pp_spans_slices): dp
    # spans the slices when it decomposes, else whole pp stages are
    # pinned per slice — activations ride DCN on the stage boundary
    # ppermute while fsdp/ep/sp/tp collectives stay on one slice's ICI
    pp_spans = config.dp % n_slices != 0
    if pp_spans and config.pp % n_slices:
        raise ValueError(
            f"neither dp={config.dp} nor pp={config.pp} is divisible by "
            f"n_slices={n_slices}: dp and pp are the only axes allowed "
            "to span DCN (fsdp/ep/sp/tp collectives must stay on one "
            "slice's ICI)"
        )
    if pp_spans:
        within = config.dp * (config.pp // n_slices) * config.fsdp \
            * config.ep * config.sp * config.tp
    else:
        within = (config.dp // n_slices) * config.pp * config.fsdp \
            * config.ep * config.sp * config.tp
    if within != per_slice:
        raise ValueError(
            f"per-slice mesh ({within}) != devices per slice ({per_slice})"
        )
    # group by hardware slice when the runtime exposes it
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) == n_slices:
        ordered = sorted(
            devices, key=lambda d: (d.slice_index, getattr(d, "id", 0))
        )
    else:
        ordered = list(devices)  # contiguous chunks = virtual slices
    if not pp_spans:
        try:
            from jax.experimental import mesh_utils

            if None not in slice_ids and len(slice_ids) == n_slices:
                ici = (config.dp // n_slices, config.pp, config.fsdp,
                       config.ep, config.sp, config.tp)
                dcn = (n_slices, 1, 1, 1, 1, 1)
                arr = mesh_utils.create_hybrid_device_mesh(
                    ici, dcn, devices=ordered
                )
                return Mesh(arr, AXIS_ORDER)
        except Exception:
            pass
        # manual hybrid layout: slice-major over the outer dp slab, so
        # mesh[d, ...] with d // (dp/n_slices) selecting the slice
        arr = np.array(ordered).reshape(
            (n_slices, config.dp // n_slices, config.pp, config.fsdp,
             config.ep, config.sp, config.tp)
        ).reshape(tuple(config.shape()[a] for a in AXIS_ORDER))
        return Mesh(arr, AXIS_ORDER)
    # pp-spanning layout: slice-major over the stage axis, so stage s
    # lives wholly on slice s // (pp/n_slices) (the stage map) and only
    # the stage-boundary ppermute crosses DCN
    arr = np.array(ordered).reshape(
        (n_slices, config.pp // n_slices, config.dp, config.fsdp,
         config.ep, config.sp, config.tp)
    ).reshape((config.pp, config.dp, config.fsdp, config.ep,
               config.sp, config.tp))
    arr = np.moveaxis(arr, 0, 1)  # -> (dp, pp, fsdp, ep, sp, tp)
    return Mesh(np.ascontiguousarray(arr), AXIS_ORDER)


def mesh_slice_of(mesh: Mesh, n_slices: int, dp_index: int) -> int:
    """Which slice a given dp-axis index lives on (slice-major layout).

    Fails loudly on a topology the layout cannot mean: ``n_slices < 1``
    or a dp axis that doesn't tile into whole slices (callers used to
    get a silent ``// 0`` crash or — worse — a wrong slice id from the
    floored quotient), and a dp index outside the axis."""
    if n_slices < 1:
        raise ValueError(f"n_slices={n_slices} must be >= 1")
    dp = mesh.shape[DP]
    if dp % n_slices:
        raise ValueError(
            f"dp={dp} does not tile into n_slices={n_slices} whole "
            "slices (the slice-major layout requires dp % n_slices == 0)"
        )
    if not 0 <= dp_index < dp:
        raise ValueError(f"dp_index={dp_index} outside dp axis of {dp}")
    per = dp // n_slices
    return dp_index // per


def mesh_slice_of_stage(mesh: Mesh, n_slices: int, pp_index: int) -> int:
    """Which slice a given pp-stage index lives on under the
    pp-spanning slice-major layout (``stage s -> slice s // (pp/n)``,
    the mesh-side face of ``WorldDescriptor.stage_map``)."""
    if n_slices < 1:
        raise ValueError(f"n_slices={n_slices} must be >= 1")
    pp = mesh.shape[PP]
    if pp % n_slices:
        raise ValueError(
            f"pp={pp} does not tile into n_slices={n_slices} whole "
            "slices (the stage-pinned layout requires pp % n_slices == 0)"
        )
    if not 0 <= pp_index < pp:
        raise ValueError(f"pp_index={pp_index} outside pp axis of {pp}")
    return pp_index // (pp // n_slices)


def config_for(world) -> MeshConfig:
    """The :class:`MeshConfig` a
    :class:`~dlrover_tpu.common.world.WorldDescriptor` describes —
    fully resolved (no ``-1`` dp), so resolve/build can't reinterpret
    it. The inverse of ``WorldDescriptor.from_axis_sizes(cfg.shape())``."""
    sizes = world.axis_sizes()
    cfg = MeshConfig(**{a: sizes.get(a, 1) for a in AXIS_ORDER})
    return cfg.resolve(world.world_size)


def mesh_for(world, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the Mesh a WorldDescriptor describes (slice-major when it
    is multislice) and CHECK the result against it — the one
    descriptor→mesh path, shared by the warm-compile speculation
    targets and planner-directed resizes, so a
    candidate world and the mesh built for it can never disagree."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)[: world.world_size]
    if len(devices) < world.world_size:
        raise ValueError(
            f"{world.spec} needs {world.world_size} devices; "
            f"{len(devices)} attached"
        )
    mesh = build_mesh(
        config_for(world), devices=devices, n_slices=world.n_slices
    )
    world.check_mesh(mesh)
    return mesh


def remesh(config: MeshConfig, n_devices: int) -> MeshConfig:
    """Re-fit a mesh config after an elastic membership change.

    Model axes (tp/sp/ep) are preserved — they are baked into checkpoint
    layouts and kernel choices. The data axes absorb the new world size,
    keeping the fsdp:dp preference of the original config. Raises if the
    new world cannot host the model axes at all (caller then falls back to
    a smaller tp/sp — a *resharding* restore, reference-equivalent of
    storage restore on world change, SURVEY.md §7 'hard parts')."""
    model = config.tp * config.sp * config.ep * config.pp
    if n_devices % model:
        raise ValueError(
            f"cannot remesh: {n_devices} devices vs model axes {model}"
        )
    data = n_devices // model
    if config.fsdp > 1 and config.dp > 1:
        # keep fsdp fixed if possible, scale dp
        if data % config.fsdp == 0:
            return dataclasses.replace(
                config, dp=data // config.fsdp
            )
        # else collapse to fsdp-only
        return dataclasses.replace(config, dp=1, fsdp=data)
    if config.fsdp > 1 or (config.dp == 1 and config.fsdp == 1):
        return dataclasses.replace(config, dp=1, fsdp=data)
    return dataclasses.replace(config, dp=data, fsdp=1)


def validate_divisibility(config: MeshConfig, *, n_heads: int,
                          n_kv_heads: int, seq_len: int, vocab: int,
                          n_layers: int = 0) -> None:
    """Fail fast (before tracing) on shape/mesh mismatches."""
    if n_layers and n_layers % max(config.pp, 1):
        raise ValueError(
            f"n_layers={n_layers} not divisible by pp={config.pp}"
        )
    if n_heads % config.tp:
        raise ValueError(f"n_heads={n_heads} not divisible by tp={config.tp}")
    if n_kv_heads % config.tp:
        raise ValueError(
            f"n_kv_heads={n_kv_heads} not divisible by tp={config.tp} "
            "(kv-head replication across tp is not supported)"
        )
    if seq_len % max(config.sp, 1):
        raise ValueError(f"seq_len={seq_len} not divisible by sp={config.sp}")
    if vocab % max(config.tp, 1):
        raise ValueError(f"vocab={vocab} not divisible by tp={config.tp}")
