"""Multislice/DCN awareness: hybrid mesh layout (dp across slices, every
other axis within a slice's ICI) and slice-aware rendezvous rank order
(reference net_topology.py:22-79 sorts DP rings under one access switch;
the TPU analogue keeps rank blocks slice-contiguous so DCN hops only occur
at slice boundaries)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from dlrover_tpu.master.rendezvous.manager import (
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.rendezvous.net_topology import (
    NodeTopologyMeta,
    TpuTopologySorter,
)
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, mesh_slice_of


# -- mesh -------------------------------------------------------------------

def test_multislice_mesh_dp_is_slice_major():
    devices = jax.devices()[:8]
    mc = MeshConfig(dp=4, fsdp=1, ep=1, sp=1, tp=2)
    mesh = build_mesh(mc, devices=devices, n_slices=2)
    grid = mesh.devices  # (dp, pp, fsdp, ep, sp, tp)
    # dp indices 0-1 = slice 0 (device ids 0-3), 2-3 = slice 1 (ids 4-7)
    assert {d.id for d in grid[:2].flat} == {0, 1, 2, 3}
    assert {d.id for d in grid[2:].flat} == {4, 5, 6, 7}
    assert mesh_slice_of(mesh, 2, 0) == 0
    assert mesh_slice_of(mesh, 2, 1) == 0
    assert mesh_slice_of(mesh, 2, 3) == 1
    # tp pairs never straddle a slice
    for d in range(4):
        tp_ids = {dev.id for dev in grid[d].flat}
        assert all(i < 4 for i in tp_ids) or all(i >= 4 for i in tp_ids)


def test_multislice_rejects_non_dp_dcn_axes():
    devices = jax.devices()[:8]
    # fsdp=4 with 2 slices of 4 devices: fsdp would have to straddle DCN
    with pytest.raises(ValueError, match="dp"):
        build_mesh(
            MeshConfig(dp=1, fsdp=4, ep=1, sp=1, tp=2),
            devices=devices, n_slices=2,
        )


def test_multislice_rejects_uneven_devices():
    with pytest.raises(ValueError, match="slices"):
        build_mesh(
            MeshConfig(dp=6, fsdp=1, ep=1, sp=1, tp=1),
            devices=jax.devices()[:6], n_slices=4,
        )


def test_multislice_psum_crosses_dcn_axis():
    """A dp-psum over the 2-slice mesh must produce the global sum — the
    collective path that rides DCN in production."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    mesh = build_mesh(
        MeshConfig(dp=4, fsdp=1, ep=1, sp=1, tp=2),
        devices=jax.devices()[:8], n_slices=2,
    )
    x = jax.device_put(
        np.arange(8, dtype=np.float32).reshape(4, 2),
        NamedSharding(mesh, P("dp", "tp")),
    )
    summed = shard_map(
        lambda v: jax.lax.psum(v, "dp"),
        mesh=mesh, in_specs=P("dp", "tp"), out_specs=P(None, "tp"),
    )(x)
    np.testing.assert_allclose(
        np.asarray(summed)[0], np.arange(8, dtype=np.float32)
        .reshape(4, 2).sum(0)
    )


# -- rendezvous rank order --------------------------------------------------

def _meta(node_id, slice_name, coords=(), rank=-1):
    return NodeTopologyMeta(
        node_id=node_id, node_rank=rank, slice_name=slice_name,
        coords=coords,
    )


def test_sorter_blocks_are_slice_contiguous():
    """Interleaved joins from 3 slices: each slice's hosts must get one
    contiguous rank block, torus-ordered inside it."""
    nodes = {
        0: _meta(0, "slice-1", (1, 0)),
        1: _meta(1, "slice-0", (0, 1)),
        2: _meta(2, "slice-2", (0, 0)),
        3: _meta(3, "slice-0", (0, 0)),
        4: _meta(4, "slice-1", (0, 0)),
        5: _meta(5, "slice-2", (1, 0)),
    }
    ranked = TpuTopologySorter().sort(nodes)
    slices_in_rank_order = [ranked[r].slice_name for r in sorted(ranked)]
    assert slices_in_rank_order == [
        "slice-0", "slice-0", "slice-1", "slice-1", "slice-2", "slice-2",
    ]
    # torus order within the slice block
    assert ranked[0].coords == (0, 0) and ranked[1].coords == (0, 1)


def test_sorter_natural_slice_numbering():
    """'slice-10' must rank after 'slice-2' (lexicographic would not)."""
    nodes = {
        0: _meta(0, "slice-10"),
        1: _meta(1, "slice-2"),
        2: _meta(2, "slice-1"),
    }
    ranked = TpuTopologySorter().sort(nodes)
    assert [ranked[r].slice_name for r in sorted(ranked)] == [
        "slice-1", "slice-2", "slice-10",
    ]


def test_rendezvous_world_is_slice_contiguous():
    """End to end through the rendezvous manager: interleaved joins from
    two slices → the completed world's rank order is slice-blocked."""
    mgr = ElasticTrainingRendezvousManager()
    mgr.update_rdzv_params(4, 4, node_unit=1, waiting_timeout=0.1)
    join_order = [
        (0, "slice-b", (0, 1)),
        (1, "slice-a", (0, 1)),
        (2, "slice-b", (0, 0)),
        (3, "slice-a", (0, 0)),
    ]
    for node_id, slice_name, coords in join_order:
        mgr.join_rendezvous(
            node_id, node_id,
            _meta(node_id, slice_name, coords, rank=node_id),
        )
    _rnd, _grp, world, _coord = mgr.get_comm_world(0)
    assert world, "rendezvous should complete at max_nodes"
    ordered = [world[r] for r in sorted(world)]
    assert [m.slice_name for m in ordered] == [
        "slice-a", "slice-a", "slice-b", "slice-b",
    ]
    assert [m.node_id for m in ordered] == [3, 1, 2, 0]


def test_multislice_train_loss_and_grads_match_single_device():
    """Numerical parity over the hybrid mesh (r3 weak #6: multislice was
    only device-order asserts + dryrun): loss AND grads of the sharded
    model on a 2-slice dp x tp mesh equal the single-device model."""
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import named_shardings

    cfg = llama.LlamaConfig.tiny(n_layers=2, n_heads=4, n_kv_heads=2)
    params = llama.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    ref = float(llama.loss_fn(params, toks, cfg))
    g_ref = jax.grad(lambda p: llama.loss_fn(p, toks, cfg))(params)

    mesh = build_mesh(
        MeshConfig(dp=4, fsdp=1, ep=1, sp=1, tp=2),
        devices=jax.devices()[:8], n_slices=2,
    )
    sharded = jax.device_put(
        params, named_shardings(mesh, llama.param_specs(cfg))
    )
    got = float(jax.jit(
        lambda p, t: llama.loss_fn(p, t, cfg, mesh))(sharded, toks))
    np.testing.assert_allclose(got, ref, rtol=1e-4)
    g = jax.jit(
        jax.grad(lambda p: llama.loss_fn(p, toks, cfg, mesh)))(sharded)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref))
    )
    assert err < 1e-4, err


# -- mesh_slice_of / axis_links edge cases ----------------------------------


def test_mesh_slice_of_rejects_bad_topologies():
    """Non-divisible slice counts and out-of-range indices fail loudly
    (the old floored quotient silently answered a WRONG slice id for
    dp % n_slices != 0, and n_slices > dp crashed with // 0)."""
    mesh = build_mesh(MeshConfig(dp=-1).resolve(8),
                      devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="tile"):
        mesh_slice_of(mesh, 3, 0)  # dp=8 % 3 != 0
    with pytest.raises(ValueError, match=">= 1"):
        mesh_slice_of(mesh, 0, 0)
    with pytest.raises(ValueError, match="outside"):
        mesh_slice_of(mesh, 2, 8)
    with pytest.raises(ValueError, match="outside"):
        mesh_slice_of(mesh, 2, -1)


def test_mesh_slice_of_single_slice_degenerate():
    """n_slices=1: every dp index lives on slice 0 (the degenerate
    mesh every single-slice job runs)."""
    mesh = build_mesh(MeshConfig(dp=-1).resolve(4),
                      devices=jax.devices()[:4])
    assert [mesh_slice_of(mesh, 1, i) for i in range(4)] == [0, 0, 0, 0]


def test_axis_links_classification():
    """axis_links: dp is the ONE dcn axis on a multislice mesh; every
    axis is ici on a single-slice mesh (degenerate case); virtual
    (slice_index-less) slices classify the same as real ones — the
    layout, not the device attribute, decides."""
    from dlrover_tpu.profiler.comm import axis_links

    # CPU devices carry no slice_index: build_mesh falls back to
    # contiguous virtual slices, and axis_links still classifies
    mesh = build_mesh(
        MeshConfig(dp=4, fsdp=1, ep=1, sp=1, tp=2),
        devices=jax.devices()[:8], n_slices=2,
    )
    assert all(getattr(d, "slice_index", None) is None
               for d in mesh.devices.flat)
    links = axis_links(mesh, 2)
    assert links["dp"] == "dcn"
    assert links["tp"] == "ici" and links["fsdp"] == "ici"
    # single-slice degenerate: everything ici, dp included
    assert set(axis_links(mesh, 1).values()) == {"ici"}


def test_axis_links_track_resize_across_slice_counts():
    """A resize that collapses 2 slices to 1 (slice loss) must re-
    classify dp as ici — the trainer refreshes the ledger's link map
    from the post-resize slice count at remesh()."""
    import numpy as np

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel import named_shardings
    from dlrover_tpu.profiler.comm import comm_ledger
    from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

    cfg = llama.LlamaConfig.tiny()
    mc = MeshConfig(dp=-1).resolve(8)
    mesh = build_mesh(mc, devices=jax.devices()[:8], n_slices=2)
    tc = TrainConfig(global_batch_size=16, micro_batch_size=2,
                     warmup_steps=0, total_steps=100)
    tr = ElasticTrainer(
        None, llama.param_specs(cfg), mesh, mc, tc,
        loss_factory=lambda m: (lambda p, t: llama.loss_fn(p, t, cfg, m)),
        n_slices=2,
    )
    params = jax.device_put(
        llama.init_params(cfg, jax.random.key(0)),
        named_shardings(mesh, llama.param_specs(cfg)),
    )
    state = tr.init_state(params)
    rows = "\n".join(comm_ledger.prometheus_lines())
    assert 'link="dcn"' in rows  # the multislice inventory
    # lose a slice: 8 devices / 2 slices -> 4 devices / 1 slice
    mc4 = MeshConfig(dp=-1).resolve(4)
    mesh4 = build_mesh(mc4, devices=jax.devices()[:4])
    tr.remesh(mesh4, mc4, state=None)
    assert tr.n_slices == 1
    rows = "\n".join(comm_ledger.prometheus_lines())
    assert 'link="dcn"' not in rows  # dp back on ICI
    assert 'link="ici"' in rows
