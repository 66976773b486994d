"""Flash-checkpoint engine tests on the 8-device CPU mesh.

Covers: memory save/restore, async persist through the saver, commit
protocol, save-on-failure, sharded save + resharded restore (world-resize
analogue: restore into a different mesh layout), deletion strategies.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler, shm_name
from dlrover_tpu.common.constants import NodeEnv


@pytest.fixture
def job_env(tmp_path, monkeypatch):
    job = f"ckpt-test-{int(time.time()*1000) % 100000}"
    monkeypatch.setenv(NodeEnv.JOB_NAME, job)
    monkeypatch.setenv(NodeEnv.NODE_ID, "0")
    monkeypatch.setenv(NodeEnv.PROCESS_ID, "0")
    yield job, str(tmp_path / "ckpt")
    h = SharedMemoryHandler(shm_name(job, 0, 0))
    if h.attach():
        h.close(unlink=True)


def _mesh(shape, names):
    return Mesh(np.array(jax.devices()).reshape(shape), names)


def _make_state(mesh):
    sharding = NamedSharding(mesh, P("dp", None))
    repl = NamedSharding(mesh, P())
    w = jax.device_put(jnp.arange(32.0).reshape(8, 4), sharding)
    b = jax.device_put(jnp.ones(4), repl)
    return {"w": w, "b": b, "step": jnp.array(0)}


def test_memory_save_restore(job_env):
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir)
    blocking = engine.save_to_memory(12, state)
    assert blocking < 5.0
    step, restored = engine.load(target=state)
    assert step == 12
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    assert restored["w"].sharding == state["w"].sharding
    engine.close()


def test_async_staging_save_restore(job_env):
    """Async staging: save returns ~immediately; load joins the stage."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir, async_staging=True)
    engine.save_to_memory(0, state)  # warmup (shm alloc)
    engine.wait_staging()
    blocking = engine.save_to_memory(7, state)
    # reference capture only; the sync stage of this state is ~1s, and
    # a mid-suite scheduler hiccup on a loaded 2-core runner has been
    # seen pushing the snapshot to ~0.052s — bound well above jitter
    # while staying an order of magnitude under the sync path
    assert blocking < 0.15
    step, restored = engine.load(target=state)  # joins the stage
    assert step == 7
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(state["w"])
    )
    # async persist to storage commits too
    engine.save_to_storage(8, state)
    engine.wait_staging()
    assert engine.committed_step() == 8
    engine.close()


def test_async_staging_snapshots_are_immutable(job_env):
    """The snapshot taken at save time is not affected by later updates —
    jax arrays are immutable, so 'later training steps' build new arrays
    and the background stage reads the originals."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir, async_staging=True)
    engine.save_to_memory(0, state)
    engine.wait_staging()
    engine.save_to_memory(1, state)
    # "training" continues: new arrays, old references untouched
    state2 = {k: v + 1 for k, v in state.items()}
    engine.wait_staging()
    step, restored = engine.load(target=state)
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(state["w"])
    )
    del state2
    engine.close()


def test_async_staging_survives_donated_buffers(job_env):
    """The trainer's jitted step donates the state buffers
    (donate_argnums) — which deletes the saved arrays as soon as the next
    step runs. The engine must have finished its device->host snapshot
    before save_to_memory returns, so the checkpoint is unaffected."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    step_fn = jax.jit(
        lambda s: {k: v + 1 for k, v in s.items()}, donate_argnums=(0,)
    )
    engine = CheckpointEngine(ckpt_dir, async_staging=True)
    engine.save_to_memory(0, state)
    engine.wait_staging()
    expect_w = np.asarray(state["w"]).copy()
    engine.save_to_memory(1, state)
    state = step_fn(state)  # donation invalidates the staged arrays
    jax.block_until_ready(state)
    engine.wait_staging()  # must not raise "Array has been deleted"
    step, restored = engine.load(target=state)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), expect_w)
    engine.close()


def test_storage_save_without_agent_persists_via_wait(job_env):
    """Bare run (no agent saver): persist happens on the staging thread;
    wait_staging() is the durability barrier."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir)
    engine.save_to_storage(3, state)
    engine.wait_staging()
    assert engine.committed_step() == 3
    # wipe shm to force storage path
    engine._shm.close(unlink=True)
    engine2 = CheckpointEngine(ckpt_dir)
    step, restored = engine2.load(target=state)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    engine2.close()


def test_async_persist_through_saver(job_env):
    job, ckpt_dir = job_env
    saver = AsyncCheckpointSaver(job_name=job, node_id=0)
    saver.start()
    try:
        mesh = _mesh((8,), ("dp",))
        state = _make_state(mesh)
        engine = CheckpointEngine(ckpt_dir)
        blocking = engine.save_to_storage(7, state)
        assert blocking < 5.0
        deadline = time.time() + 30
        while engine.committed_step() != 7 and time.time() < deadline:
            time.sleep(0.2)
        assert engine.committed_step() == 7
        engine.close()
    finally:
        saver.stop()


def test_close_then_drain_commits_the_last_checkpoint(job_env, monkeypatch):
    """Seen on the v5e at an 8.9 GB state: the trainer is released once
    the saver has copied shm, but fanout + commit run on for tens of
    seconds; a finished job's worker and agent left first and the last
    checkpoint was never committed. ``engine.close()`` now waits for
    the copy and ``saver.drain()`` for the commit."""
    job, ckpt_dir = job_env
    saver = AsyncCheckpointSaver(job_name=job, node_id=0)
    slow_commit = saver.persister._maybe_commit

    def commit_slowly(*args, **kwargs):
        time.sleep(1.0)  # the fanout/commit tail that outlived the job
        return slow_commit(*args, **kwargs)

    monkeypatch.setattr(saver.persister, "_maybe_commit", commit_slowly)
    saver.start()
    try:
        assert saver.drain(timeout=5)  # nothing queued: returns at once
        mesh = _mesh((8,), ("dp",))
        state = _make_state(mesh)
        engine = CheckpointEngine(ckpt_dir)
        engine.save_to_storage(9, state)
        engine.close()  # the worker's last act: waits for the shm copy
        assert engine.committed_step() != 9  # the commit is still running
        assert saver.drain(timeout=30)  # the agent's, before it exits
        assert engine.committed_step() == 9
    finally:
        saver.stop()


def test_save_on_failure_persists_staged_step(job_env):
    """Memory-only save; then the 'node dies' -> saver persists staged shm."""
    job, ckpt_dir = job_env
    saver = AsyncCheckpointSaver(job_name=job, node_id=0)
    saver.start()
    try:
        mesh = _mesh((8,), ("dp",))
        state = _make_state(mesh)
        engine = CheckpointEngine(ckpt_dir)
        engine.save_to_memory(21, state)  # never asked for disk
        engine.wait_staging()  # staged in shm, still not on disk
        assert engine.committed_step() == -1
        ok = saver.save_shm_to_storage(ckpt_dir)  # breakpoint save
        assert ok
        assert engine.committed_step() == 21
        engine.close()
    finally:
        saver.stop()


def test_resharded_restore(job_env):
    """Save under dp=8 sharding, restore into a dp=4,tp=2 target mesh."""
    job, ckpt_dir = job_env
    mesh1 = _mesh((8,), ("dp",))
    state = _make_state(mesh1)
    engine = CheckpointEngine(ckpt_dir)
    engine.save_to_storage(5, state)
    engine.wait_staging()
    engine._shm.close(unlink=True)

    mesh2 = _mesh((4, 2), ("dp", "tp"))
    target = {
        "w": jax.device_put(
            jnp.zeros((8, 4)), NamedSharding(mesh2, P("dp", "tp"))
        ),
        "b": jax.device_put(jnp.zeros(4), NamedSharding(mesh2, P())),
        "step": jnp.array(0),
    }
    engine2 = CheckpointEngine(ckpt_dir)
    step, restored = engine2.load(target=target)
    assert step == 5
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(32.0).reshape(8, 4)
    )
    assert restored["w"].sharding == target["w"].sharding
    engine2.close()


def test_shm_restore_is_shard_wise(job_env):
    """A same-world shm restore never assembles a full host array: every
    leaf is placed by slicing the staged piece for exactly the requested
    index (engine.last_restore_stats pins the fast path)."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir)
    engine.save_to_memory(3, state)
    engine.wait_staging()
    step, restored = engine.load(target=state)
    assert step == 3
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(state["w"])
    )
    stats = engine.last_restore_stats
    assert stats.get("sliced", 0) > 0
    assert stats.get("region_assembled", 0) == 0
    assert stats.get("full_assembled", 0) == 0
    # the restored arrays own their bytes: a later staged save must not
    # mutate them (the CPU backend zero-copy-aliases host buffers, and
    # the pieces are read as views into shm)
    before = np.asarray(restored["w"]).copy()
    state2 = {
        "w": jax.device_put(
            jnp.full((8, 4), 7.0), NamedSharding(mesh, P("dp", None))
        ),
        "b": jax.device_put(jnp.zeros(4), NamedSharding(mesh, P())),
        "step": jnp.array(9),
    }
    engine.save_to_memory(4, state2)
    engine.wait_staging()
    np.testing.assert_array_equal(np.asarray(restored["w"]), before)
    engine.close()


def test_host_scalar_leaf_restore_owns_its_bytes(job_env):
    """A target leaf with no shape/dtype (plain python scalar) takes the
    host-assembly branch — the restored value must be a COPY, not a view
    into shm that the next staged save overwrites."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = {**_make_state(mesh), "epoch": 7}
    engine = CheckpointEngine(ckpt_dir)
    engine.save_to_memory(1, state)
    engine.wait_staging()
    _, restored = engine.load(target={**state, "epoch": 0})
    assert int(np.asarray(restored["epoch"])) == 7
    engine.save_to_memory(2, {**state, "epoch": 99})
    engine.wait_staging()
    assert int(np.asarray(restored["epoch"])) == 7  # not 99
    engine.close()


def test_storage_restore_region_assembles_on_world_change(job_env):
    """A resized-world storage restore whose requested index spans
    multiple old-world shards assembles just that region (never the
    full array)."""
    job, ckpt_dir = job_env
    mesh1 = _mesh((8,), ("dp",))  # w is split into 8 row-shards
    state = _make_state(mesh1)
    engine = CheckpointEngine(ckpt_dir)
    engine.save_to_storage(6, state)
    engine.wait_staging()
    engine._shm.close(unlink=True)

    mesh2 = _mesh((2, 4), ("dp", "tp"))  # 2 row-shards: each spans 4 old
    target = {
        "w": jax.device_put(
            jnp.zeros((8, 4)), NamedSharding(mesh2, P("dp", None))
        ),
        "b": jax.device_put(jnp.zeros(4), NamedSharding(mesh2, P())),
        "step": jnp.array(0),
    }
    engine2 = CheckpointEngine(ckpt_dir)
    step, restored = engine2.load(target=target)
    assert step == 6
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(32.0).reshape(8, 4)
    )
    stats = engine2.last_restore_stats
    assert stats.get("region_assembled", 0) > 0
    assert stats.get("full_assembled", 0) == 0
    engine2.close()


def test_checkpointer_facade_and_deletion(job_env):
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    ckpt = Checkpointer(ckpt_dir)
    for step in [1, 2, 3, 4, 5]:
        ckpt.save(step, state, StorageType.DISK)
    ckpt.wait_staging()
    assert ckpt.committed_step() == 5
    steps = sorted(
        int(d.split("-")[1])
        for d in os.listdir(ckpt_dir)
        if d.startswith("step-")
    )
    assert steps == [3, 4, 5]  # keep-latest-3
    step, _ = ckpt.load(target=state)
    assert step == 5
    ckpt.close()


def test_train_state_checkpoint(job_env):
    """Full flax TrainState over a sharded mesh round-trips."""
    import optax
    from flax.training.train_state import TrainState

    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))
    params = {"w": jax.device_put(jnp.arange(8.0), sharding)}
    state = TrainState.create(
        apply_fn=lambda p, x: x, params=params, tx=optax.adam(1e-3)
    )
    ckpt = Checkpointer(ckpt_dir)
    ckpt.save(9, {"params": state.params, "opt": state.opt_state}, StorageType.DISK)
    restored_step, restored = ckpt.load(
        target={"params": state.params, "opt": state.opt_state}
    )
    assert restored_step == 9
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.arange(8.0)
    )
    ckpt.close()


def test_storage_roundtrip_bfloat16(tmp_path):
    """bf16 leaves must survive disk persist + restore (np.save can't
    round-trip ml_dtypes — the raw-bytes leaf format can)."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    state = {
        "w": jnp.arange(16, dtype=jnp.bfloat16).reshape(4, 4) / 7,
        "b": jnp.ones((3,), jnp.float32),
    }
    eng = CheckpointEngine(str(tmp_path), job_name="bf16rt", node_id=91,
                           process_id=0)
    try:
        eng.save_to_storage(5, state)
        eng.wait_staging()
        # wipe shm so the load exercises the storage path
        eng._shm.close(unlink=True)
        eng2 = CheckpointEngine(str(tmp_path), job_name="bf16rt-other",
                                node_id=92, process_id=0)
        try:
            step, restored = eng2.load()
            assert step == 5
            assert restored["w"].dtype == jnp.bfloat16
            import numpy as np

            np.testing.assert_array_equal(
                np.asarray(restored["w"], dtype=np.float32),
                np.asarray(state["w"], dtype=np.float32),
            )
        finally:
            eng2._shm.close(unlink=True)
            eng2.close()
    finally:
        eng.close()


def test_device_snapshot_is_the_default_stage_mode(job_env):
    """VERDICT r3 #2: the pause is a device-side HBM copy, not the d2h
    transfer — and the snapshot survives a donating step issued
    immediately after save (before the background d2h even starts)."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    step_fn = jax.jit(
        lambda s: {k: v + 1 for k, v in s.items()}, donate_argnums=(0,)
    )
    engine = CheckpointEngine(ckpt_dir)  # async + device snapshot default
    engine.save_to_memory(0, state)
    engine.wait_staging()
    expect_w = np.asarray(state["w"]).copy()
    engine.save_to_memory(1, state)
    assert engine.last_stage_mode == "device_snapshot"
    state = step_fn(state)  # donates the source buffers right away
    jax.block_until_ready(state)
    engine.wait_staging()
    step, restored = engine.load(target=state)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), expect_w)
    engine.close()


def test_device_snapshot_headroom_fallback(job_env, monkeypatch):
    """No HBM room for a second state copy -> degrade to the blocking
    host gather, same correctness."""
    job, ckpt_dir = job_env
    mesh = _mesh((8,), ("dp",))
    state = _make_state(mesh)
    engine = CheckpointEngine(ckpt_dir)
    monkeypatch.setattr(
        CheckpointEngine, "_hbm_headroom", staticmethod(lambda arrays: (8, 8))
    )
    engine.save_to_memory(4, state)
    assert engine.last_stage_mode == "host_gather"
    engine.wait_staging()
    step, restored = engine.load(target=state)
    assert step == 4
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(state["w"])
    )
    engine.close()
