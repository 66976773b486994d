"""ZeRO-1 weight-update sharding (train/zero1.py + the trainer wiring).

The contract under test, end to end: with zero-1 on, the adam moments
are born dp-sharded, the step's grad reduction lowers as the
reduce-scatter + all-gather rewrite (REAL ops in the dp4 HLO — the
checked-in ``dp4+zero1`` contract pins them), training is numerically
equivalent to the replicated baseline, the sharded moments survive
resizes (live reshard AND checkpoint restore, including zero-on↔off
transitions, flipped through ``trainer.tc``). Plus the
comm-ledger↔IR-census agreement the analytic inventory claims.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler, shm_name
from dlrover_tpu.common import flags
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.lint import shardcheck
from dlrover_tpu.lint.__main__ import main as lint_main
from dlrover_tpu.models import llama
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train import live_reshard as lr
from dlrover_tpu.train import warm_compile as wc
from dlrover_tpu.train import zero1
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

CFG = llama.LlamaConfig.tiny()
SEQ = 16
GB = 16  # micro=2 → accum 2 on dp4 (the grad-accum scan is exercised)


def _drain_speculation():
    """Join in-flight speculative compile threads (armed whenever a
    CheckpointEngine configured a persistent cache dir): a background
    neighbor-world compile would steal CPU from — and write ledgers
    under — the next test."""
    for c in list(wc._live_compilers):
        c._stop.set()
        c.wait_idle(timeout=120)


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """No reshard kill-switches leaking in from the outer
    environment; fresh ledgers; isolated shm name space."""
    job = f"zero1-{int(time.time() * 1000) % 100000}"
    monkeypatch.setenv(NodeEnv.JOB_NAME, job)
    monkeypatch.setenv(NodeEnv.NODE_ID, "0")
    monkeypatch.setenv(NodeEnv.PROCESS_ID, "0")
    monkeypatch.delenv(flags.LIVE_RESHARD.name, raising=False)
    monkeypatch.delenv(wc.ENV_KILL_SWITCH, raising=False)
    monkeypatch.delenv(wc.ENV_CACHE_DIR, raising=False)
    _drain_speculation()
    lr.resize_ledger.clear()
    yield job
    _drain_speculation()
    lr.resize_ledger.clear()
    h = SharedMemoryHandler(shm_name(job, 0, 0))
    if h.attach():
        h.close(unlink=True)


def _factory(mesh):
    return lambda p, t: llama.loss_fn(p, t, CFG, mesh)


def _mk(world, **axes):
    mc = MeshConfig(**axes).resolve(world) if axes else \
        MeshConfig(dp=-1).resolve(world)
    mesh = build_mesh(mc, devices=jax.devices()[:world])
    return mesh, mc


def _make_trainer(mesh, mc, zero1_on):
    specs = llama.param_specs(CFG)
    tc = TrainConfig(global_batch_size=GB, micro_batch_size=2,
                     warmup_steps=0, total_steps=100, zero1=zero1_on)
    tr = ElasticTrainer(None, specs, mesh, mc, tc, loss_factory=_factory)
    params = jax.device_put(
        llama.init_params(CFG, jax.random.key(0)),
        named_shardings(mesh, specs),
    )
    state = tr.init_state(params)
    return tr, state


def _batch(tr, key):
    a, b = tr.step_batch_shape
    return jax.random.randint(jax.random.key(key), (a, b, SEQ), 0,
                              CFG.vocab_size)


def _moment_specs(state):
    return {
        str(l.sharding.spec)
        for l in jax.tree.leaves(state["opt"])
        if getattr(l, "ndim", 0) > 0
    }


def _run(axes, zero1_on, steps):
    world = 1
    for v in axes.values():
        world *= v
    mesh, mc = _mk(world, **axes)
    tr, state = _make_trainer(mesh, mc, zero1_on)
    losses = []
    for i in range(steps):
        state, loss = tr.step(state, _batch(tr, 100 + i))
        losses.append(float(loss))
    return tr, state, losses


# ---------------------------------------------------------------------------
# the sharding rule (pure units)
# ---------------------------------------------------------------------------


def test_partition_spec_rule():
    sizes = {"dp": 4, "fsdp": 2, "tp": 1}
    # plain replicated 1-d leaf: dp lands on dim 0
    assert zero1.partition_spec(P(), (64,), sizes) == P("dp")
    # fsdp already shards dim 0: dp FUSES after it (64/2=32, 32%4==0)
    assert zero1.partition_spec(P("fsdp"), (64, 16), sizes) == \
        P(("fsdp", "dp"))
    # dim 0 not divisible → dp moves to the first dim that is
    assert zero1.partition_spec(P(), (3, 8), sizes) == P(None, "dp")
    # nothing divisible → replicated fallback
    assert zero1.partition_spec(P(), (3, 5), sizes) is None
    # scalars never shard
    assert zero1.partition_spec(P(), (), sizes) is None
    # idempotent: a spec already carrying dp is returned unchanged
    assert zero1.partition_spec(P(("fsdp", "dp")), (64, 16), sizes) == \
        P(("fsdp", "dp"))
    # dp=1 mesh: no-op
    assert zero1.partition_spec(P(), (64,), {"dp": 1}) is None


def test_strip_spec_roundtrip_and_has_dp():
    sizes = {"dp": 4}
    spec = zero1.partition_spec(P("fsdp"), (64, 16), {"dp": 4, "fsdp": 2})
    assert zero1.spec_has_dp(spec)
    assert zero1.strip_spec(spec) == P("fsdp")
    assert not zero1.spec_has_dp(P("fsdp"))
    assert zero1.strip_spec(P("dp")) == P()
    assert zero1.scatter_dim(P(), (64,), sizes) == 0
    assert zero1.scatter_dim(P(), (3, 8), sizes) == 1
    assert zero1.scatter_dim(P(), (3, 5), sizes) is None


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


def test_mode_for():
    tc = TrainConfig(zero1=True)
    off = TrainConfig(zero1=False)
    # pure dp + factory → explicit-scatter strategy
    assert zero1.mode_for(_FakeMesh(dp=4, fsdp=1), tc, True) == "scatter"
    # pure dp without the factory form → gspmd constraints
    assert zero1.mode_for(_FakeMesh(dp=4), tc, False) == "gspmd"
    # mixed mesh → gspmd
    assert zero1.mode_for(_FakeMesh(dp=2, fsdp=2), tc, True) == "gspmd"
    # no dp axis / knob off / pp → off
    assert zero1.mode_for(_FakeMesh(dp=1, fsdp=4), tc, True) == "off"
    assert zero1.mode_for(_FakeMesh(dp=4), off, True) == "off"
    assert zero1.mode_for(_FakeMesh(dp=2, pp=2), tc, True) == "off"


def test_contract_spec_roundtrip():
    assert shardcheck.contract_spec_of({"dp": 4}, True) == "dp4+zero1"
    assert shardcheck.contract_spec_of({"dp": 4}, False) == "dp4"
    assert shardcheck.parse_contract_spec("dp4+zero1") == (
        {"dp": 4}, True, 1
    )
    assert shardcheck.parse_contract_spec("sp2xdp2") == (
        {"sp": 2, "dp": 2}, False, 1
    )
    # the multislice hierarchical variants (ops/hier_collectives.py):
    # canonical suffix order mesh + Nslice + zero1
    assert shardcheck.contract_spec_of({"dp": 4}, False, 2) == \
        "dp4+2slice"
    assert shardcheck.contract_spec_of({"dp": 4}, True, 2) == \
        "dp4+2slice+zero1"
    assert shardcheck.parse_contract_spec("dp4+2slice+zero1") == (
        {"dp": 4}, True, 2
    )
    assert shardcheck.parse_contract_spec("dp8+4slice") == (
        {"dp": 8}, False, 4
    )
    with pytest.raises(ValueError):
        shardcheck.parse_contract_spec("zz4+zero1")
    with pytest.raises(ValueError):
        shardcheck.parse_contract_spec("+2slice")


# ---------------------------------------------------------------------------
# parity matrix: zero-1 vs the replicated baseline (≥8 steps)
# ---------------------------------------------------------------------------


def _assert_parity(l_off, l_on, s_off, s_on):
    np.testing.assert_allclose(l_off, l_on, rtol=0, atol=2e-5)
    for a, b in zip(jax.tree.leaves(s_off["params"]),
                    jax.tree.leaves(s_on["params"])):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-6
        )


def test_parity_dp4_scatter():
    """Pure dp4 (the scatter strategy): 8 steps match the replicated
    baseline, moments live dp-sharded, and the live program's census
    shows the allreduce→reduce-scatter+all-gather rewrite with LESS
    dp traffic than a grad all-reduce."""
    tr_off, s_off, l_off = _run({"dp": 4}, False, steps=8)
    tr_on, s_on, l_on = _run({"dp": 4}, True, steps=8)
    assert tr_on._zero1_mode(tr_on.mesh) == "scatter"
    _assert_parity(l_off, l_on, s_off, s_on)
    specs_on = _moment_specs(s_on)
    assert any("'dp'" in s for s in specs_on), specs_on
    assert not any("'dp'" in s for s in _moment_specs(s_off))

    # the rewrite, in the compiled program the trainer actually runs
    # (lower_step is a warm cache hit for a stepped trainer)
    compiled, info = tr_on.lower_step(tr_on.mesh, tr_on.mesh_config)
    census = shardcheck.collective_census(
        compiled.as_text(), shardcheck.MeshCoords(dict(tr_on.mesh.shape))
    )
    assert census.get("reduce-scatter|dp", {}).get("count", 0) >= 1
    assert census.get("all-gather|dp", {}).get("count", 0) >= 1
    # psum-class dp traffic is scalars only (loss + clip norm)
    assert census.get("all-reduce|dp", {}).get("bytes", 0) < 1024


def test_parity_dp2xfsdp2_gspmd():
    """Mixed dp×fsdp (the gspmd strategy): parity holds and the
    moments shard over the FUSED (fsdp, dp) tiling where both axes
    divide."""
    tr_off, s_off, l_off = _run({"dp": 2, "fsdp": 2}, False, steps=8)
    tr_on, s_on, l_on = _run({"dp": 2, "fsdp": 2}, True, steps=8)
    assert tr_on._zero1_mode(tr_on.mesh) == "gspmd"
    _assert_parity(l_off, l_on, s_off, s_on)
    specs_on = _moment_specs(s_on)
    assert any("'fsdp', 'dp'" in s for s in specs_on), specs_on


def test_grad_accumulator_is_sharding_pinned():
    """The f32 grad-accum buffer carries an explicit sharding
    constraint (satellite: it used to materialize with none — fully
    replicated under dp). Under zero-1 the pinned layout is the dp
    shard itself: the accumulator tree costs 1/dp per device."""
    mesh, mc = _mk(4)  # dp4, accum = 16/(2*4) = 2 → the scan path
    tr, state = _make_trainer(mesh, mc, True)
    tr.record_avatars(state, np.zeros(
        (tr.accum_steps, tr.step_batch_shape[1], SEQ), np.int32))
    assert tr.accum_steps > 1, "this test needs the accumulator scan"
    program = tr.step_ir()
    # param-shaped f32 @Sharding sites = the pinned accumulator leaves
    # (model activations in this program are rank-3 batch tensors)
    sites = [
        (m.group(1), m.group(2))
        for m in shardcheck._SHARDING_CONSTRAINT_RE.finditer(
            program.stablehlo)
    ]
    embed_sites = [sh for sh, t in sites if t == "256x64xf32"]
    assert embed_sites, f"no accumulator constraint site in {sites}"
    # the embed param has TWO sites in this program: the f32
    # accumulator (dp-tiled — the satellite under test) and the
    # post-update param gather pin (replicated on pure dp)
    assert any(
        shardcheck.parse_sharding(sh).kind == "tiled" for sh in embed_sites
    ), embed_sites
    # and the live zero-1 program is clean under the full SC rule set
    # (incl. the SC002 moment arm: every divisible moment sharded)
    assert shardcheck.check_program(program) == []
    assert program.zero1 and program.label.endswith("+zero1")


# ---------------------------------------------------------------------------
# resizes: live reshard and checkpoint restore, zero-on↔off transitions
# ---------------------------------------------------------------------------


def _ckpt_reference(tr, state, mesh_b, ckpt_dir):
    """What the checkpoint round-trip restores for mesh_b, placed by
    the trainer's zero-1-aware targets."""
    target = tr.state_targets(mesh_b)
    eng = CheckpointEngine(ckpt_dir)
    try:
        eng.save_to_memory(1, state)
        eng.wait_staging()
        restored = eng.load(target=target)
        assert restored is not None
        return restored[1]
    finally:
        eng.close()


def _assert_states_equal(got, ref):
    got_flat, got_def = jax.tree_util.tree_flatten(got)
    ref_flat, ref_def = jax.tree_util.tree_flatten(ref)
    assert got_def == ref_def
    for g, r in zip(got_flat, ref_flat):
        assert g.sharding == r.sharding
        gb = np.ascontiguousarray(np.asarray(g)).reshape(-1)
        rb = np.ascontiguousarray(np.asarray(r)).reshape(-1)
        np.testing.assert_array_equal(gb.view(np.uint8), rb.view(np.uint8))


def test_resize_parity_live_vs_checkpoint(tmp_path):
    """Shrink dp4→dp2 with zero-1 on: the live-resharded state is
    BITWISE what the checkpoint round-trip restores (both placed by
    ``state_targets``, whose moment specs re-derive for dp2), and the
    post-resize step accepts it."""
    mesh_a, mc_a = _mk(4)
    tr, state = _make_trainer(mesh_a, mc_a, True)
    state, _ = tr.step(state, _batch(tr, 1))
    jax.block_until_ready(state)

    mesh_b, mc_b = _mk(2)
    ref = _ckpt_reference(tr, state, mesh_b, str(tmp_path / "ckpt"))
    # the reference carries dp2-derived moment shardings
    assert any(
        "'dp'" in str(l.sharding.spec)
        for l in jax.tree.leaves(ref["opt"])
        if getattr(l, "ndim", 0) > 0
    )
    new_state = tr.remesh(mesh_b, mc_b, state=state)
    assert new_state is not None
    _assert_states_equal(new_state, ref)
    next_state, loss = tr.step(new_state, _batch(tr, 2))
    assert np.isfinite(float(loss))

    # a trainer whose state came from checkpoint restore (no
    # init_state, no step: avatars unseeded) must seed BOTH avatars at
    # remesh — with _params_avatar left None the next _build_step
    # silently downgrades to the replicated path while the
    # signature/ledger/contract label still say zero-1
    tc2 = TrainConfig(global_batch_size=GB, micro_batch_size=2,
                      warmup_steps=0, total_steps=100, zero1=True)
    tr2 = ElasticTrainer(None, llama.param_specs(CFG), mesh_a, mc_a,
                         tc2, loss_factory=_factory)
    assert tr2._params_avatar is None
    moved = tr2.remesh(mesh_b, mc_b, state=state)
    assert moved is not None
    assert tr2._params_avatar is not None
    assert tr2._zero1_mode(mesh_b) != "off"


def test_resize_grow_and_zero_transitions(tmp_path):
    """One elastic journey: dp2(on) → grow dp4 while flipping zero-1
    OFF (moments gather back to replicated) → flip ON again and shrink
    to dp2 (moments re-shard). Each hop is checked against the
    checkpoint-restore placement; every world steps to a finite
    loss."""
    mesh_a, mc_a = _mk(2)
    tr, state = _make_trainer(mesh_a, mc_a, True)
    state, _ = tr.step(state, _batch(tr, 1))
    jax.block_until_ready(state)
    assert any("'dp'" in s for s in _moment_specs(state))

    # grow dp2→dp4 with zero-1 turned OFF: the off-transition
    tr.tc = dataclasses.replace(tr.tc, zero1=False)
    mesh_b, mc_b = _mk(4)
    ref = _ckpt_reference(tr, state, mesh_b, str(tmp_path / "c1"))
    off_state = tr.remesh(mesh_b, mc_b, state=state)
    assert off_state is not None
    _assert_states_equal(off_state, ref)
    assert not any("'dp'" in s for s in _moment_specs(off_state))
    off_state, loss = tr.step(off_state, _batch(tr, 2))
    assert np.isfinite(float(loss))
    jax.block_until_ready(off_state)

    # back ON and shrink dp4→dp2: the on-transition re-shards
    tr.tc = dataclasses.replace(tr.tc, zero1=True)
    mesh_c, mc_c = _mk(2)
    ref2 = _ckpt_reference(tr, off_state, mesh_c, str(tmp_path / "c2"))
    on_state = tr.remesh(mesh_c, mc_c, state=off_state)
    assert on_state is not None
    _assert_states_equal(on_state, ref2)
    assert any("'dp'" in s for s in _moment_specs(on_state))
    on_state, loss = tr.step(on_state, _batch(tr, 3))
    assert np.isfinite(float(loss))


def test_resize_with_live_reshard_off_takes_checkpoint_path(
    monkeypatch, tmp_path
):
    """DLROVER_TPU_LIVE_RESHARD=0 with zero-1 on: remesh returns None
    (today's behavior) and the checkpoint restore — placed by
    ``state_targets`` — produces a state the new world steps from."""
    monkeypatch.setenv(flags.LIVE_RESHARD.name, "0")
    mesh_a, mc_a = _mk(4)
    tr, state = _make_trainer(mesh_a, mc_a, True)
    state, _ = tr.step(state, _batch(tr, 1))
    jax.block_until_ready(state)
    mesh_b, mc_b = _mk(2)
    # snapshot BEFORE remesh (the restart path stages pre-resize)
    eng = CheckpointEngine(str(tmp_path / "ckpt"))
    try:
        eng.save_to_memory(1, state)
        eng.wait_staging()
        assert tr.remesh(mesh_b, mc_b, state=state) is None
        restored = eng.load(target=tr.state_targets(mesh_b))
        assert restored is not None
    finally:
        eng.close()
    new_state = restored[1]
    assert any("'dp'" in s for s in _moment_specs(new_state))
    new_state, loss = tr.step(new_state, _batch(tr, 2))
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# comm-ledger ↔ IR-census agreement (the analytic inventory, verified)
# ---------------------------------------------------------------------------


def _ledger_dp_events(axis_sizes, zero1_on):
    """The analytic inventory for the contract model on this mesh,
    computed without lowering anything."""
    from dlrover_tpu.lint import contract_model
    from dlrover_tpu.profiler.comm import comm_ledger

    trainer, _, _ = contract_model.build_contract_trainer(
        axis_sizes, zero1=zero1_on
    )
    return {
        (e.kind, e.axis): e for e in comm_ledger.events()
    }, trainer.accum_steps


CONTRACT_MESHES = [
    ("dp4", {"dp": 4}, False),
    ("dp2xfsdp2", {"dp": 2, "fsdp": 2}, False),
    ("dp2xsp2", {"dp": 2, "sp": 2}, False),
]


@pytest.mark.parametrize("spec,axes,z1", CONTRACT_MESHES,
                         ids=[m[0] for m in CONTRACT_MESHES])
def test_ledger_agrees_with_census_replicated(spec, axes, z1):
    """The analytic dp inventory vs the checked-in SC001 census, for
    all three contract meshes. Units differ by construction — the
    census counts each op once per PROGRAM (a scan body counts once:
    the llama layer scan and the chunked-CE vocab scan both compress
    it), the ledger counts per ISSUE — so the census's dp grad bytes
    must be bounded by the ledger's per-issue payload from above, and
    below by the known scan-compression factor (layers scanned twice,
    CE chunks four times: measured ~0.54 on the pinned model)."""
    contract = shardcheck.load_contract(
        shardcheck.DEFAULT_CONTRACTS_DIR, spec
    )
    assert contract is not None
    events, accum = _ledger_dp_events(axes, z1)
    grad = events[("psum", "dp")]
    # the fix under test: the dp grad reduction happens once per LOSS
    # CALL (inside the grad-accum scan body), not once per step
    assert grad.per == "loss_call" and grad.count == 1
    assert ("reduce_scatter", "dp") not in events
    assert ("all_gather", "dp") not in events
    census_dp = {
        k.split("|")[0]: c for k, c in contract["census"].items()
        if k.split("|")[1] == "dp"
    }
    assert set(census_dp) == {"all-reduce"}, census_dp
    ratio = census_dp["all-reduce"]["bytes"] / grad.nbytes
    assert 0.35 <= ratio <= 1.02, (ratio, grad.nbytes, census_dp)


def test_ledger_agrees_with_census_zero1_dp4_exactly():
    """On dp4+zero1 (scatter mode, accum=1 in the contract model) the
    reduce-scatter and all-gather sit OUTSIDE every scan — one op per
    divisible leaf — so the static census and the analytic ledger
    agree EXACTLY on bytes: param_bytes/dp for each half."""
    contract = shardcheck.load_contract(
        shardcheck.DEFAULT_CONTRACTS_DIR, "dp4+zero1"
    )
    assert contract is not None and contract.get("zero1") is True
    events, accum = _ledger_dp_events({"dp": 4}, True)
    rs = events[("reduce_scatter", "dp")]
    ag = events[("all_gather", "dp")]
    assert ("psum", "dp") not in events
    census = contract["census"]
    assert census["reduce-scatter|dp"]["bytes"] == rs.nbytes
    assert census["all-gather|dp"]["bytes"] == ag.nbytes
    # psum-class dp traffic is scalars only
    assert census.get("all-reduce|dp", {}).get("bytes", 0) < 1024


def test_zero1_contract_beats_replicated_dp_bytes():
    """The acceptance bar, pinned on the checked-in artifacts: the
    dp4+zero1 contract shows ≥1 dp reduce-scatter, ≥1 dp all-gather,
    no param-scale dp psum, and LOWER total dp-axis bytes than the
    replicated dp4 contract."""
    repl = shardcheck.load_contract(shardcheck.DEFAULT_CONTRACTS_DIR, "dp4")
    z1 = shardcheck.load_contract(
        shardcheck.DEFAULT_CONTRACTS_DIR, "dp4+zero1"
    )
    assert repl is not None and z1 is not None

    def dp_bytes(contract):
        return sum(
            c["bytes"] for k, c in contract["census"].items()
            if k.split("|")[1] == "dp"
        )

    assert z1["census"]["reduce-scatter|dp"]["count"] >= 1
    assert z1["census"]["all-gather|dp"]["count"] >= 1
    assert z1["census"].get("all-reduce|dp", {}).get("bytes", 0) < 1024
    assert dp_bytes(z1) < dp_bytes(repl), (dp_bytes(z1), dp_bytes(repl))
    # the two programs are distinct contract keys with distinct hashes
    assert z1["config_hash"] != repl["config_hash"]


# ---------------------------------------------------------------------------
# shardcheck integration: SC002 moment arm + the CLI gate
# ---------------------------------------------------------------------------


def _state_program(moments_sharded):
    """A minimal donated-state step (a [0]['opt']… result with pinned
    shardings) — the entry-signature shape SC002's zero-1 arm reads,
    without a full contract-model lowering."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sh_p = NamedSharding(mesh, P())
    sh_m = NamedSharding(mesh, P("dp")) if moments_sharded else sh_p

    def step(state):
        out = {
            "params": state["params"] * 2.0,
            "opt": {"mu": state["opt"]["mu"] + 1.0},
        }
        return out, state["params"].sum()

    av = {
        "params": jax.ShapeDtypeStruct((64, 64), np.float32,
                                       sharding=sh_p),
        "opt": {"mu": jax.ShapeDtypeStruct((64, 64), np.float32,
                                           sharding=sh_m)},
    }
    f = jax.jit(
        step, donate_argnums=(0,),
        out_shardings=(
            {"params": sh_p, "opt": {"mu": sh_m}},
            NamedSharding(mesh, P()),
        ),
    )
    return shardcheck.StepProgram(
        label="t", stablehlo=f.lower(av).as_text(),
        axis_sizes={"dp": 4}, zero1=True,
    )


def test_sc002_fires_on_replicated_moment_under_zero1():
    """A zero-1 program whose optimizer moment stayed replicated
    across dp is exactly the regression SC002's zero-1 arm exists
    for; the sharded moment stays quiet. (The real trainer program is
    covered by test_grad_accumulator_is_sharding_pinned, which runs
    the full rule set over a live zero-1 lowering.)"""
    bad = _state_program(moments_sharded=False)
    v = shardcheck.check_replicated_moments(bad, 1024)
    assert v and all(x.rule == "SC002" for x in v)
    assert "'opt'" in v[0].message and "replicated across dp=4" in \
        v[0].message
    # same program, zero-1 NOT claimed: dp replication is the
    # documented cost of pure-dp, not a finding
    bad.zero1 = False
    assert shardcheck.check_replicated_moments(bad, 1024) == []
    bad.zero1 = True
    # below threshold: quiet
    assert shardcheck.check_replicated_moments(bad, 1 << 20) == []
    good = _state_program(moments_sharded=True)
    assert shardcheck.check_replicated_moments(good, 1024) == []


def test_sc002_quiet_on_dp_sharded_sp_replicated_moment():
    """A moment correctly dp-sharded on a mixed mesh is replicated
    across the OTHER axis (sp) — ``replicate_ways >= dp`` alone would
    misread that as a zero-1 fallback and (strict mode) veto a correct
    build. The arm mirrors the base rule: only untiled replication is
    a finding."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp")
    )
    sh_p = NamedSharding(mesh, P())
    sh_m = NamedSharding(mesh, P("dp"))  # dp-sharded, sp-replicated

    def step(state):
        out = {
            "params": state["params"] * 2.0,
            "opt": {"mu": state["opt"]["mu"] + 1.0},
        }
        return out, state["params"].sum()

    av = {
        "params": jax.ShapeDtypeStruct((64, 64), np.float32,
                                       sharding=sh_p),
        "opt": {"mu": jax.ShapeDtypeStruct((64, 64), np.float32,
                                           sharding=sh_m)},
    }
    f = jax.jit(
        step, donate_argnums=(0,),
        out_shardings=(
            {"params": sh_p, "opt": {"mu": sh_m}},
            NamedSharding(mesh, P()),
        ),
    )
    prog = shardcheck.StepProgram(
        label="t", stablehlo=f.lower(av).as_text(),
        axis_sizes={"dp": 2, "sp": 2}, zero1=True,
    )
    assert shardcheck.check_replicated_moments(prog, 1024) == []


def test_config_hash_keys_on_effective_mode():
    """The config hash's zero-1 marker follows what the step actually
    builds, not the request: on a mesh where the mode resolves to off
    (no dp axis), a zero-1-requesting trainer hashes identically to
    the replicated one — so its program matches the checked-in plain
    contract instead of failing on config_hash."""
    def bare(mesh, mc, on):
        # no params / init_state: _config_hash reads only knobs+avatars
        tc = TrainConfig(global_batch_size=GB, micro_batch_size=2,
                         warmup_steps=0, total_steps=100, zero1=on)
        return ElasticTrainer(None, llama.param_specs(CFG), mesh, mc,
                              tc, loss_factory=_factory)

    mesh_f, mc_f = _mk(2, fsdp=2)
    tr_on, tr_off = bare(mesh_f, mc_f, True), bare(mesh_f, mc_f, False)
    assert tr_on._zero1_mode(mesh_f) == "off"  # no dp to shard over
    assert tr_on._config_hash(mesh_f) == tr_off._config_hash(mesh_f)
    mesh_d, mc_d = _mk(2)
    tr_d_on, tr_d_off = bare(mesh_d, mc_d, True), bare(mesh_d, mc_d, False)
    assert tr_d_on._zero1_mode(mesh_d) != "off"
    assert (
        tr_d_on._config_hash(mesh_d) != tr_d_off._config_hash(mesh_d)
    )


@pytest.mark.slow
def test_cli_passes_checked_in_zero1_contracts():
    """``python -m dlrover_tpu.lint --hlo dp4+zero1 ...`` exits 0
    against the checked-in zero-1 contract variants. Slow-marked:
    three contract-model lowerings — the tier1.yml shardcheck job runs
    the identical CLI invocation as a CI gate."""
    assert lint_main(
        ["--hlo", "dp4+zero1", "--hlo", "dp2xfsdp2+zero1",
         "--hlo", "dp2xsp2+zero1"]
    ) == 0
