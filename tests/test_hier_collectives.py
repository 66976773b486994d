"""Hierarchical DCN-aware collectives (ops/hier_collectives.py + the
trainer strategy layer + the per-link SC001 census).

The contract under test, end to end: on a multislice mesh the dp
gradient reduction runs ICI-first (reduce-scatter within the slice →
DCN exchange of only the slice-local 1/dp_in shard → ICI all-gather),
training is numerically equivalent to the flat path (the acceptance
criterion's step-loss parity), the DCN bytes drop to ~1/dp_in of the
flat path's — provable three ways (the analytic ledger exactly, the
per-link census against the flat per-issue baseline, and the
checked-in ``dp4+2slice`` / ``dp4+2slice+zero1`` contracts) — and
``TrainConfig(hier_collectives=False)`` builds the flat path
byte-identically (plain contract spec, plain config hash).
"""

import dataclasses

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from dlrover_tpu.lint import shardcheck
from dlrover_tpu.models import llama
from dlrover_tpu.ops import hier_collectives as hc
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train import warm_compile as wc
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

CFG = llama.LlamaConfig.tiny()
SEQ = 16
GB = 16  # micro=2 → accum 2 on dp4 (the grad-accum scan composes)


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(wc.ENV_KILL_SWITCH, raising=False)
    monkeypatch.delenv(wc.ENV_CACHE_DIR, raising=False)
    yield


def _factory(mesh):
    return lambda p, t: llama.loss_fn(p, t, CFG, mesh)


def _make(world, n_slices, zero1=False, hier=True, gb=GB,
          overlap=False):
    """``overlap=False`` pins the FUSED hierarchical engine — the
    TrainConfig default is overlap-on, and most of this file tests the
    fused engine's census/ledger shape specifically."""
    mc = MeshConfig(dp=-1).resolve(world)
    mesh = build_mesh(
        mc, devices=jax.devices()[:world],
        n_slices=n_slices if n_slices > 1 else 1,
    )
    tc = TrainConfig(global_batch_size=gb, micro_batch_size=2,
                     warmup_steps=0, total_steps=100, zero1=zero1,
                     hier_collectives=hier, overlap_collectives=overlap)
    tr = ElasticTrainer(None, llama.param_specs(CFG), mesh, mc, tc,
                        loss_factory=_factory, n_slices=n_slices)
    params = jax.device_put(
        llama.init_params(CFG, jax.random.key(0)),
        named_shardings(mesh, llama.param_specs(CFG)),
    )
    state = tr.init_state(params)
    return tr, state


def _batch(tr, key):
    a, b = tr.step_batch_shape
    return jax.random.randint(jax.random.key(key), (a, b, SEQ), 0,
                              CFG.vocab_size)


def _run(world, n_slices, zero1, hier, steps, overlap=False):
    tr, state = _make(world, n_slices, zero1, hier, overlap=overlap)
    losses = []
    for i in range(steps):
        state, loss = tr.step(state, _batch(tr, 100 + i))
        losses.append(float(loss))
    return tr, state, losses


def _assert_parity(l_a, l_b, s_a, s_b):
    np.testing.assert_allclose(l_a, l_b, rtol=0, atol=2e-5)
    for a, b in zip(jax.tree.leaves(s_a["params"]),
                    jax.tree.leaves(s_b["params"])):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-6
        )


# ---------------------------------------------------------------------------
# pure units: mode selection, derived mesh, spec translation
# ---------------------------------------------------------------------------


class _FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


def test_mode_for():
    tc = TrainConfig(hier_collectives=True, overlap_collectives=False)
    ov = TrainConfig()  # both knobs default on → overlap
    off = TrainConfig(hier_collectives=False)
    # multislice pure dp with a non-trivial within-slice remainder
    assert hc.mode_for(_FakeMesh(dp=4), 2, tc, True) == "hier"
    assert hc.mode_for(_FakeMesh(dp=8), 2, tc, True, "scatter") == "hier"
    # overlap = hier eligibility + the overlap knob
    assert hc.mode_for(_FakeMesh(dp=4), 2, ov, True) == "overlap"
    assert hc.mode_for(_FakeMesh(dp=8), 2, ov, True, "scatter") == \
        "overlap"
    # single slice / knob off / no factory → flat
    assert hc.mode_for(_FakeMesh(dp=4), 1, tc, True) == "flat"
    assert hc.mode_for(_FakeMesh(dp=4), 2, off, True) == "flat"
    assert hc.mode_for(_FakeMesh(dp=4), 2, tc, False) == "flat"
    # dp_in == 1: the dp axis IS the DCN axis, nothing to do ICI-first
    assert hc.mode_for(_FakeMesh(dp=2), 2, tc, True) == "flat"
    # dp not tiling into slices
    assert hc.mode_for(_FakeMesh(dp=6), 4, tc, True) == "flat"
    # non-trivial model axis: the manual body is single-device code
    assert hc.mode_for(_FakeMesh(dp=4, tp=2), 2, tc, True) == "flat"
    # gspmd zero-1 has no manual engine to compose with — and overlap
    # never outlives hier eligibility
    assert hc.mode_for(_FakeMesh(dp=4), 2, tc, True, "gspmd") == "flat"
    assert hc.mode_for(_FakeMesh(dp=4), 2, ov, True, "gspmd") == "flat"
    assert hc.mode_for(_FakeMesh(dp=4), 2, tc, True, "off") == "hier"


def test_mixed_mesh_flat_fallback_warns_once(monkeypatch):
    """satellite: the mixed-mesh silent flat fallback is silent no
    more — the FIRST multislice mixed-mesh build logs a warning naming
    the config field and the docs, subsequent ones stay quiet (one
    latch, not one log line per lowering)."""
    monkeypatch.setattr(hc, "_warned_mixed_flat", False)
    warnings = []
    monkeypatch.setattr(
        hc.logger, "warning",
        lambda msg, *a, **k: warnings.append(msg % a if a else msg),
    )
    ov = TrainConfig()
    assert hc.mode_for(_FakeMesh(dp=4, tp=2), 2, ov, True) == "flat"
    assert hc.mode_for(_FakeMesh(dp=4, tp=2), 2, ov, True) == "flat"
    named = [w for w in warnings if "TrainConfig.hier_collectives" in w]
    assert len(named) == 1, warnings
    assert "tp" in named[0]  # names the offending axes too


def test_partition_buckets():
    items = list("abcdef")
    sizes = [10, 20, 30, 40, 50, 60]
    # greedy in-order, bound respected, oversized item → own bucket
    assert hc._partition_buckets(items, sizes, 60) == \
        [["a", "b", "c"], ["d"], ["e"], ["f"]]
    assert hc._partition_buckets(items, sizes, 1) == \
        [[i] for i in items]
    assert hc._partition_buckets(items, sizes, 10 ** 9) == [items]
    assert hc._partition_buckets([], [], 5) == []


def test_hier_mesh_preserves_flat_device_order():
    """The derived mesh is a pure reshape: same devices, same flat
    order, dp split slice-major — so base-mesh and derived-mesh
    shardings describe identical placements."""
    mesh = build_mesh(
        MeshConfig(dp=-1).resolve(8), devices=jax.devices()[:8],
        n_slices=2,
    )
    hm = hc.hier_mesh(mesh, 2)
    assert hm.shape[hc.SLICE_AXIS] == 2
    assert hm.shape[hc.DP_IN_AXIS] == 4
    assert [d.id for d in hm.devices.flat] == \
        [d.id for d in mesh.devices.flat]
    with pytest.raises(ValueError, match="divisible"):
        hc.hier_mesh(mesh, 3)


def test_split_spec():
    assert hc.split_spec(P("dp")) == P(("slice", "dp_in"))
    assert hc.split_spec(P(("dp", "fsdp"))) == \
        P(("slice", "dp_in", "fsdp"))
    assert hc.split_spec(P(None, "tp")) == P(None, "tp")
    assert hc.split_spec(P()) == P()


# ---------------------------------------------------------------------------
# parity: the fast path is the same math (acceptance criterion)
# ---------------------------------------------------------------------------


def test_parity_replicated_dp4_2slice():
    """8 steps on a virtual 2-slice dp4 mesh: the hierarchical
    reduction matches the flat path's losses and final params within
    float tolerance (the reductions associate differently — bitwise
    equality is not expected, the acceptance bar is
    bitwise-or-tolerance)."""
    tr_f, s_f, l_f = _run(4, 2, False, hier=False, steps=8)
    tr_h, s_h, l_h = _run(4, 2, False, hier=True, steps=8)
    assert tr_f._hier_mode(tr_f.mesh) == "flat"
    assert tr_h._hier_mode(tr_h.mesh) == "hier"
    _assert_parity(l_f, l_h, s_f, s_h)


def test_parity_zero1_dp4_2slice():
    """zero-1 composition: the DCN leg is itself a reduce-scatter into
    the zero-1 layout; losses and params match the flat scatter
    engine, and the moments stay dp-sharded."""
    tr_f, s_f, l_f = _run(4, 2, True, hier=False, steps=8)
    tr_h, s_h, l_h = _run(4, 2, True, hier=True, steps=8)
    assert tr_h._zero1_mode(tr_h.mesh) == "scatter"
    assert tr_h._hier_mode(tr_h.mesh) == "hier"
    _assert_parity(l_f, l_h, s_f, s_h)
    specs = {
        str(l.sharding.spec) for l in jax.tree.leaves(s_h["opt"])
        if getattr(l, "ndim", 0) > 0
    }
    assert any("'dp'" in s for s in specs), specs


@pytest.mark.slow
def test_parity_overlap_replicated_dp4_2slice():
    """satellite (bucketing parity): 8 steps, replicated weight
    update — the overlap schedule (pipelined DCN exchange + post-scan
    flush) matches BOTH the flat path and the fused hierarchical
    engine within float tolerance. The accumulation order is
    constructed identical; only op fusion differs."""
    tr_f, s_f, l_f = _run(4, 2, False, hier=False, steps=8)
    tr_o, s_o, l_o = _run(4, 2, False, hier=True, steps=8,
                          overlap=True)
    assert tr_o._hier_mode(tr_o.mesh) == "overlap"
    _assert_parity(l_f, l_o, s_f, s_o)


@pytest.mark.slow
def test_parity_overlap_zero1_dp4_2slice():
    """satellite (bucketing parity), zero-1 scatter mode: the bucketed
    psum_scatter exchange lands the same shards as the fused chained
    scatters, and the hierarchized trailing param gather rebuilds the
    same params."""
    tr_h, s_h, l_h = _run(4, 2, True, hier=True, steps=8)
    tr_o, s_o, l_o = _run(4, 2, True, hier=True, steps=8, overlap=True)
    assert tr_o._zero1_mode(tr_o.mesh) == "scatter"
    assert tr_o._hier_mode(tr_o.mesh) == "overlap"
    _assert_parity(l_h, l_o, s_h, s_o)


def test_overlap_kill_switch_restores_hier_program():
    """``overlap_collectives=False`` downgrades an overlap trainer to
    the fused hier program — contract key and mode revert, hier itself
    stays on."""
    tr, _ = _make(4, 2, overlap=True)
    assert tr._hier_mode(tr.mesh) == "overlap"
    assert tr._contract_spec(tr.mesh) == "dp4+2slice+overlap"
    tr.tc = dataclasses.replace(tr.tc, overlap_collectives=False)
    assert tr._hier_mode(tr.mesh) == "hier"
    assert tr._contract_spec(tr.mesh) == "dp4+2slice"
    # and hier_collectives=False still flattens everything
    tr.tc = dataclasses.replace(tr.tc, hier_collectives=False)
    assert tr._hier_mode(tr.mesh) == "flat"
    assert tr._contract_spec(tr.mesh) == "dp4"


@pytest.mark.slow
def test_overlap_engine_bucket_bounds_do_not_change_math():
    """Engine-level: ANY bucket bound — single-bucket degenerate, a
    bound that cuts mid-list (non-dividing), one-leaf-per-bucket —
    produces gradients equal to the fused engine's, in both weight
    -update layouts (per-element addition order is identical by
    construction; tolerance covers op-fusion rounding).

    Slow-marked: the 3 overlap-parity compile matrices (~48 s of cold
    compiles) would push the tier-1 ``-m 'not slow'`` sweep past its
    870 s budget; CI runs them in an explicit tier1.yml step, same
    pattern as the bench contracts."""
    mesh = build_mesh(
        MeshConfig(dp=-1).resolve(4), devices=jax.devices()[:4],
        n_slices=2,
    )
    specs = llama.param_specs(CFG)
    params = jax.device_put(
        llama.init_params(CFG, jax.random.key(0)),
        named_shardings(mesh, specs),
    )
    micro = jax.random.randint(jax.random.key(7), (4, SEQ), 0,
                               CFG.vocab_size)
    # mesh=None: inside the full-manual engines the loss must not emit
    # its own sharding constraints (the trainer passes None the same way)
    loss = _factory(None)
    for z1 in (False, True):
        fused = jax.jit(hc.hier_value_and_grad(
            loss, mesh, 2, specs, params, zero1_scatter=z1
        ))
        l_ref, g_ref = fused(params, micro)
        for bb in (1, 50_000, 1 << 30):
            comp, exch = hc.overlap_value_and_grad(
                loss, mesh, 2, specs, params, zero1_scatter=z1,
                bucket_bytes=bb,
            )
            l_o, pending = jax.jit(comp)(params, micro)
            g_o = jax.jit(exch)(pending)
            np.testing.assert_allclose(
                float(l_ref), float(l_o), rtol=0, atol=1e-6
            )
            for a, b in zip(jax.tree.leaves(g_ref),
                            jax.tree.leaves(g_o)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=0, atol=1e-6,
                )


# ---------------------------------------------------------------------------
# the DCN-bytes claim, proven three ways
# ---------------------------------------------------------------------------


def _census_of(tr, state):
    tr.record_avatars(state, np.asarray(_batch(tr, 0)))
    program = tr.step_ir()
    return shardcheck.collective_census(program.hlo, program.coords())


def _dp_dcn(census):
    return sum(
        c.get("dcn_bytes", 0) for k, c in census.items()
        if "dp" in k.split("|")[1]
    )


def test_ledger_dcn_ratio():
    """The analytic comm ledger (per-ISSUE accounting, the unit the
    /metrics ``dlrover_tpu_comm_bytes_total{link=…}`` rows export).
    Replicated mode: hier DCN bytes/step == flat's / dp_in, exactly
    (the flat psum moves the whole gradient over DCN, the hier psum
    only the 1/dp_in shard). Zero-1 scatter mode: the ledger's
    contribution unit scores flat RS and the hier DCN RS leg the same
    (both emit a 1/dp shard) — the census's operand-based DCN model is
    the instrument that shows that win — so the ledger asserts
    no-worse DCN plus the new ICI legs."""
    from dlrover_tpu.profiler.comm import comm_ledger

    dp_in = 4 // 2
    for z1 in (False, True):
        tr_f, _ = _make(4, 2, zero1=z1, hier=False)
        flat_links = comm_ledger.link_bytes()
        tr_h, _ = _make(4, 2, zero1=z1, hier=True)
        hier_links = comm_ledger.link_bytes()
        assert flat_links.get("dcn", 0) > 0
        assert flat_links.get("ici", 0) == 0  # pure dp, one flat leg
        assert hier_links.get("ici", 0) > 0   # the within-slice legs
        assert hier_links["dcn"] <= flat_links["dcn"]
        if not z1:
            assert hier_links["dcn"] * dp_in == flat_links["dcn"]


def test_census_dcn_drop_replicated():
    """SC001 per-link census, replicated mode: the hierarchical
    program's dp DCN bytes are ≤ (1/dp_in + tolerance) of the flat
    path's per-issue DCN baseline.

    The flat census itself is scan-compressed (the llama layer scan
    and chunked-CE vocab scan count a reduction once per PROGRAM, the
    documented SC001 unit) while the hier engine's reductions sit
    outside every scan — so the honest flat baseline is the analytic
    ledger's per-issue bytes under the same DCN model (payload × (1 −
    1/n_slices)), which the flat census bounds from below."""
    n_slices, dp = 2, 4
    dp_in = dp // n_slices
    tr_f, s_f = _make(dp, n_slices, hier=False)
    from dlrover_tpu.profiler.comm import comm_ledger

    flat_ledger_dcn = comm_ledger.link_bytes()["dcn"]
    flat_census = _census_of(tr_f, s_f)
    tr_h, s_h = _make(dp, n_slices, hier=True)
    hier_census = _census_of(tr_h, s_h)
    # flat per-issue DCN baseline under the census's model
    flat_baseline = flat_ledger_dcn * (1.0 - 1.0 / n_slices)
    hier_dcn = _dp_dcn(hier_census)
    assert hier_dcn > 0
    assert hier_dcn <= (1.0 / dp_in + 0.05) * flat_baseline, (
        hier_dcn, flat_baseline
    )
    # and program-to-program (both fingerprints), strictly less
    assert hier_dcn < _dp_dcn(flat_census)
    # the ICI legs exist: RS + AG cells with zero DCN bytes
    assert hier_census["reduce-scatter|dp"]["dcn_bytes"] == 0
    assert hier_census["all-gather|dp"]["dcn_bytes"] == 0


def test_census_dcn_drop_zero1_exact():
    """zero-1 scatter mode: BOTH engines sit outside every scan, so
    the census comparison is equal-footing and exact — the hier grad
    reduce-scatter's DCN bytes are flat's × 1/dp_in, and (satellite)
    the trailing param all-gather is hierarchized too: AG over slice
    first (DCN carries only the 1/dp_in slice-local shard) then AG
    over dp_in on ICI — its DCN bytes are also flat's × 1/dp_in, at
    the cost of a second ICI stage (doubled op count)."""
    n_slices, dp = 2, 4
    dp_in = dp // n_slices
    tr_f, s_f = _make(dp, n_slices, zero1=True, hier=False)
    flat = _census_of(tr_f, s_f)
    tr_h, s_h = _make(dp, n_slices, zero1=True, hier=True)
    hier = _census_of(tr_h, s_h)
    assert hier["reduce-scatter|dp"]["dcn_bytes"] * dp_in == \
        flat["reduce-scatter|dp"]["dcn_bytes"]
    assert hier["all-gather|dp"]["dcn_bytes"] * dp_in == \
        flat["all-gather|dp"]["dcn_bytes"]
    assert hier["all-gather|dp"]["dcn_bytes"] > 0
    assert hier["all-gather|dp"]["count"] == \
        2 * flat["all-gather|dp"]["count"]


# ---------------------------------------------------------------------------
# contracts: checked-in artifacts + the slow-link veto
# ---------------------------------------------------------------------------


def test_checked_in_2slice_contracts_show_the_drop():
    """The acceptance bar, pinned on the checked-in artifacts: the
    dp4+2slice contracts exist, carry per-cell dcn_bytes, and their
    grad-reduction DCN bytes are ≤ (1/dp_in + tol) of the flat
    per-issue baseline computed from the same contract model."""
    repl = shardcheck.load_contract(
        shardcheck.DEFAULT_CONTRACTS_DIR, "dp4+2slice"
    )
    z1 = shardcheck.load_contract(
        shardcheck.DEFAULT_CONTRACTS_DIR, "dp4+2slice+zero1"
    )
    assert repl is not None and z1 is not None
    assert repl["n_slices"] == 2 and z1["n_slices"] == 2
    dp_in = 2
    # replicated: the contract model is accum=1 and its grad psums are
    # per-leaf outside the hier engine's scans — param bytes of the
    # pinned tiny model (the flat baseline payload) recovered from the
    # zero-1 contract's DCN reduce-scatter leg: per-leaf dcn model is
    # (leaf/dp) × n_slices × (1 − 1/n_slices) = leaf/dp, so the cell's
    # dcn_bytes × dp is the full payload
    param_bytes = z1["census"]["reduce-scatter|dp"]["dcn_bytes"] * 4
    flat_baseline = param_bytes * (1.0 - 1.0 / 2)  # flat AR, 2 slices
    hier_dcn = repl["census"]["all-reduce|dp"]["dcn_bytes"]
    assert 0 < hier_dcn <= (1.0 / dp_in + 0.05) * flat_baseline
    # zero-1: the hier DCN reduce-scatter carries 1/dp of the grads —
    # half the flat RS's dcn share; the flat zero-1 RS under the same
    # model would be param_bytes × (1-1/2)
    assert z1["census"]["reduce-scatter|dp"]["dcn_bytes"] * dp_in == \
        int(param_bytes * 0.5)
    # ICI legs carry no DCN bytes in the replicated contract
    assert repl["census"]["reduce-scatter|dp"]["dcn_bytes"] == 0
    assert repl["census"]["all-gather|dp"]["dcn_bytes"] == 0
    # distinct programs → distinct hashes vs the flat dp4 contracts
    flat = shardcheck.load_contract(shardcheck.DEFAULT_CONTRACTS_DIR,
                                    "dp4")
    assert repl["config_hash"] != flat["config_hash"]


def test_checked_in_overlap_contracts_record_positive_ratio():
    """The overlap acceptance bar, pinned on checked-in artifacts: the
    ``+overlap`` contracts exist and record ``overlap_ratio > 0`` with
    most DCN bytes classified overlapped — so a change that
    re-serializes the DCN exchange fails SC006 in CI. The fused-hier
    contracts carry the same section at ratio 0.0 (their exposure
    baseline)."""
    d = shardcheck.DEFAULT_CONTRACTS_DIR
    ov = shardcheck.load_contract(d, "dp4+2slice+overlap")
    ovz = shardcheck.load_contract(d, "dp4+2slice+overlap+zero1")
    repl = shardcheck.load_contract(d, "dp4+2slice")
    assert ov is not None and ovz is not None
    # accum=3 → 2 of 3 exchanges ride the scan carry: ratio 2/3; the
    # zero-1 variant adds the (exposed) hierarchized param gather
    assert ov["overlap"]["overlap_ratio"] == pytest.approx(2 / 3,
                                                           abs=0.01)
    assert ovz["overlap"]["overlap_ratio"] == pytest.approx(0.5,
                                                            abs=0.01)
    assert ov["overlap"]["dcn_overlapped_bytes"] > \
        ov["overlap"]["dcn_exposed_bytes"]
    assert repl["overlap"]["overlap_ratio"] == 0.0
    # distinct program identity from the fused-hier contract
    assert ov["config_hash"] != repl["config_hash"]


# ---------------------------------------------------------------------------
# the overlap classifier itself + the SC006 veto (seeded regressions)
# ---------------------------------------------------------------------------

# hand-written post-GSPMD HLO for a dp4 / 2-slice world (slice-major:
# devices {0,1} are slice 0, {2,3} slice 1 — groups {{0,2},{1,3}} span
# the DCN cut). One trip-4 loop whose body carries TWO dcn all-reduces:
# %pipelined consumes only loop-carried state (overlapped), %serial
# consumes this iteration's dot (exposed); plus an entry-level flush.
_SCHED_HLO = """\
HloModule sched_test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%cond (cp: (s32[], f32[256], f32[256], f32[256])) -> pred[] {
  %cp = (s32[], f32[256], f32[256], f32[256]) parameter(0)
  %ci = s32[] get-tuple-element((s32[], f32[256], f32[256], f32[256]) %cp), index=0
  %lim = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %ci, s32[] %lim), direction=LT
}

%body (bp: (s32[], f32[256], f32[256], f32[256])) -> (s32[], f32[256], f32[256], f32[256]) {
  %bp = (s32[], f32[256], f32[256], f32[256]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[256], f32[256], f32[256]) %bp), index=0
  %one = s32[] constant(1)
  %ni = s32[] add(s32[] %i, s32[] %one)
  %x = f32[256] get-tuple-element((s32[], f32[256], f32[256], f32[256]) %bp), index=1
  %carry = f32[256] get-tuple-element((s32[], f32[256], f32[256], f32[256]) %bp), index=2
  %resh = f32[256] reshape(f32[256] %carry)
  %pipelined = f32[256] all-reduce(f32[256] %resh), channel_id=1, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  %m = f32[16,16] reshape(f32[256] %x)
  %d = f32[16,16] dot(f32[16,16] %m, f32[16,16] %m), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %flatd = f32[256] reshape(f32[16,16] %d)
  %serial = f32[256] all-reduce(f32[256] %flatd), channel_id=2, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  ROOT %bt = (s32[], f32[256], f32[256], f32[256]) tuple(s32[] %ni, f32[256] %x, f32[256] %serial, f32[256] %pipelined)
}

ENTRY %main (p0: f32[256], p1: f32[256]) -> f32[256] {
  %p0 = f32[256] parameter(0)
  %p1 = f32[256] parameter(1)
  %zero = s32[] constant(0)
  %t = (s32[], f32[256], f32[256], f32[256]) tuple(s32[] %zero, f32[256] %p0, f32[256] %p1, f32[256] %p1)
  %w = (s32[], f32[256], f32[256], f32[256]) while((s32[], f32[256], f32[256], f32[256]) %t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  %res = f32[256] get-tuple-element((s32[], f32[256], f32[256], f32[256]) %w), index=3
  ROOT %flush = f32[256] all-reduce(f32[256] %res), channel_id=3, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
}
"""


def _sched_coords():
    return shardcheck.MeshCoords({"dp": 4}, n_slices=2)


def test_overlap_report_sync_classification():
    """The sync closure rule on the seeded module: the loop-carried
    all-reduce is overlapped (trip-weighted ×4), the dot-fed one and
    the entry flush exposed. All three move the same 512 modeled DCN
    bytes per issue (1024B result × (1 − 1/2))."""
    rep = shardcheck.overlap_report(_SCHED_HLO, _sched_coords())
    per_issue = 1024 // 2
    assert rep["dcn_overlapped_bytes"] == 4 * per_issue
    assert rep["dcn_exposed_bytes"] == 4 * per_issue + per_issue
    assert 0 < rep["overlap_ratio"] < 1
    verdicts = {r["line"]: r["overlapped"] for r in rep["ops"]}
    assert list(verdicts.values()).count(True) == 1


def test_overlap_report_async_pairs():
    """The async rule: a ``-start``/``-done`` pair with an independent
    dot in the same computation is overlapped; when the only compute
    consumes the ``-done`` (or feeds the ``-start``), it is exposed."""
    hidden = """\
HloModule async_ok

ENTRY %main (p0: f32[256], p1: f32[16,16]) -> (f32[512], f32[16,16]) {
  %p0 = f32[256] parameter(0)
  %p1 = f32[16,16] parameter(1)
  %ags = (f32[256], f32[512]) all-gather-start(f32[256] %p0), channel_id=1, replica_groups={{0,2},{1,3}}, dimensions={0}, use_global_device_ids=true
  %d = f32[16,16] dot(f32[16,16] %p1, f32[16,16] %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %agd = f32[512] all-gather-done((f32[256], f32[512]) %ags)
  ROOT %out = (f32[512], f32[16,16]) tuple(f32[512] %agd, f32[16,16] %d)
}
"""
    serial = hidden.replace("async_ok", "async_serial").replace(
        "dot(f32[16,16] %p1", "dot(f32[16,16] %dep"
    ).replace(
        "%d = f32[16,16] ",
        "%mat = f32[16,16] reshape(f32[512] %agd)\n"
        "  %dep = f32[16,16] slice(f32[16,16] %mat), "
        "slice={[0:16], [0:16]}\n  %d = f32[16,16] ",
    )
    coords = _sched_coords()
    rep_ok = shardcheck.overlap_report(hidden, coords)
    assert rep_ok["dcn_overlapped_bytes"] > 0
    assert rep_ok["dcn_exposed_bytes"] == 0
    rep_bad = shardcheck.overlap_report(serial, coords)
    assert rep_bad["dcn_overlapped_bytes"] == 0
    assert rep_bad["dcn_exposed_bytes"] > 0


def test_sc006_serialized_program_fails_overlap_contract():
    """satellite (seeded shardcheck regression): a program whose DCN
    exchange was deliberately re-serialized — the loop-carried
    all-reduce now consumes the CURRENT iteration's dot — fails the
    overlap contract on BOTH arms (exposed bytes grew, ratio
    dropped); the faithful program passes the same contract."""
    good_rep = shardcheck.overlap_report(_SCHED_HLO, _sched_coords())
    contract = {
        "config_hash": "h", "n_slices": 2,
        "census": {}, "overlap": {
            "dcn_exposed_bytes": good_rep["dcn_exposed_bytes"],
            "dcn_overlapped_bytes": good_rep["dcn_overlapped_bytes"],
            "overlap_ratio": good_rep["overlap_ratio"],
        },
    }
    # re-serialize: feed the pipelined all-reduce from the dot instead
    # of the loop carry
    serialized = _SCHED_HLO.replace(
        "all-reduce(f32[256] %resh)", "all-reduce(f32[256] %flatd)"
    )
    mk = lambda hlo: shardcheck.StepProgram(  # noqa: E731
        label="t", axis_sizes={"dp": 4}, hlo=hlo, config_hash="h",
        n_slices=2, overlap=True,
    )
    assert shardcheck.check_overlap_against_contract(
        mk(_SCHED_HLO), contract
    ) == []
    v = shardcheck.check_overlap_against_contract(
        mk(serialized), contract
    )
    assert len(v) == 2 and all(x.rule == "SC006" for x in v)
    assert any("re-serialized" in x.message for x in v)
    assert any("overlap_ratio dropped" in x.message for x in v)
    # a contract with no overlap section (pre-overlap vintage) or a
    # different config hash stays silent
    assert shardcheck.check_overlap_against_contract(
        mk(serialized), {"config_hash": "h", "census": {}}
    ) == []
    other = dict(contract, config_hash="other")
    assert shardcheck.check_overlap_against_contract(
        mk(serialized), other
    ) == []


def test_sc001_dcn_veto():
    """The slow-link veto: a program whose census moved bytes onto DCN
    beyond tolerance fails against a slice-aware contract, even when
    total bytes are unchanged."""
    program = shardcheck.StepProgram(
        label="t", axis_sizes={"dp": 4}, hlo="x", config_hash="h",
        n_slices=2,
    )
    contract = {
        "config_hash": "h", "n_slices": 2,
        "census": {"all-reduce|dp": {
            "count": 1, "bytes": 1000, "dcn_bytes": 100,
        }},
    }
    ok = {"all-reduce|dp": {"count": 1, "bytes": 1000, "dcn_bytes": 100}}
    bad = {"all-reduce|dp": {"count": 1, "bytes": 1000, "dcn_bytes": 500}}
    assert shardcheck.check_census_against_contract(
        program, contract, census=ok
    ) == []
    v = shardcheck.check_census_against_contract(
        program, contract, census=bad
    )
    assert len(v) == 1 and "DCN bytes grew" in v[0].message
    # a contract WITHOUT slice info never fires the dcn arm (old
    # contracts keep working against multislice flat programs)
    legacy = {"config_hash": "h",
              "census": {"all-reduce|dp": {"count": 1, "bytes": 1000}}}
    assert shardcheck.check_census_against_contract(
        program, legacy, census=bad
    ) == []
    # dcn shrink is an improvement note, not a violation
    better = {"all-reduce|dp": {"count": 1, "bytes": 1000,
                                "dcn_bytes": 10}}
    assert shardcheck.check_census_against_contract(
        program, contract, census=better
    ) == []
    notes = shardcheck.census_improvements(better, contract)
    assert notes and "slow link" in notes[0]


def test_link_classification_units():
    """MeshCoords link attribution: within-slice groups are ici,
    cross-slice groups dcn; degenerate topologies fail soft."""
    coords = shardcheck.MeshCoords({"dp": 4}, n_slices=2)
    assert coords.slice_of(0) == 0 and coords.slice_of(3) == 1
    assert coords.link_of_groups([(0, 1), (2, 3)]) == ("ici", 1)
    assert coords.link_of_groups([(0, 2), (1, 3)]) == ("dcn", 2)
    assert coords.link_of_groups([]) == ("dcn", 2)  # all-participants
    assert coords.link_of_pairs([(0, 1)]) == ("ici", 1)
    assert coords.link_of_pairs([(1, 2)]) == ("dcn", 2)
    # single slice: everything ici, censuses carry no dcn keys
    c1 = shardcheck.MeshCoords({"dp": 4})
    assert c1.link_of_groups([(0, 2)]) == ("ici", 1)
    # a world that doesn't tile into slices degrades to single-slice
    odd = shardcheck.MeshCoords({"dp": 3}, n_slices=2)
    assert odd.n_slices == 1


# ---------------------------------------------------------------------------
# signatures, labels, kill-switch fallback in the trainer
# ---------------------------------------------------------------------------


def test_signatures_and_labels_separate_programs():
    """Flat and hier builds on the same mesh must never share an AOT
    executable or a contract key; turning the field off restores the
    plain label and the plain (pre-hier) config hash."""
    tr_h, state = _make(4, 2, hier=True)
    tr_f, _ = _make(4, 2, hier=False)
    batch = np.asarray(_batch(tr_h, 1))
    tr_h.record_avatars(state, batch)
    tr_f.record_avatars(state, batch)
    sig_h, hash_h = tr_h._step_signature(tr_h.mesh, tr_h.mesh_config,
                                         tr_h.accum_steps)
    sig_f, hash_f = tr_f._step_signature(tr_f.mesh, tr_f.mesh_config,
                                         tr_f.accum_steps)
    assert sig_h != sig_f and hash_h != hash_f
    assert tr_h._contract_spec(tr_h.mesh) == "dp4+2slice"
    assert tr_f._contract_spec(tr_f.mesh) == "dp4"
    # hier_collectives=False downgrades the hier trainer to the flat
    # program — label, hash and signature all revert
    tr_h.tc = dataclasses.replace(tr_h.tc, hier_collectives=False)
    sig_k, hash_k = tr_h._step_signature(tr_h.mesh, tr_h.mesh_config,
                                         tr_h.accum_steps)
    assert (sig_k, hash_k) == (sig_f, hash_f)
    assert tr_h._contract_spec(tr_h.mesh) == "dp4"


def test_slices_for_neighbor_worlds():
    """Warm-compile targets: slices are atomic, so a neighbor world's
    slice count derives from the per-slice size — a slice loss
    speculates the (smaller) multislice program, a collapse to one
    slice speculates flat."""
    tr, _ = _make(8, 2)
    assert tr._slices_for_size(8) == 2
    assert tr._slices_for_size(4) == 1   # one slice left → flat
    assert tr._slices_for_size(12) == 3  # grown by a slice
    assert tr._slices_for_size(6) == 1   # partial slice → flat
    tr1, _ = _make(4, 1)
    assert tr1._slices_for_size(2) == 1


def test_resize_across_slice_counts():
    """The elastic journey the feature exists for: a 2-slice world
    loses a slice. The state live-reshards, the surviving single-slice
    world builds the FLAT program (hier needs >1 slice), and training
    continues to a finite loss; n_slices follows the resize."""
    tr, state = _make(8, 2)
    state, _ = tr.step(state, _batch(tr, 1))
    jax.block_until_ready(state)
    assert tr._hier_mode(tr.mesh) == "hier"
    mc4 = MeshConfig(dp=-1).resolve(4)
    mesh4 = build_mesh(mc4, devices=jax.devices()[:4])
    new_state = tr.remesh(mesh4, mc4, state=state)
    assert tr.n_slices == 1
    assert tr._hier_mode(tr.mesh) == "flat"
    assert new_state is not None
    new_state, loss = tr.step(new_state, _batch(tr, 2))
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_cli_passes_checked_in_2slice_contracts():
    """``python -m dlrover_tpu.lint --hlo dp4+2slice ...`` exits 0
    against the checked-in multislice contract variants — the
    tier1.yml shardcheck job runs the identical invocation as a CI
    gate."""
    from dlrover_tpu.lint.__main__ import main as lint_main

    assert lint_main(
        ["--hlo", "dp4+2slice", "--hlo", "dp4+2slice+zero1",
         "--hlo", "dp4+2slice+overlap",
         "--hlo", "dp4+2slice+overlap+zero1"]
    ) == 0
