"""memcheck (dlrover_tpu/lint/memcheck.py): the guarded
``memory_analysis()`` reader degrades instead of crashing, the analytic
per-leaf model explains the measured bytes on the pinned contract
program, MC001 names the component that grew on a seeded regression,
MC002 gates on the device-class budget, the HeadroomOracle's scaling
law round-trips, and the trainer wirings (strict lower-time veto, the
speculation filter) enforce the verdicts."""

import json
import types

import pytest

from dlrover_tpu.common.world import WorldDescriptor
from dlrover_tpu.lint import memcheck

# ---------------------------------------------------------------------------
# satellite 1: the guarded reader (no jax involved)
# ---------------------------------------------------------------------------


class _Compiled:
    """A fake compiled executable whose memory_analysis() misbehaves in
    every way a backend has been observed to."""

    def __init__(self, ma):
        self._ma = ma

    def memory_analysis(self):
        if isinstance(self._ma, Exception):
            raise self._ma
        return self._ma


def _full_ma(**over):
    fields = dict(
        argument_size_in_bytes=100,
        output_size_in_bytes=50,
        temp_size_in_bytes=30,
        alias_size_in_bytes=40,
        generated_code_size_in_bytes=10,
    )
    fields.update(over)
    return types.SimpleNamespace(**fields)


def test_read_memory_analysis_full_backend():
    out = memcheck.read_memory_analysis(_Compiled(_full_ma()),
                                        label="t-full")
    assert out["argument_bytes"] == 100
    assert out["alias_bytes"] == 40
    # peak = arg + out + temp + generated - alias
    assert out["peak_bytes"] == 100 + 50 + 30 + 10 - 40


def test_read_memory_analysis_none_and_raising_degrade_empty():
    assert memcheck.read_memory_analysis(
        _Compiled(None), label="t-none") == {}
    assert memcheck.read_memory_analysis(
        _Compiled(RuntimeError("no analysis on this backend")),
        label="t-raise") == {}


def test_read_memory_analysis_partial_backend_degrades_per_field():
    # older jaxlib CPU: no generated_code bytes — the key is simply
    # absent and the peak estimate monotonically degrades
    ma = _full_ma()
    del ma.generated_code_size_in_bytes
    out = memcheck.read_memory_analysis(_Compiled(ma), label="t-part")
    assert "generated_code_bytes" not in out
    assert out["peak_bytes"] == 100 + 50 + 30 - 40
    # non-numeric fields degrade the same way
    out = memcheck.read_memory_analysis(
        _Compiled(_full_ma(temp_size_in_bytes="n/a")), label="t-nan"
    )
    assert "temp_bytes" not in out and out["argument_bytes"] == 100


def test_read_memory_analysis_warns_once_per_label_field(monkeypatch):
    warned = []
    rec = types.SimpleNamespace(
        warning=lambda fmt, *a: warned.append(fmt % a)
    )
    monkeypatch.setattr(memcheck, "logger", rec)
    ma = _full_ma()
    del ma.alias_size_in_bytes
    memcheck.read_memory_analysis(_Compiled(ma), label="t-once")
    assert len(warned) == 1 and "alias_size_in_bytes" in warned[0]
    # the second lowering of the same label is silent: one line per
    # (label, field) per process, not one per compile
    memcheck.read_memory_analysis(_Compiled(ma), label="t-once")
    assert len(warned) == 1


def test_measured_peak_clamps_at_zero():
    assert memcheck.measured_peak_bytes({"alias_bytes": 999}) == 0


# ---------------------------------------------------------------------------
# the analytic per-leaf model
# ---------------------------------------------------------------------------


def test_dtype_bytes():
    assert memcheck.dtype_bytes("float32") == 4
    assert memcheck.dtype_bytes("bfloat16") == 2
    assert memcheck.dtype_bytes("float8_e4m3fn") == 1
    assert memcheck.dtype_bytes("int4") == 1  # sub-byte floors at 1
    assert memcheck.dtype_bytes("mystery") == 4  # unknown -> f32 width


def test_leaf_avatar_bytes():
    leaf = memcheck.LeafAvatar(
        path="['params']['w']", shape=(4, 8), dtype="float32",
        sharded_axes=("fsdp", "tp"),
    )
    assert leaf.global_bytes() == 4 * 8 * 4
    assert leaf.per_device_bytes({"fsdp": 2, "tp": 2}) == 32.0
    # an axis the mesh doesn't have divides by 1, never by 0
    assert leaf.per_device_bytes({"fsdp": 2}) == 64.0


def test_classify_leaf():
    assert memcheck.classify_leaf("['params']['blocks'][0]") == "params"
    assert memcheck.classify_leaf("['opt'][0]['mu']") == "moments"
    assert memcheck.classify_leaf("['step']") == "moments"


def _leaves():
    state = [
        memcheck.LeafAvatar("['params']['w']", (250,), "float32"),
        memcheck.LeafAvatar("['opt'][0]['mu']", (125,), "float32"),
    ]
    batch = [memcheck.LeafAvatar("['tokens']", (16,), "int32")]
    return state, batch


def test_analytic_components_and_temp_residue():
    state, batch = _leaves()
    comps = memcheck.analytic_components(state, batch, {})
    assert comps["params"] == 1000
    assert comps["moments"] == 500
    assert comps["grads_accum"] == 1000  # shaped like the params
    assert comps["activations"] == 64
    assert comps["temp"] == 0  # nothing measured
    # temp = measured arena + generated code - the modeled grads
    comps = memcheck.analytic_components(
        state, batch, {}, measured={"temp_bytes": 5000,
                                    "generated_code_bytes": 100}
    )
    assert comps["temp"] == 5000 + 100 - 1000
    # ...clamped at zero when the arena is smaller than the grads
    comps = memcheck.analytic_components(
        state, batch, {}, measured={"temp_bytes": 400}
    )
    assert comps["temp"] == 0
    assert memcheck.analytic_peak_bytes(comps) == sum(comps.values())


def test_explain_delta_frac():
    comps = {"params": 100, "moments": 50, "activations": 10}
    assert memcheck.explain_delta_frac(
        comps, {"argument_bytes": 160}) == 0.0
    assert memcheck.explain_delta_frac(
        comps, {"argument_bytes": 200}) == pytest.approx(0.2)
    assert memcheck.explain_delta_frac(comps, {}) is None


# ---------------------------------------------------------------------------
# MC001: contract round-trip and the seeded diff
# ---------------------------------------------------------------------------

_COMPS = {
    "params": 1_000_000, "moments": 500_000, "grads_accum": 1_000_000,
    "activations": 100_000, "temp": 400_000,
}
_PEAK = sum(_COMPS.values())


def test_contract_write_load_round_trip(tmp_path):
    memcheck.write_mem_contract(
        str(tmp_path), "dp4", _COMPS, _PEAK,
        measured={"argument_bytes": 7}, extra={"config_hash": "abc"},
    )
    data = memcheck.load_mem_contract(str(tmp_path), "dp4")
    assert data["components"] == _COMPS
    assert data["peak_bytes"] == _PEAK
    assert data["config_hash"] == "abc"
    assert memcheck.load_mem_contract(str(tmp_path), "dp8") is None


def test_load_rejects_foreign_contract_file(tmp_path):
    # an SC001 census contract on the same spec name must not parse as
    # a memcheck one
    with open(tmp_path / "mem-dp4.json", "w") as f:
        json.dump({"census": {}}, f)
    with pytest.raises(ValueError, match="not a .*memcheck contract"):
        memcheck.load_mem_contract(str(tmp_path), "dp4")


def test_check_components_names_the_grown_component():
    contract = {"components": dict(_COMPS), "peak_bytes": _PEAK}
    grown = dict(_COMPS, moments=int(_COMPS["moments"] * 1.5))
    out = memcheck.check_components(
        grown, sum(grown.values()), contract
    )
    assert out, "a 1.5x component growth must fail MC001"
    assert any("'moments'" in v.message for v in out)
    assert all(v.rule == "MC001" for v in out)


def test_check_components_tolerances():
    contract = {"components": dict(_COMPS), "peak_bytes": _PEAK}
    # +5% is inside the 10% tolerance
    ok = dict(_COMPS, params=int(_COMPS["params"] * 1.05))
    assert memcheck.check_components(ok, sum(ok.values()), contract) == []
    # a KB-scale component exploding relatively but under the absolute
    # floor never flaps the gate
    small = {"components": dict(_COMPS, activations=1000),
             "peak_bytes": _PEAK}
    noisy = dict(_COMPS, activations=60_000)
    assert memcheck.check_components(
        noisy, sum(noisy.values()), small) == []


def test_peak_growth_blames_largest_component_delta():
    contract = {"components": dict(_COMPS), "peak_bytes": _PEAK}
    # spread growth so no single component trips its own gate but the
    # peak does: the violation still points at the biggest mover
    grown = dict(_COMPS)
    grown["temp"] = int(_COMPS["temp"] * 1.09)
    grown["moments"] = int(_COMPS["moments"] * 1.09)
    grown["params"] = int(_COMPS["params"] * 1.30)
    out = memcheck.check_components(grown, sum(grown.values()), contract)
    peak_v = [v for v in out if "peak grew" in v.message]
    assert peak_v and "'params'" in peak_v[0].message


def test_component_improvements_note_shrinks():
    contract = {"components": dict(_COMPS), "peak_bytes": _PEAK}
    better = dict(_COMPS, temp=100_000)
    notes = memcheck.component_improvements(
        better, sum(better.values()), contract
    )
    assert any("'temp'" in n for n in notes)
    assert memcheck.component_improvements(
        dict(_COMPS), _PEAK, contract) == []


# ---------------------------------------------------------------------------
# MC002 + the HeadroomOracle scaling law
# ---------------------------------------------------------------------------


def test_budget_bytes_precedence():
    assert memcheck.budget_bytes("v5e") == 16e9
    assert memcheck.budget_bytes("cpu-host") == 4e9
    # an explicit GB override beats the class table
    assert memcheck.budget_bytes("v5e", 2.0) == 2e9
    assert memcheck.budget_bytes("") == 0.0


def test_check_budget():
    assert memcheck.check_budget(20e9, device_class="v5e") != []
    # usable = 16 GB * 0.9 = 14.4 GB
    assert memcheck.check_budget(15e9, device_class="v5e") != []
    assert memcheck.check_budget(14e9, device_class="v5e") == []
    assert memcheck.check_budget(1e18) == []  # no budget -> off
    v = memcheck.check_budget(5e9, device_class="cpu-host")[0]
    assert v.rule == "MC002" and "cpu-host" in v.message


def test_component_divisor_scaling_laws():
    wd = WorldDescriptor.from_axis_sizes({"dp": 2, "fsdp": 4})
    assert memcheck.component_divisor("params", wd) == 4
    assert memcheck.component_divisor("grads_accum", wd) == 4
    assert memcheck.component_divisor("moments", wd) == 4
    # zero-1 adds the dp term to the moments — the reason a SHRINK can
    # OOM while a grow never does
    assert memcheck.component_divisor(
        "moments", wd, assume_zero1=True) == 8
    z1 = WorldDescriptor.from_axis_sizes({"dp": 4}, zero1=True)
    assert memcheck.component_divisor("moments", z1) == 4
    # ...and the caller's override wins over the descriptor flag
    assert memcheck.component_divisor("moments", z1, assume_zero1=False) == 1
    sp = WorldDescriptor.from_axis_sizes({"dp": 2, "sp": 2})
    assert memcheck.component_divisor("activations", sp) == 2
    assert memcheck.component_divisor("temp", wd) == 1


def test_oracle_from_components_round_trips_at_base():
    comps = {"params": 100.0, "moments": 40.0, "grads_accum": 100.0,
             "activations": 8.0, "temp": 7.0}
    base = WorldDescriptor.from_axis_sizes({"dp": 4}, zero1=True)
    oracle = memcheck.HeadroomOracle.from_components(
        comps, base, assume_zero1=True
    )
    pred = oracle.predict(base)
    for c, v in comps.items():
        assert pred[c] == pytest.approx(v)
    assert pred["peak_bytes"] == pytest.approx(255.0)
    # halving dp doubles the per-device moments and nothing else
    dp2 = WorldDescriptor.from_axis_sizes({"dp": 2})
    pred2 = oracle.predict(dp2)
    assert pred2["moments"] == pytest.approx(80.0)
    assert pred2["params"] == pytest.approx(100.0)
    assert pred2["temp"] == pytest.approx(7.0)


def test_oracle_fits_and_unarmed_budget():
    base = WorldDescriptor.from_axis_sizes({"dp": 4}, zero1=True)
    oracle = memcheck.HeadroomOracle.from_components(
        {"moments": 2e9, "temp": 0.5e9}, base,
        budget_gb=4.0, assume_zero1=True,
    )
    assert oracle.fits(base)["fits"]  # 2.5 GB < 3.6 usable
    dp1 = WorldDescriptor.from_axis_sizes({"dp": 1})
    verdict = oracle.fits(dp1)  # 8 + 0.5 GB on one device
    assert not verdict["fits"]
    assert verdict["peak_bytes"] == int(8.5e9)
    assert verdict["usable_bytes"] == int(4e9 * 0.9)
    # zero budget = unarmed: everything fits
    unarmed = memcheck.HeadroomOracle.from_components(
        {"moments": 2e9}, base, assume_zero1=True
    )
    assert unarmed.fits(dp1)["fits"]


def test_oracle_from_checked_in_contract():
    contract = memcheck.load_mem_contract(
        memcheck.DEFAULT_CONTRACTS_DIR, "dp4+zero1"
    )
    assert contract is not None, "checked-in mem-dp4+zero1.json missing"
    oracle = memcheck.HeadroomOracle.from_contract(contract)
    base = WorldDescriptor.parse("dp4+zero1")
    pred = oracle.predict(base)
    for c in memcheck.COMPONENTS:
        assert pred[c] == pytest.approx(contract["components"][c])
    assert pred["peak_bytes"] == pytest.approx(
        contract["peak_bytes"], rel=1e-9
    )
    # the shrink direction packs the zero-1 moments tighter per device
    dp2 = WorldDescriptor.parse("dp2")
    assert oracle.predict(dp2, assume_zero1=True)["moments"] == (
        pytest.approx(contract["components"]["moments"] * 2)
    )


# ---------------------------------------------------------------------------
# the compiled program: parity, the checked-in contracts, the trainer
# wirings (everything below lowers the pinned contract model on CPU)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dp4():
    from dlrover_tpu.lint import contract_model

    with contract_model._pinned_flags():
        trainer, state, batch = contract_model.build_contract_trainer(
            {"dp": 4}
        )
        payload = trainer.memcheck_payload()
    return trainer, state, batch, payload


def test_payload_matches_checked_in_contract(dp4):
    _, _, _, payload = dp4
    contract = memcheck.load_mem_contract(
        memcheck.DEFAULT_CONTRACTS_DIR, "dp4"
    )
    assert contract is not None
    assert contract["config_hash"] == payload["config_hash"], (
        "the pinned contract model drifted: regenerate every "
        "mem-*.json with --fix-contracts in this PR"
    )
    assert memcheck.check_components(
        payload["components"], payload["peak_bytes"], contract
    ) == []


def test_analytic_model_explains_measured_bytes(dp4):
    """The acceptance parity: the per-leaf model vs XLA's own
    accounting, within 10% — on this backend it is within 1%."""
    _, _, _, payload = dp4
    measured = payload.get("measured") or {}
    if not measured.get("peak_bytes"):
        pytest.skip("backend reported no memory_analysis()")
    peak = measured["peak_bytes"]
    assert abs(payload["peak_bytes"] - peak) / peak <= 0.10
    # the argument cross-check (params+moments+activations vs the
    # measured argument bytes) is even tighter
    assert payload["argument_delta_frac"] <= 0.01


def test_hook_strict_vetoes_seeded_regression(dp4, tmp_path,
                                              monkeypatch):
    """Seed a contract whose moments are a quarter of the program's:
    the lower-time hook must refuse the build AND say which component
    grew."""
    from dlrover_tpu.lint import contract_model

    trainer, state, _, payload = dp4
    seeded = dict(payload["components"])
    seeded["moments"] //= 4
    memcheck.write_mem_contract(
        str(tmp_path), "dp4", seeded, sum(seeded.values()),
        extra={"config_hash": payload["config_hash"]},
    )
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK", "2")
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK_CONTRACTS", str(tmp_path))
    trainer.warm.clear()
    with contract_model._pinned_flags():
        with pytest.raises(memcheck.MemcheckError) as exc:
            trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert "'moments'" in str(exc.value)


def test_hook_strict_budget_veto_propagates_to_step(dp4, monkeypatch):
    """MC002 in strict mode: a budget the program cannot fit rejects
    the build, and step() re-raises instead of silently falling back to
    plain jit (which would run the rejected program)."""
    trainer, state, batch, _ = dp4
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK", "2")
    # ~100 KB budget vs a ~1.5 MB/device program
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK_BUDGET_GB", "0.0001")
    trainer.warm.clear()
    with pytest.raises(memcheck.MemcheckError) as exc:
        trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert any(v.rule == "MC002" for v in exc.value.violations)
    with pytest.raises(memcheck.MemcheckError):
        trainer.step(state, batch)


def test_hook_warn_mode_builds_anyway(dp4, monkeypatch):
    trainer, _, _, _ = dp4
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK", "1")
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK_BUDGET_GB", "0.0001")
    trainer.warm.clear()
    compiled, info = trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert compiled is not None and info["cache"] == "miss"


def test_speculation_filter_drops_oom_worlds(dp4, monkeypatch):
    """The oracle in front of the speculative compiles: no AOT build is
    spent on a world the planner would oom-veto anyway."""
    trainer, _, _, _ = dp4
    targets = [WorldDescriptor.parse("dp2"), WorldDescriptor.parse("dp8")]
    # unarmed: pass-through untouched
    monkeypatch.delenv("DLROVER_TPU_MEMCHECK_DEVICE_CLASS",
                       raising=False)
    monkeypatch.delenv("DLROVER_TPU_MEMCHECK_BUDGET_GB", raising=False)
    assert trainer._filter_speculation_targets(targets) == targets
    # armed with a budget nothing fits: every neighbor dropped
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK_BUDGET_GB", "0.0001")
    assert trainer._filter_speculation_targets(targets) == []
    # armed with room to spare: every neighbor kept
    monkeypatch.setenv("DLROVER_TPU_MEMCHECK_BUDGET_GB", "1000")
    assert trainer._filter_speculation_targets(targets) == targets


def test_read_memory_analysis_planned_peak_where_the_backend_states_it():
    # the compiler's own heap simulation + generated code, beside the
    # sum (which can overcount a step whose temporaries reuse donated
    # arguments); absent, not zero, where the backend has no such field
    out = memcheck.read_memory_analysis(
        _Compiled(_full_ma(peak_memory_in_bytes=120)), label="t-plan")
    assert out["planned_peak_bytes"] == 120 + 10
    assert out["peak_bytes"] == 100 + 50 + 30 + 10 - 40
    for absent in (_full_ma(), _full_ma(peak_memory_in_bytes=0),
                   _full_ma(peak_memory_in_bytes=None)):
        assert "planned_peak_bytes" not in memcheck.read_memory_analysis(
            _Compiled(absent), label="t-noplan")
