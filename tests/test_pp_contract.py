"""SC008 — pipeline-schedule contracts over the explicit-collective
1F1B engine.

The contract records the schedule's analytic steady-state bubble
fraction ((p-1)/(m*v), the paper's (p-1)/(p*m) at v = p) plus the
HLO's stage-handoff fingerprint (static collective-permute|pp ops and
their trip-weighted executions — the rolled tick loop's trip count IS
the schedule length). A change that re-serializes the schedule (drops
the interleave, flips to gpipe, stretches the tick table) fails the
diff; these tests seed exactly those regressions.
"""

import json

import pytest

from dlrover_tpu.lint import contract_model, shardcheck
from dlrover_tpu.lint.__main__ import main as lint_main


@pytest.fixture(scope="module")
def pp_setup():
    trainer, state, batch = contract_model.build_contract_trainer(
        {"dp": 2, "pp": 2}
    )
    program = trainer.step_ir()
    program.label = "hlo:dp2xpp2"
    return trainer, state, batch, program


def test_pp_program_is_clean(pp_setup):
    _, _, _, program = pp_setup
    assert shardcheck.check_program(program) == []


def test_pp_schedule_hints_ride_the_program(pp_setup):
    _, _, _, program = pp_setup
    assert program.pp_schedule == {
        "schedule": contract_model.PP_SCHEDULE,
        "microbatches": contract_model.PP_MICROBATCHES,
        "virtual_stages": contract_model.PP_VIRTUAL_STAGES,
    }


def test_pp_schedule_report_geometry(pp_setup):
    """The report's bubble fraction is the interleaved model's
    (p-1)/(m*v) — with the pinned v = p geometry, the paper's
    (p-1)/(p*m) — and the handoff evidence shows a rolled tick loop
    (trip-weighted hops exceed the static op count)."""
    _, _, _, program = pp_setup
    report = shardcheck.pp_schedule_report(program)
    assert report["pp"] == 2
    assert report["schedule"] == "1f1b"
    assert report["bubble_fraction"] == pytest.approx(
        (2 - 1)
        / (contract_model.PP_MICROBATCHES
           * contract_model.PP_VIRTUAL_STAGES)
    )
    assert report["bubble_fraction"] == pytest.approx(
        (2 - 1) / (2 * contract_model.PP_MICROBATCHES)
    ), "v = p geometry: (p-1)/(m*v) must equal the paper's (p-1)/(p*m)"
    assert report["ppermute_calls"] > 0
    assert report["ppermute_hops"] > report["ppermute_calls"], (
        "the tick loop must be rolled: trip-weighted hops exceed the "
        "static permute count"
    )


def test_schedule_bubble_fraction_model():
    # interleaved 1f1b, v = p = 2, m = 4: the paper's (p-1)/(p*m)
    assert shardcheck.schedule_bubble_fraction("1f1b", 2, 4, 2) == 0.125
    # losing the interleave doubles the bubble
    assert shardcheck.schedule_bubble_fraction("1f1b", 2, 4, 1) == 0.25
    # gpipe never interleaves, whatever v claims
    assert shardcheck.schedule_bubble_fraction("gpipe", 2, 4, 2) == 0.25
    assert shardcheck.schedule_bubble_fraction("1f1b", 1, 4, 2) == 0.0


def test_pp_contract_roundtrip_and_seeded_regressions(pp_setup, tmp_path):
    """generate → pass; then seeded regressions each fail: a grown
    bubble fraction, a collapsed/stretched handoff pattern; and the
    hash/section gates stay silent."""
    _, _, _, program = pp_setup
    cdir = str(tmp_path)
    shardcheck.write_contract(cdir, "dp2xpp2", program)
    contract = shardcheck.load_contract(cdir, "dp2xpp2")
    assert contract["pp_schedule"]["bubble_fraction"] == 0.125
    assert shardcheck.check_pp_schedule_against_contract(
        program, contract
    ) == []

    # contract remembers a tighter schedule than the program runs
    seeded = json.loads(json.dumps(contract))
    seeded["pp_schedule"]["bubble_fraction"] = 0.0625
    v = shardcheck.check_pp_schedule_against_contract(program, seeded)
    assert any("bubble fraction grew" in x.message for x in v)

    # handoff pattern: the contract schedule ran fewer hops
    seeded = json.loads(json.dumps(contract))
    seeded["pp_schedule"]["ppermute_hops"] = int(
        seeded["pp_schedule"]["ppermute_hops"] * 0.5
    )
    v = shardcheck.check_pp_schedule_against_contract(program, seeded)
    assert any("stage-handoff pattern changed" in x.message for x in v)

    # config-hash mismatch: SC001 owns that report, SC008 stays silent
    seeded = json.loads(json.dumps(contract))
    seeded["config_hash"] = "0000deadbeef"
    assert shardcheck.check_pp_schedule_against_contract(
        program, seeded
    ) == []

    # non-pp contract vintage: no section, no check
    seeded = json.loads(json.dumps(contract))
    del seeded["pp_schedule"]
    assert shardcheck.check_pp_schedule_against_contract(
        program, seeded
    ) == []


@pytest.mark.parametrize(
    "knobs",
    [
        # losing the interleave: v 2 -> 1, bubble 0.125 -> 0.25
        {"PP_VIRTUAL_STAGES": 1},
        # gpipe fallback: serial fill/drain, bubble 0.25
        {"PP_SCHEDULE": "gpipe", "PP_VIRTUAL_STAGES": 1},
    ],
    ids=["deinterleaved", "gpipe"],
)
def test_seeded_reserialization_fails_the_contract(
    pp_setup, tmp_path, monkeypatch, knobs
):
    """The acceptance regression: re-lower the SAME pinned program
    with the schedule re-serialized and diff it against the healthy
    1F1B contract — SC008 must veto it (the config hash covers shapes
    and specs, not the schedule knobs, so the gate stays armed)."""
    _, _, _, program = pp_setup
    cdir = str(tmp_path)
    shardcheck.write_contract(cdir, "dp2xpp2", program)
    contract = shardcheck.load_contract(cdir, "dp2xpp2")

    for name, value in knobs.items():
        monkeypatch.setattr(contract_model, name, value)
    trainer, _, _ = contract_model.build_contract_trainer(
        {"dp": 2, "pp": 2}
    )
    serialized = trainer.step_ir()
    serialized.label = "hlo:dp2xpp2-serialized"
    assert serialized.config_hash == program.config_hash, (
        "schedule knobs must not re-key the program — otherwise the "
        "hash gate would silence exactly the regression SC008 exists "
        "to catch"
    )
    v = shardcheck.check_pp_schedule_against_contract(
        serialized, contract
    )
    assert any(
        x.rule == "SC008" and "bubble fraction grew" in x.message
        for x in v
    ), [x.message for x in v]


def test_checked_in_pp_contracts_pass():
    """The acceptance gate: ``python -m dlrover_tpu.lint --hlo`` exits
    0 against the checked-in pp contracts — the single-slice dp2xpp2
    world and the stage-per-slice pp2+2slice world."""
    assert lint_main(["--hlo", "dp2xpp2", "--hlo", "pp2+2slice"]) == 0
