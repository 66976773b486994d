"""bench.py output contract, pinned.

Whoever records bench.py's single JSON line depends on the shapes
asserted here.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_json_line_contract(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_BENCH_PHASES"] = "mfu,ckpt"
    # this test pins the CPU contract (tiny config, fast sweep, sub-second
    # shm save). bench.py runs on the CPU only when that is asked for
    # explicitly, and fails otherwise when it finds no TPU
    env["JAX_PLATFORMS"] = "cpu"
    # isolate the persistent jit cache per test run
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jitcache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])

    # the driver's parse: one JSON object with these exact keys
    assert d["metric"] == "train_step_mfu"
    assert d["unit"] == "fraction"
    assert isinstance(d["value"], (int, float))
    assert isinstance(d["vs_baseline"], (int, float))
    detail = d["detail"]
    assert detail["backend"] in ("cpu", "tpu")
    # SC001 comms fingerprint rides every round (lint/shardcheck):
    # a dict of "op|axes" cells — empty on this single-device mesh,
    # and never an {"error": ...} marker
    assert isinstance(detail["collective_census"], dict)
    assert "error" not in detail["collective_census"]
    # phase accounting: completed phases, in order
    assert detail["phases_done"] == ["mfu", "ckpt"]
    assert detail["sweep"], "sweep must list measured candidates"
    assert detail["model"] == detail["sweep"][0]["name"]
    ckpt = detail["ckpt"]
    assert ckpt["stage_mode"] == "device_snapshot"
    assert ckpt["blocking_save_s"] < 1.0  # the design claim, CPU-measured
    assert ckpt["trials"] >= 1
    # the tier-0 fast path stays pinned: a same-world shm restore is
    # attributed to shm — with its piece/byte accounting — never
    # silently rerouted through disk/object
    rs = ckpt["restore_stats"]
    assert rs["tier"] == "shm"
    assert rs["pieces"] > 0 and rs["bytes"] > 0
    # XLA's HBM accounting rides every round: winner + per-candidate.
    # The zero-1 compare belongs to the resize phase (not requested
    # here) and must say so instead of silently missing.
    hbm = detail["hbm"]
    assert hbm["winner"].get("argument_bytes", 0) > 0, hbm
    assert all("hbm" in c for c in detail["sweep"])
    assert hbm["zero1"].get("skipped")
    # ISSUE 17: the winner's step decomposes into a per-kernel
    # breakdown (profiler/kernel_ledger) whose top-k names >=80 % of
    # the measured step, and the attention tiling sweep reports why it
    # sat out on CPU (reference attention has no tiles to sweep)
    kb = detail["kernel_breakdown"]
    assert "error" not in kb, kb
    assert kb["top"], "top-k cut must be non-empty"
    assert kb["covered_share"] >= 0.8
    assert all(
        {"op", "seconds", "share", "sites"} <= set(row) for row in kb["top"]
    )
    assert detail["attn_tiling"].get("skipped")
    # fce-vs-cce A/B provenance: every candidate measured under pinned
    # flags records them; the _fce candidate never runs on CPU (the
    # dispatcher would silently measure the chunked program)
    assert not any(c["name"].endswith("_fce") for c in detail["sweep"])
    for c in detail["sweep"]:
        if c["name"].endswith("_cce"):
            assert c["flags"] == {"FUSED_CE": False}


@pytest.mark.slow
def test_bench_ckpt_dedup_contract(tmp_path):
    """ISSUE 7 acceptance, pinned on the dp4 CPU world: deduplicated
    per-node persisted bytes ≈ 1/dp of the replicated baseline, the
    blocking shm save stays unchanged (sub-second), and a simulated
    missing-node restore succeeds through the tier ladder with tier
    attribution recorded.

    Slow-marked: a third full bench subprocess (cold jit cache) would
    push the tier-1 ``-m 'not slow'`` sweep past its 870 s budget; CI
    runs it explicitly in the tier1.yml checkpoint-tiers step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_BENCH_PHASES"] = "mfu,ckpt"
    env["JAX_PLATFORMS"] = "cpu"
    # 4 virtual devices -> the dp4 world of the acceptance criterion
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        ).strip() + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jitcache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    ckpt = d["detail"]["ckpt"]
    # the dedup legs must not have perturbed the blocking save
    assert ckpt["blocking_save_s"] < 1.0
    dd = ckpt["dedup"]
    assert dd["dp"] == 4
    base = dd["replicated_baseline_bytes"]
    assert base > 0
    per_node = dd["per_node_persisted_bytes"]
    assert len(per_node) == 4
    # every byte persisted exactly once: the union IS the state
    assert sum(per_node) == base
    # the contract: per-node bytes beat replicated by ~dp (the 0.5
    # slack absorbs the round-robin of unsplittable scalars)
    assert dd["max_node_bytes"] < base / (dd["dp"] - 0.5), dd
    assert dd["dedup_ratio"] < 1 / (dd["dp"] - 0.5)
    # the missing-node restore: node 0's shm AND local disk destroyed,
    # the union of the survivors + object tier restores bitwise
    tr = dd["tiered_restore"]
    assert tr["ok"] is True
    assert tr["bitwise_equal"] is True
    assert tr["tier"] == "object"
    assert tr["pieces"] > 0 and tr["bytes"] == base
    assert tr["restore_s"] > 0


@pytest.mark.slow
def test_bench_resize_phase_contract(tmp_path):
    """The ``resize`` phase reports remesh→first-step downtime cold vs
    warm, and the warm-compile cache makes the rebuild measurably
    faster (ISSUE 2 acceptance: warm/cold ratio in the JSON detail),
    plus the layout leg's warm dp↔fsdp flip (ISSUE 17).

    Slow-marked since the layout leg landed: the dp2→fsdp2 flip is a
    cold compile in the bench subprocess, which pushed the tier-1
    ``-m 'not slow'`` sweep past its 870 s budget; CI runs this test
    explicitly in the tier1.yml resize-contract step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_BENCH_PHASES"] = "resize"
    env["JAX_PLATFORMS"] = "cpu"
    # 4 virtual devices so the resize is a REAL world change (4 → 2)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        ).strip() + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jitcache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    rz = d["detail"]["resize"]
    assert rz["mode"] == "half_world"
    assert rz["world"] == 4 and rz["target_world"] == 2
    assert rz["speculation_completed"]
    assert rz["cold_downtime_s"] > 0 and rz["warm_downtime_s"] > 0
    # the post-resize program's comms fingerprint: the target world is
    # dp=2, so its data all-reduce must show up attributed to dp
    census = rz["collective_census"]
    assert any(k.endswith("|dp") for k in census), census
    # the acceptance bar: a warm cache beats a cold compile. The cold
    # side recompiles a full train step (seconds even for the tiny
    # model); the warm side dispatches a cached executable (~ms) — 0.9
    # leaves an order of magnitude of slack for CI jitter.
    # both sides independently rounded to 4 decimals in the JSON
    assert rz["warm_cold_ratio"] == pytest.approx(
        rz["warm_downtime_s"] / rz["cold_downtime_s"], abs=1e-3
    )
    assert rz["warm_cold_ratio"] < 0.9
    # the ledger shows the speculative compile that made warm possible
    sources = [
        c["source"]
        for entry in rz["compile_ledger"].values()
        for c in entry
    ]
    assert "speculative" in sources
    assert "warm" in sources
    # the state half (live reshard, ISSUE 4): moving the train state
    # device-to-device beats the shm round-trip by a wide margin
    state = rz["state"]
    assert state["state_bytes"] > 0
    assert state["transfer_path"] in ("direct", "leafwise", "bridge")
    assert state["state_transfer_s"] > 0
    assert state["compile_s"] >= 0
    assert state["shm_roundtrip_s"] >= state["shm_restore_s"] > 0
    # acceptance bar: live state transfer WELL below the round-trip
    # (measured ~0.06 on CPU 4-dev; 0.5 leaves wide CI slack)
    assert state["live_vs_shm_ratio"] == pytest.approx(
        state["state_transfer_s"] / state["shm_roundtrip_s"], abs=1e-3
    )
    assert state["live_vs_shm_ratio"] < 0.5
    assert "resize" in d["detail"]["phases_done"]
    # the zero-1 HBM claim as a measured number (4 devices → dp4, the
    # scatter mode): sharded moments shrink the per-device step
    # arguments by 3/4 of the two adam moment trees, the temp arena
    # shrinks too, and the dp-axis collective bytes DROP (the
    # allreduce → reduce-scatter + all-gather rewrite moves less)
    z1 = d["detail"]["hbm"]["zero1"]
    assert z1["on"]["mode"] == "scatter"
    assert z1["argument_saved_bytes"] > 0, z1
    assert z1["temp_saved_bytes"] > 0, z1
    assert z1["on"]["dp_axis_bytes"] < z1["off"]["dp_axis_bytes"]
    # ISSUE 17: the same-world layout flip (the planner's layout_payback
    # action). Flipping dp2 -> fsdp2 pays the fsdp compile; flipping
    # back lands on the executable this very trainer built minutes ago
    # — the warm in-process remesh a planner-hinted flip is promised.
    layout = rz["layout"]
    assert layout["from"] == "dp2" and layout["to"] == "fsdp2"
    assert layout["flip_to_s"] > 0 and layout["flip_back_warm_s"] > 0
    assert layout["warm_hit"] is True
    assert layout["flip_back_warm_s"] < layout["flip_to_s"]


@pytest.mark.slow
def test_bench_multislice_contract(tmp_path):
    """ISSUE 13 + 16 acceptance, pinned on the 8-device
    2-virtual-slice CPU world (dp8, dp_in=4): the bench multislice
    phase runs three legs — flat, fused-hier, and the
    overlap-scheduled hierarchy. The hierarchical program's ledger DCN
    bytes are exactly 1/dp_in of the flat path's, the per-link census
    confirms the drop with its ICI legs dcn-free, the overlap leg's
    *exposed* DCN bytes land strictly below the fused-hier baseline
    with a positive SC006 overlap_ratio, and step-loss parity holds
    across all legs (the overlap schedule is the same math in the same
    addition order).

    Slow-marked for the same budget reason as the ckpt dedup contract;
    CI runs it explicitly in the tier1.yml hierarchical-collectives
    step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_BENCH_PHASES"] = "multislice"
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jitcache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    ms = d["detail"]["multislice"]
    assert "multislice" in d["detail"]["phases_done"], ms
    assert ms["n_slices"] == 2 and ms["world"] == 8
    dp_in = ms["world"] // ms["n_slices"]
    assert ms["flat"]["mode"] == "flat"
    assert ms["hier"]["mode"] == "hier"
    # the headline: analytic DCN bytes drop to exactly 1/dp_in
    assert ms["dcn_bytes_ratio"] == pytest.approx(1.0 / dp_in)
    # per-link census: the hier program moves strictly less over DCN,
    # and its within-slice RS/AG legs are dcn-free
    assert 0 < ms["hier"]["census_dcn_bytes"] < \
        ms["flat"]["census_dcn_bytes"]
    cells = ms["hier"]["census_dp_cells"]
    assert cells["reduce-scatter|dp"]["dcn_bytes"] == 0
    assert cells["all-gather|dp"]["dcn_bytes"] == 0
    # contract keys: each leg is its own program variant
    assert ms["hier"]["contract_spec"] == "dp8+2slice"
    assert ms["flat"]["contract_spec"] == "dp8"
    assert ms["overlap"]["contract_spec"] == "dp8+2slice+overlap"
    # the overlap headline (PR 16 acceptance): the schedule hides most
    # of the DCN leg behind compute — trip-weighted EXPOSED bytes
    # strictly below the fused-hier baseline (whose DCN is all
    # exposed), ratio (accum-1)/accum with accum=3
    assert ms["overlap"]["mode"] == "overlap"
    assert ms["hier"]["overlap_ratio"] == 0.0
    assert ms["hier"]["dcn_overlapped_bytes"] == 0
    assert 0 < ms["overlap"]["dcn_exposed_bytes"] < \
        ms["hier"]["dcn_exposed_bytes"]
    assert ms["overlap"]["overlap_ratio"] == pytest.approx(
        2.0 / 3.0, abs=0.01
    )
    assert ms["overlap"]["dcn_overlapped_bytes"] > \
        ms["overlap"]["dcn_exposed_bytes"]
    # overlap never changes the loss: step parity across ALL legs
    assert ms["max_loss_delta"] <= 1e-5


@pytest.mark.slow
def test_bench_pp_resize_contract(tmp_path):
    """ISSUE 19 acceptance, pinned on the 8-device CPU world: the
    resize phase's two pipeline legs. The ``pp`` leg shrinks dp2xpp2
    to pp2 through the per-stage transfer plan and must land warm —
    the post-resize step dispatches the stage-aware speculatively
    compiled executable — with the schedule-table bubble fraction
    matching the analytic ``(p-1)/(p·m)``. The ``pp_multislice`` leg
    pins one stage per virtual slice and must attribute the stage-1
    handoff to DCN before collapsing the slice boundary.

    Slow-marked: two extra cold pp compiles in the bench subprocess
    don't fit the tier-1 870 s budget; CI runs this test explicitly in
    the tier1.yml pp-resize-contract step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["DLROVER_BENCH_PHASES"] = "resize"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count=8", ""
        ).strip() + " --xla_force_host_platform_device_count=8"
    ).strip()
    # the speculative thread only arms with a persistent compile
    # cache to land its executables in — without this the warm leg
    # silently degrades to a cold rebuild
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jitcache")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])

    pp = d["detail"]["resize"]["pp"]
    assert "error" not in pp, pp
    assert pp["from"] == "dp2xpp2" and pp["to"] == "pp2"
    # shrinking dp within stages never crosses a stage boundary
    assert pp["stage_plan_kind"] == "dp_within_stage"
    # the acceptance bar: the schedule table's measured fill/drain
    # fraction IS the paper's closed form (p-1)/(p·m) for v = p
    assert pp["bubble_fraction_analytic"] == pytest.approx(0.125)
    assert pp["bubble_fraction"] == pytest.approx(
        pp["bubble_fraction_analytic"], abs=0.02
    )
    assert pp["speculation_completed"] is True
    # the definitive warm evidence: the post-resize step landed on the
    # speculatively-built executable, and it beat the cold rebuild
    assert pp["warm_hit"] is True
    assert 0 < pp["warm_downtime_s"] < pp["cold_downtime_s"]
    assert pp["warm_cold_ratio"] < 0.9
    assert "loss_mismatch" not in pp, pp
    # SC008 fingerprint of the live post-resize program rides the
    # trajectory JSON: same analytic bubble, rolled tick loop
    rep = pp["pp_schedule_report"]
    assert rep["pp"] == 2 and rep["schedule"] == "1f1b"
    assert rep["bubble_fraction"] == pytest.approx(0.125)
    assert rep["ppermute_hops"] > rep["ppermute_calls"] > 0
    census = pp["collective_census"]
    assert any(
        k.startswith("collective-permute") and "pp" in k for k in census
    ), census

    ms = d["detail"]["resize"]["pp_multislice"]
    assert "error" not in ms, ms
    assert ms["from"] == "pp2+2slice" and ms["to"] == "pp2"
    # one stage per virtual slice; the stage count survives the
    # collapse (dp_within_stage), but stage 1's leg crosses the
    # (virtual) DCN cut — exactly the per-stage plan's cross_slice mark
    assert ms["stage_map"] == [[0], [1]]
    assert ms["stage_plan_kind"] == "dp_within_stage"
    assert ms["cross_slice_stages"] == [1]
    assert ms["census_dcn_bytes"] > 0
    assert ms["pp_schedule_report"]["bubble_fraction"] == \
        pytest.approx(0.125)
    assert ms["cross_slice_resize_s"] > 0
