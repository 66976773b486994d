"""The minicpm_sala family's plain reference against the program at a tiny
size on the CPU, as ``test_qwen3_next_reference.py`` has it for
``qwen3_next``; the comparisons that decide ``correct`` shown to fail for
wrong programs; ``harness/minicpm_sala_flops.py`` against hand counts; the
cell's rehearsal; the new readers on a program that lacks their scopes."""

import json
import math
import os
import subprocess
import sys
import time
import types

import pytest

from conftest import BENCH, ROOT, cell_metrics, load_json, one_device_mesh

from benchmarks.families import minicpm_sala as family
from benchmarks.harness import minicpm_sala_flops as flops
from benchmarks.jobs import finetune_loop

LISTED = "minicpm-sala-9b-d4-1chip.json"
CELL = "minicpm-sala-d4-1chip-steady"
METRICS = ("sala_lightning_ms", "sala_lightning_chunk_ms",
           "sala_lightning_chunk_roofline", "sala_blk_select_ms",
           "sala_blk_score_roofline", "sala_blk_flash_ms",
           "sala_blk_flash_roofline", "sala_attn_proj_ms",
           "sala_dense_mlp_ms", "sala_blk_live_tiles")
#: readers every family shares, which list this cell (or every cell) since
#: PR 58, where they were copies under names of this cell's
SHARED = ("embed_ms", "hbm_peak_gib", "live_rows_drift", "build_lower_s",
          "build_xla_s", "first_step_host_s", "step_dispatch_ms",
          "trainer_idle_ms")


def _ctx(cell_name="tiny-cpu-minicpm-sala-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _built(config, seq=64):
    import jax
    import jax.numpy as jnp

    fam = family.build(config, one_device_mesh())
    params = fam.init_params(jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (1, seq), 0,
                                fam.cfg.vocab_size, dtype=jnp.int32)
    return fam, params, tokens


def test_reference_agrees_with_program_in_float32(capsys):
    config = _ctx().config
    fam, params, tokens = _built(config)
    assert math.isfinite(fam.reference_loss(params, tokens))
    said = capsys.readouterr().out
    assert "FAILED" not in said and all(name in said for name in family.LIMITS)


def test_the_recurrences_blocks_do_not_change_it(monkeypatch):
    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(family, "T_BLOCK", 16)
    monkeypatch.setattr(family, "Q_ROWS", 16)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 2e-5


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(BENCH, "families", "minicpm_sala.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("dlrover_tpu" in ast.unparse(n) for n in top)
    uses = {f.name for f in tree.body if isinstance(f, ast.FunctionDef)
            and "dlrover_tpu" in ast.unparse(f)}
    assert uses == {"build", "_program_la_vjp", "program_pieces", "_seeded",
                    "near_nothing_witness"}


def test_every_width_of_the_listed_file_is_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"name": "MiniCPM-SALA"' in line)
    listed = load_json("configs", LISTED)
    assert listed["source"] == entry["source_url"]
    assert listed["reduced"] == [
        "num_hidden_layers", "mixer_types", "vocab_size"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key
    assert listed["mixer_types"] == entry["config"]["mixer_types"][:4]
    assert (listed["num_hidden_layers"], listed["vocab_size"]) == (
        4, 73448 // 4)


# wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes all of them
WRONG = {
    "another theta": dict(rope_theta=100.0),
    "another eps": dict(rms_norm_eps=0.1),
    "the residual scale by the cut's depth": dict(
        published_num_hidden_layers=16),
    "no forced window": dict(assumed=dict(sparse_config=dict(
        kernel_size=4, kernel_stride=2, block_size=8, topk=4, init_blocks=0,
        window_size=1, dense_len=32))),
    "slower decays": dict(assumed=dict(lightning_slopes=[
        [0.01] * 4, [0.01] * 4])),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    config = _ctx().config
    _, params, tokens = _built(config)
    wrong = family.build(
        dict(config, **{k: {**config[k], **v} if isinstance(v, dict) else v
                        for k, v in WRONG[what].items()}),
        one_device_mesh())
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        wrong.cfg, one_device_mesh(), params, tokens, config, want)
    assert "FAILED" in capsys.readouterr().out


def test_rounding_is_seen_only_below_bfloat16():
    """``second_reading``'s sides at the tiny size: the reference rounded
    to float8 at a scale a tensor stays in range and fails the mixers'
    limits by its rounding; rounded to bfloat16 it passes all but the
    block scores', which are sized on the chip at 16384 positions and 1023
    pooled keys (64 positions and 7 keys read five times as high)."""
    failed = family.second_reading(_ctx().config, seed=5, seq=64)
    assert {"la_rel_median", "sattn_rel_median", "la_grad_rel_p99",
            "la_vjp_rel_max"} <= set(failed["float8_e4m3fn"])
    assert set(failed["bfloat16"]) <= {"blk_score_rel_median"}


def test_the_witness_reads_every_side_at_the_tiny_size():
    """`near_nothing_witness` runs whole: at float32 activations the
    program is the reference to rounding, with the cotangent whole."""
    read = family.near_nothing_witness(_ctx().config, seed=5, seq=64)
    assert set(read) == {"rounded_reference", "program", "program_float32",
                         "program_masked"}
    assert max(read["program_float32"].values()) < 1e-4


def test_correct_when_nothing_is_wrong():
    result = finetune_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}
    assert result["counters"]["live_rows"] > 0


def test_the_rehearsal_prints_a_well_formed_last_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-cpu-minicpm-sala-steady", "--seed", "2147483999", "--seconds",
         "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # on the CPU no kernel runs and no roofline has a peak to read
    for name in METRICS + SHARED:
        if not name.endswith("_roofline") and name != "sala_blk_flash_ms":
            assert math.isfinite(line["metrics"][name]["value"]), name
    assert line["device"]["busy_s"] > 0


def test_the_counts_against_a_hand_count():
    listed = load_json("configs", LISTED)
    assert flops.selected_pairs(16384, listed) == 58_335_232
    assert flops.selected_pairs(8192, listed) == 8192 * 8193 // 2
    assert flops.sparse_matmul_params(listed) == (
        3 * 4096 * 4096 + 2 * 4096 * 256)
    assert flops.lightning_matmul_params(listed) == 5 * 4096 * 4096
    products = 6 * flops.active_matmul_params(listed)
    assert round(products / 1e9, 2) == 7.11
    per_token = flops.flops_per_token(listed, 16384)
    pairs = 14 * 128 * 32 * 58_335_232
    rule = 3 * 3 * 32 * 16384 * (4 * 256 * 128 + 4 * 128 * 128)
    assert per_token * 16384 == pytest.approx(
        products * 16384 + pairs + rule, rel=1e-12)
    assert round(pairs / 1e12, 2) == 3.35 and round(rule / 1e12, 2) == 0.93
    assert round(per_token * 16384 / 1e12, 1) == 120.7
    chunk = flops.lightning_chunk_flops(tokens=16384, heads=32, d=128,
                                        chunk=256)
    assert (chunk["fwd"], chunk["bwd"]) == (
        16384 * 32 * 196608, 16384 * 32 * (10 * 256 * 128 + 8 * 128 * 128))
    assert flops.expected_first_loss(listed) == pytest.approx(
        math.log(18362) + 0.0032)


def test_param_count_is_the_issues_arithmetic():
    fam = family.build(load_json("configs", LISTED), one_device_mesh())
    assert fam.param_count == 1_259_853_184
    assert round(6 * fam.param_count / 1e9, 2) == 7.56
    assert round(8 * fam.param_count / 2**30, 2) == 9.39


def test_the_listed_metrics_are_this_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    ours = [m for m in benchmark["per_layer"] if m["name"].startswith("sala_")]
    assert tuple(m["name"] for m in ours) == METRICS
    at = benchmark["per_layer"].index(ours[0])                  # one block
    assert benchmark["per_layer"][at:at + len(ours)] == ours
    assert set(SHARED) <= set(cell_metrics(CELL))
    for m in ours:
        assert m["workloads"] == [CELL], m["name"]
        spec = load_json("layer_metrics", m["name"] + ".json")
        assert (spec["unit"], spec["better"], spec["source"], spec["layer"],
                spec["moves"]) == (m["unit"], m["better"], m["source"],
                                   m["layer"], m["moves"])
    listed, = [w for w in benchmark["workloads"] if w["name"] == CELL]
    held, = [c for c in benchmark["configs"]
             if c["file"] == "benchmarks/configs/" + LISTED]
    assert listed["config"] == held["name"]
    cell = load_json("workloads", CELL + ".json")
    assert cell["params"] == dict(seq=16384, batch=1, save_every=0,
                                  trace_steps=5, reference_seq=16384)


def test_new_readers_report_nothing_without_their_scopes():
    """On a program that lacks the scopes (the parent's), and off the
    chip, the readers return None and do not raise."""
    import importlib.util

    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    for name in METRICS:
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        if not os.path.exists(path):
            continue
        spec = load_json("layer_metrics", name + ".json")
        # (a gauge and the build's counters are this process's own, which
        # an earlier test's trainer has filled)
        if "gauge" in spec or spec.get("span", "").startswith(
                ("build.", "first_step")):
            continue
        module_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        assert module.read(spec, ctx) is None, name
