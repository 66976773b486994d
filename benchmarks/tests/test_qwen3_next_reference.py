"""The qwen3_next family's plain reference against the program at a tiny
size on the CPU, as ``test_dots3_reference.py`` has it for ``dots3``; the
comparisons that decide ``correct`` shown to fail for wrong programs;
``harness/qwen3_next_flops.py`` against hand counts; the cell's
rehearsal; the new readers on a program that lacks their scopes."""

import json
import math
import os
import re
import subprocess
import sys
import time
import types

import pytest

from conftest import BENCH, ROOT, cell_metrics, load_json, one_device_mesh

from benchmarks.families import qwen3_next as family
from benchmarks.harness import qwen3_next_flops as flops
from benchmarks.jobs import finetune_loop

LISTED = "qwen3-next-80b-a3b-ep16-1chip.json"
CELL = "qwen3next-ep16-1chip-steady"
METRICS = ("q3n_gdn_ms", "q3n_gdn_chunk_ms", "q3n_gdn_chunk_roofline",
           "q3n_gattn_flash_ms", "q3n_gattn_flash_roofline",
           "q3n_attn_proj_ms", "q3n_moe_experts_roofline")
#: readers every family shares, which list this cell (or every cell) since
#: PR 58, where they were copies under names of this cell's
SHARED = ("moe_share_ms", "moe_live_rows", "live_rows_drift", "hbm_peak_gib",
          "moe_experts_ms", "moe_dispatch_ms", "embed_ms")


def _ctx(cell_name="tiny-cpu-qwen3-next-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _weighty(params):
    """At 64 wide and sigma 0.02 every branch adds next to nothing:
    branches that weigh, norm weights and gates off their init."""
    import jax

    keys = iter(jax.random.split(jax.random.key(5), 256))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "o_norm",
                     "dt_bias"):
            if name in lp:
                lp[name] = lp[name] + 0.3 * jax.random.normal(
                    next(keys), lp[name].shape)
        for name, by in (("router", 40.0), ("w_o", 30.0), ("w_down", 100.0),
                         ("ws_down", 30.0), ("w_s", 30.0), ("w_ba", 20.0),
                         ("w_qkvz", 5.0), ("w_q", 5.0), ("w_k", 5.0)):
            if name in lp:
                lp[name] = lp[name] * by
        return lp

    return dict(params, layers={
        k: slab(v) for k, v in params["layers"].items()})


def _built(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def test_reference_agrees_with_program_in_float32(capsys):
    import jax

    config = _ctx().config
    fam, params, tokens = _built(config)
    program = float(jax.jit(fam.loss_fn)(params, tokens))
    assert abs(program - family.reference_loss(params, tokens, config)) < 2e-5
    # the hook the job calls: every comparison holds, so it is the loss
    assert abs(fam.reference_loss(params, tokens) - program) < 2e-5
    out = capsys.readouterr().out
    assert "FAILED" not in out
    for name in family.LIMITS:
        assert name in out, name


def test_the_recurrences_blocks_do_not_change_it(monkeypatch):
    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(family, "T_BLOCK", 16)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 2e-5


def test_the_reference_imports_nothing_of_the_program():
    """``build`` and the program's side of the comparison import
    ``dlrover_tpu`` inside their functions; the reference's own code
    does not name it."""
    import ast

    path = os.path.join(BENCH, "families", "qwen3_next.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("dlrover_tpu" in ast.unparse(n) for n in top)
    uses = {f.name for f in tree.body if isinstance(f, ast.FunctionDef)
            and "dlrover_tpu" in ast.unparse(f)}
    assert uses == {"build", "program_pieces", "second_reading"}


def test_every_width_of_the_listed_file_is_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"Qwen3-Next-80B-A3B-Instruct"' in line)
    listed = load_json("configs", LISTED)
    assert listed["source"] == entry["source_url"]
    assert listed["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key
    assert (listed["num_hidden_layers"], listed["num_experts"],
            listed["vocab_size"]) == (8, 32, 151936 // 8)


# wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes most of them (a router as peaked as ``_weighty``'s sums
# to one over its two chosen, so "not renormalised" is not among them:
# ``tests/test_qwen3_next.py`` moves that term)
WRONG = {
    "another theta": dict(rope_theta=100.0),
    "the whole head turned": dict(partial_rotary_factor=1.0),
    "another eps": dict(rms_norm_eps=0.1),
    # the loss's second term, about 0.001 of it: ``loss_abs`` lies under it
    "no aux term": dict(assumed=dict(router_aux_loss_coef=0.0)),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    import jax

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


def test_a_gate_left_out_fails_its_piece(monkeypatch, capsys):
    """The shared expert's gate dropped from the program: the expert
    piece fails, the mixers' pieces hold."""
    from dlrover_tpu.models import moe

    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    real = moe._shared_expert
    monkeypatch.setattr(moe, "_shared_expert", lambda lp, y: real(
        {k: v for k, v in lp.items() if k != "w_s"}, y))
    assert not family._compare(
        fam.cfg, one_device_mesh(), params, tokens, config, want)
    out = capsys.readouterr().out
    assert re.search(r"expert_rel_median \S+ \(limit \S+, FAILED\)", out)
    for piece in ("gdn_rel_median", "gattn_rel_median", "gdn_grad_rel_p99",
                  "gattn_grad_rel_p99", "router_agree_min"):
        assert re.search(piece + r" \S+ \(limit \S+, ok\)", out), piece


def test_a_wrong_backward_of_the_passes_fails_the_mixers_vjp_alone(
        monkeypatch, capsys):
    """The chip's path in interpret mode with the output pass's gate
    slope a sigmoid's, not a SiLU's: every forward piece and the core's
    gradients hold, the whole mixer's vjp fails (``d w_qkvz`` holds z's
    columns)."""
    import functools

    import jax

    from dlrover_tpu.models import qwen3_next
    from dlrover_tpu.ops import kda

    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    monkeypatch.setattr(qwen3_next, "gdn_attention", functools.partial(
        qwen3_next.gdn_attention, interpret=True))
    mesh = one_device_mesh()
    assert family._compare(fam.cfg, mesh, params, tokens, config, want)
    assert "FAILED" not in capsys.readouterr().out
    jax.clear_caches()      # the passes are jitted: trace them again
    monkeypatch.setattr(kda, "_gate_slope",
                        lambda gate, sig, act: sig * (1.0 - sig))
    try:
        assert not family._compare(
            fam.cfg, mesh, params, tokens, config, want)
    finally:
        jax.clear_caches()
    out = capsys.readouterr().out
    assert re.search(r"gdn_vjp_rel_max \S+ \(limit \S+, FAILED\)", out)
    for piece in ("gdn_rel_median", "gdn_grad_rel_p99", "hidden_rel_median",
                  "loss_abs"):
        assert re.search(piece + r" \S+ \(limit \S+, ok\)", out), piece


def test_the_memory_metric_reads_the_compilers_plan_not_the_sum(monkeypatch):
    """``hbm_peak_gib`` reads the compiler's own peak + code (the
    chip's numbers of this cell's step), not ``memory_analysis()``'s sum,
    which counts 17.11 GiB on a chip of 15.75; a program without the
    gauge leaves the metric out."""
    from benchmarks.harness import program_spans

    spec = load_json("layer_metrics", "hbm_peak_gib.json")
    gauges = {"step.hbm_peak_bytes": 18375170560}
    monkeypatch.setattr(program_spans, "_program_table", lambda _: gauges)
    assert program_spans.gauge(spec, None) is None
    gauges["step.hbm_planned_peak_bytes"] = 15411112960 + 84305920
    assert round(program_spans.gauge(spec, None), 3) == 14.431


def test_rounding_is_seen_only_below_bfloat16():
    """``second_reading``'s sides at the tiny size: the reference rounded
    to float8 fails a limit, rounded to bfloat16 it passes all."""
    passed = family.second_reading(_ctx().config, seed=5, seq=64)
    assert (passed["float8_e4m3fn"], passed["bfloat16"]) == (False, True)


def test_correct_when_nothing_is_wrong():
    result = finetune_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}
    assert result["counters"]["live_rows"] > 0


def test_the_rehearsal_prints_a_well_formed_last_line():
    """``run.py`` on the rehearsal cell (which ``BENCHMARK.json`` does
    not list), traced, as the driver would call it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-cpu-qwen3-next-steady", "--seed", "2147483999", "--seconds",
         "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # a traced run's line carries the per-layer metrics; on the CPU no
    # flash kernel runs and no roofline has a peak to read
    for name in METRICS + SHARED:
        if not name.endswith("_roofline") and name != "q3n_gattn_flash_ms":
            assert math.isfinite(line["metrics"][name]["value"]), name
    assert line["device"]["busy_s"] > 0


def test_the_chunk_rules_count_against_a_hand_count():
    # one chunk of 4 rows, one key head, two value heads, dk 3, dv 5
    products = 2 * (4 * 5 // 2) * 2 * 3          # kk and qk, 10 pairs each
    solve = 4 * 4 * (5 + 3)                      # C^2 / 2 madds a column
    body = 3 * 2 * 4 * 3 * 5 + 4 * 4 * 5         # W_k S, Q S, K^T U; A_qk U
    assert flops.gdn_chunk_flops(
        chunk=4, key_heads=1, value_heads=2, dk=3, dv=5) == (
            products + 2 * (solve + body))
    assert flops.gdn_chunk_flops_per_step(
        tokens=32, layers=3, chunk=4, key_heads=1, value_heads=2, dk=3,
        dv=5) == 3 * 3 * 8 * (products + 2 * (solve + body))
    # q, k, v, o in bf16 and g, beta in float32, a token and layer
    operands = 2 * (2 * 1 * 3 + 2 * 5) + 4 * 2 * 2
    assert flops.gdn_chunk_bytes_per_step(
        tokens=32, layers=3, key_heads=1, value_heads=2, dk=3, dv=5) == (
            32 * 3 * (3 * operands + 2 * 2 * 2 * 5))


def test_flops_of_the_listed_configuration():
    config = load_json("configs", LISTED)
    # ISSUE 45's arithmetic, millions of parameters
    gdn = flops.gdn_matmul_params(config)
    assert gdn == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    gattn = flops.gattn_matmul_params(config)
    assert gattn == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert round(gdn / 1e6, 2) == 33.69 and round(gattn / 1e6, 2) == 27.26
    # the held 32 of 512 of the 10 chosen: five eighths of an expert
    expert = 2048 * 512 + 3 * 2048 * 512 + 2048 + 0.625 * 3 * 2048 * 512
    want = 6 * gdn + 2 * gattn + 8 * expert + 2048 * 18992
    assert flops.active_matmul_params(config) == pytest.approx(want)
    attn = 3.0 * 2 * 16 * 16384 * 512
    rule = flops.gdn_chunk_flops_per_step(
        tokens=1, layers=6, chunk=64, key_heads=16, value_heads=32, dk=128,
        dv=128)
    assert flops.flops_per_token(config, 16384) == pytest.approx(
        6.0 * want + attn + rule)
    # the step's count the roofline reads: 1.24 TFLOP, 6.5 GB
    assert 16384 * rule == pytest.approx(1.2382e12, rel=1e-3)


def test_param_count_is_the_issues_arithmetic():
    fam = family.build(load_json("configs", LISTED), one_device_mesh())
    assert fam.param_count == 1_173_540_992
    assert round(6 * fam.param_count / 1e9, 2) == 7.04


def test_the_listed_metrics_are_this_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    ours = [m for m in benchmark["per_layer"] if m["name"].startswith("q3n_")]
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
    for name in ("q3n_gdn_ms", "q3n_gdn_chunk_ms", "q3n_gdn_chunk_roofline",
                 "q3n_gattn_flash_roofline", "q3n_attn_proj_ms",
                 "moe_experts_ms", "q3n_moe_experts_roofline",
                 "moe_dispatch_ms", "embed_ms"):
        spec = load_json("layer_metrics", name + ".json")
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        module_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        assert module.read(spec, ctx) is None, name
