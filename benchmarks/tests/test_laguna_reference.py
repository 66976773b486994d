"""The laguna family's plain reference against the program at a tiny
size on the CPU; the comparisons that decide ``correct`` shown to fail
for each wrong program the limits are there to catch (a window off by
one, a dropped gate, the factor left off cos and sin among them); its
FLOPs against a hand count; its readers on a made-up trace."""

import copy
import dataclasses
import math
import time
import types

import pytest

from conftest import (
    BENCH, cell_metrics, load_json, made_up_v5e_ctx, one_device_mesh)

from benchmarks.families import laguna as family
from benchmarks.harness import laguna_flops
from benchmarks.jobs import finetune_loop

CELL = "laguna-xs2-ep8-1chip-steady"


def _ctx(cell_name="tiny-cpu-laguna-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _changed(config, path, value):
    out = copy.deepcopy(config)
    *groups, key = path.split("/")
    into = out
    for group in groups:
        into = into[group]
    into[key] = value
    return out


def _weighty(params):
    """At 64 wide and sigma 0.02 the scores are flat, the gate sits at a
    half and the 1e-4 output projections add next to nothing, so a wrong
    rotary, a dropped gate or a wrong feed-forward would not show: scores
    of order one, a gate that spreads, branches that weigh, norms apart
    (the published widths give the first two by themselves)."""
    import jax

    keys = iter(jax.random.split(jax.random.key(5), 64))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
        for name, by in (("router", 40.0), ("wq", 20.0), ("wk", 5.0),
                         ("w_g", 30.0), ("wo", 8e3), ("w_down", 2.4e4),
                         ("ws_down", 8e3)):
            if name in lp:
                lp[name] = lp[name] * by
        return lp

    return dict(params, **{
        group: {k: slab(v) for k, v in params[group].items()}
        for group in ("dense", "layers", "tail")})


def _built(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def _holds(cfg, params, tokens, config) -> bool:
    """The family's comparison of the program built as ``cfg`` against
    the reference of ``config``."""
    read, _ = family.compare(params, tokens, config, family._Program(
        cfg, one_device_mesh(), params, tokens))
    return family._report("program against reference", read)


def _failed(out: str):
    """The names of the limits a comparison's line says FAILED."""
    line = next(l for l in out.splitlines() if "program against" in l)
    return {part.split(":")[-1].split()[0] for part in line.split(";")
            if "FAILED" in part}


def test_the_rehearsal_and_the_cell_check_past_the_window_and_past_yarn():
    for cell in (_ctx().cell, load_json("workloads", CELL + ".json")):
        config = load_json("configs", cell["config"] + ".json")
        seq = cell["params"]["reference_seq"]
        full = config["rope_parameters"]["full_attention"]
        assert config["sliding_window"] < seq
        assert full["original_max_position_embeddings"] < seq


def test_reference_agrees_with_program_in_float32():
    import jax

    config = _ctx().config
    fam, params, tokens = _built(config)
    program = float(jax.jit(fam.loss_fn)(params, tokens))
    # both in float32 here, so they agree to rounding; on the chip the
    # program computes in bfloat16 and the job allows REFERENCE_TOLERANCE
    assert abs(program - family.reference_loss(params, tokens, config)) < 1e-5
    # the hook the job calls: every comparison holds, so it is the loss
    assert abs(fam.reference_loss(params, tokens) - program) < 1e-5


def test_the_references_blocks_do_not_change_it(monkeypatch):
    from benchmarks.families import smallthinker

    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(smallthinker, "Q_BLOCK", 16)
    monkeypatch.setattr(smallthinker, "CE_BLOCK", 32)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 1e-6


# the wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes every one of them
WRONG = {
    "a window off by one": ("sliding_window", 17),
    "a full mask where a window is": (
        "layer_types", ["full_attention"] * 8),
    "the factor left off cos and sin": (
        "rope_parameters/full_attention/attention_factor", 1.0),
    "plain rotary where yarn is": (
        "rope_parameters/full_attention/factor", 1),
    "the whole head turned on a full layer": (
        "rope_parameters/full_attention/partial_rotary_factor", 1.0),
    "another theta on the window layers": (
        "rope_parameters/sliding_attention/rope_theta", 5e5),
    "not scaled": ("moe_routed_scaling_factor", 1.0),
    "another eps": ("rms_norm_eps", 0.1),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    config = _ctx().config
    wrong = family.build(_changed(config, *WRONG[what]), one_device_mesh())
    _, params, tokens = _built(config)
    assert not _holds(wrong.cfg, params, tokens, config)
    assert "FAILED" in capsys.readouterr().out


def test_a_window_off_by_one_is_seen_by_the_window_pieces_alone(capsys):
    """One key more in the band: no full layer's piece and no loss sees
    it; the window layer's output past the window and its backward do."""
    config = _ctx().config
    wrong = family.build(
        _changed(config, "sliding_window", 17), one_device_mesh())
    _, params, tokens = _built(config)
    assert not _holds(wrong.cfg, params, tokens, config)
    failed = _failed(capsys.readouterr().out)
    assert {"window_attn_rel_median", "window_attn_grad_rel_p99"} <= failed
    assert not failed & {"full_attn_rel_median", "full_attn_grad_rel_p99",
                         "gate_rel_median", "dense_rel_median", "ce_abs"}


@pytest.mark.parametrize("what", ["a dropped gate", "a gate a layer",
                                  "a gated shared expert"])
def test_a_wrong_gate_fails_the_comparison(what, monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import laguna, moe

    config = _ctx().config
    fam, params, tokens = _built(config)
    if what == "a dropped gate":
        monkeypatch.setattr(
            laguna, "head_gate", lambda lp, y, dt: jnp.ones(
                y.shape[:2] + (lp["w_g"].shape[1],), dt))
    elif what == "a gate a layer":
        real = laguna.head_gate
        monkeypatch.setattr(
            laguna, "head_gate", lambda lp, y, dt: jnp.broadcast_to(
                real(lp, y, dt)[..., :1], y.shape[:2] + (lp["w_g"].shape[1],)))
    else:
        real_shared = moe._shared_expert
        monkeypatch.setattr(
            moe, "_shared_expert", lambda lp, y: real_shared(lp, y)
            * jax.nn.sigmoid(jnp.mean(y, -1, keepdims=True)))
    assert not _holds(fam.cfg, params, tokens, config)
    failed = _failed(capsys.readouterr().out)
    if what == "a gated shared expert":
        assert "shared_rel_median" in failed
    else:
        assert {"full_attn_rel_median", "window_attn_rel_median"} <= failed


def test_a_router_in_bfloat16_fails_its_piece_alone(monkeypatch, capsys):
    """The router's logits through bfloat16 where float32 is stated: the
    routers still agree on most pairs, but not on all when both read the
    same input."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def route(cfg, router, yt, bias=None):
        logits = (yt.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)
                  ).astype(jnp.float32)
        probs = jax.nn.sigmoid(logits)
        top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
        return probs, cfg.routed_scaling * top_p / top_p.sum(
            -1, keepdims=True), top_e

    monkeypatch.setattr(moe, "route", route)
    config = _ctx().config
    fam, params, tokens = _built(config, seq=64, batch=8)
    assert not _holds(fam.cfg, params, tokens, config)
    assert "router_same_input_min" in _failed(capsys.readouterr().out)


def test_rounding_is_seen_only_below_bfloat16():
    """``second_reading``'s two sides at the tiny size: the reference
    rounded to float8 fails a limit, rounded to bfloat16 it passes all."""
    passed = family.second_reading(_ctx().config, seed=5, seq=64)
    assert (passed["float8_e4m3fn"], passed["bfloat16"]) == (False, True)


def test_correct_when_nothing_is_wrong():
    result = finetune_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}
    assert result["counters"]["live_rows"] > 0


def test_expected_first_loss_of_the_listed_configuration():
    config = load_json("configs", "laguna-xs.2-ep8-1chip.json")
    # ln 12544 + 2048 x 0.02^2 / 2
    assert math.log(config["vocab_size"]) + config["hidden_size"] * (
        config["assumed"]["initializer_range"] ** 2) / 2 == pytest.approx(
            9.8466, abs=1e-4)


def test_the_cell_lists_its_own_metrics_and_has_the_shared_ones():
    listed = set(cell_metrics(CELL))
    assert {"lag_swa_flash_ms", "lag_swa_flash_roofline",
            "lag_full_flash_roofline", "lag_attn_proj_ms",
            "lag_attn_gate_ms", "lag_moe_experts_roofline"} <= listed
    # the readers without a list: the cell has them without an entry
    assert {"flash_attn_ms", "fused_ce_ms", "embed_ms", "hbm_peak_gib",
            "mfu", "layer_scan_ms", "step_drift_pct"} <= listed
    # the gate's scope alone, and with the projections: two readings
    assert load_json("layer_metrics", "lag_attn_gate_ms.json")[
        "scopes"] == ["attn_gate"]
    assert load_json("layer_metrics", "lag_attn_proj_ms.json")[
        "scopes"] == ["attn_proj", "attn_gate"]


def _read(name, ctx):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(load_json("layer_metrics", name + ".json"), ctx)


def test_the_rooflines_on_a_made_up_trace():
    """A step of 6 window and 2 full calls of each kernel, each taking
    exactly what the peak allows for the pairs under its mask: 100 %."""
    from benchmarks.harness import peaks

    peak = peaks.peaks_for("TPU v5 lite")
    call = laguna_flops.attention_flops_per_call
    swa = call(batch=1, n_heads=64, head_dim=128, pairs=8257792)
    full = call(batch=1, n_heads=48, head_dim=128, pairs=134225920)
    names = {"fwd": "attention_fwd", "dq": "attention_bwd_dq",
             "dkv": "attention_bwd_dkv"}
    calls = []
    for i in range(6):
        calls += [(f"{names[k]}_swa.{i}", swa[k] / peak["bf16_flops_per_s"])
                  for k in names]
    for i in range(2):
        calls += [(f"{names[k]}.{i}", 2 * full[k] / peak["bf16_flops_per_s"])
                  for k in names]
    rows, dim, ffn = 16384.0, 2048, 512
    from benchmarks.harness import moe_flops
    least = max(
        moe_flops.grouped_matmul_flops(rows, dim, ffn)
        / peak["bf16_flops_per_s"],
        moe_flops.grouped_matmul_bytes(rows, dim, ffn, 32)
        / peak["hbm_bytes_per_s"])
    calls += [(f"grouped_matmul.{i}", 4 * least) for i in range(12)]
    ctx = made_up_v5e_ctx(CELL, calls)
    ctx.counters = {"live_rows": rows}
    assert _read("lag_swa_flash_roofline", ctx) == pytest.approx(100.0)
    assert _read("lag_full_flash_roofline", ctx) == pytest.approx(50.0)
    assert _read("lag_moe_experts_roofline", ctx) == pytest.approx(25.0)
    assert _read("lag_swa_flash_ms", ctx) == pytest.approx(
        6e3 * sum(swa.values()) / peak["bf16_flops_per_s"])
    assert any("64 heads, 8257792 pairs" in line for line in ctx.logged)


def test_new_readers_report_nothing_without_their_kernels():
    """On a program that lacks the kernels and scopes (the parent's), for
    another family's cell and off the chip, the readers return None and
    do not raise."""
    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    names = ("lag_swa_flash_ms", "lag_swa_flash_roofline",
             "lag_full_flash_roofline", "lag_attn_proj_ms",
             "lag_attn_gate_ms", "lag_moe_experts_roofline")
    for name in names:
        assert _read(name, ctx) is None, name
    # a v5e whose trace has another family's kernels: still nothing
    other = made_up_v5e_ctx(
        "smallthinker-ep4-1chip-steady", [("attention_fwd_swa.1", 1e-3)])
    other.counters = {"live_rows": 100.0}
    for name in names:
        assert _read(name, other) is None, name
