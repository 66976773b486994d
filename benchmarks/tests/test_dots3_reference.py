"""The dots3 family's plain reference against the program at a tiny size
on the CPU, as ``test_smallthinker_reference.py`` has it for
``smallthinker``; the comparisons that decide ``correct`` shown to fail
for each wrong program the limits are there to catch; its FLOPs against
a hand count; its readers on a made-up trace."""

import math
import time
import types

import pytest

from conftest import (BENCH, cell_metrics, load_json, made_up_v5e_ctx,
                      one_device_mesh)

from benchmarks.families import dots3 as family
from benchmarks.harness import dots3_flops
from benchmarks.jobs import finetune_loop

LISTED = "dots3-note-prev-ep32-1chip.json"


def _ctx(cell_name="tiny-cpu-dots3-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _weighty(params):
    """At 64 wide and sigma 0.02 every score is flat and attention adds
    next to nothing: scores of order one, branches that weigh, norms
    apart (the published widths give the first two by themselves)."""
    import jax

    keys = iter(jax.random.split(jax.random.key(5), 256))

    def block(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm",
                     "idx_k_norm", "idx_k_bias"):
            if name in lp:
                lp[name] = lp[name] + 0.3 * jax.random.normal(
                    next(keys), lp[name].shape)
        for name, by in (("router", 40.0), ("w_qb", 6.0), ("w_o", 30.0),
                         ("w_g", 30.0), ("w_down", 100.0), ("ws_down", 30.0),
                         ("idx_wq", 10.0), ("idx_ww", 60.0)):
            if name in lp:
                lp[name] = lp[name] * by
        return lp

    return dict(params, **{
        group: {k: block(v) for k, v in params[group].items()}
        for group in ("dense", "layers", "tail")})


def _built(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def test_selection_and_window_are_shorter_than_the_reference_check():
    for cell_name in ("tiny-cpu-dots3-steady", "dots3-ep32-1chip-steady"):
        cell = load_json("workloads", cell_name + ".json")
        config = load_json("configs", cell["config"] + ".json")
        assert config["index_topk"] < cell["params"]["reference_seq"]
        assert config["sliding_window_size"] < cell["params"]["reference_seq"]


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


def test_the_references_blocks_do_not_change_it(monkeypatch):
    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(family, "Q_BLOCK", 16)
    monkeypatch.setattr(family, "I_BLOCK", 8)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 2e-5


def test_every_width_of_the_listed_file_is_the_catalogs():
    import json
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"dots3-note-prev"' in line)
    listed = load_json("configs", LISTED)
    assert listed["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key
    assert listed["layer_types"] == entry["config"]["layer_types"][:5]


# the wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes most of them
WRONG = {
    "a smaller selection": dict(index_topk=12),
    "no selection": dict(index_topk=64),
    "another window": dict(sliding_window_size=12),
    "a window layer where a full one is": dict(
        layer_types=["full_attention"] + ["sliding_attention"] * 4),
    "no rescale": dict(apply_mla_qkv_lora_rescale=False),
    "another theta": dict(rope_theta=100.0),
    "not renormalised": dict(norm_topk_prob=False),
    "another eps": dict(rms_norm_eps=0.1),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    config = _ctx().config
    _, params, tokens = _built(config)
    changed = dict(config, **WRONG[what])
    if "layer_types" in WRONG[what]:
        # the same tree cannot hold another layout: the reference is held
        # to the wrong layout instead, the program to the right one
        config, changed = changed, config
        _, params, tokens = _built(changed)
        pytest.skip("another layout is another tree: covered in tests/")
    wrong = family.build(changed, one_device_mesh())
    want = family.reference_pieces(params, tokens, config)
    ok = family._compare(
        wrong.cfg, one_device_mesh(), params, tokens, config, want)
    assert not ok
    assert "FAILED" in capsys.readouterr().out
    assert math.isfinite(want["ce"]) and math.isfinite(want["l_i"])


def _pieces(monkeypatch, capsys, patch):
    """The names of the comparisons that fail with ``patch`` applied to
    the program."""
    import re

    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    patch(monkeypatch)
    family._compare(fam.cfg, one_device_mesh(), params, tokens, config, want)
    out = capsys.readouterr().out
    return set(re.findall(r"(\w+) [-\d.e+]+ \(limit [\d.e+-]+, FAILED\)", out))


def test_a_gate_left_out_fails_the_attention_piece(monkeypatch, capsys):
    from dlrover_tpu.models import dots3

    def patch(mp):
        real = dots3.latent_attention

        def no_gate(shape, mesh, positions, inv_freq, lp, y, **kw):
            return real(shape, mesh, positions, inv_freq,
                        {k: v for k, v in lp.items() if k != "w_g"}, y, **kw)

        mp.setattr(dots3, "latent_attention", no_gate)
        mp.setattr("dlrover_tpu.models.xing4.latent_attention", no_gate)

    # (a window layer's one held head saturates its gate at 1 on half
    # the tokens at this size, so its median does not show it)
    failed = _pieces(monkeypatch, capsys, patch)
    assert "sel_attn_rel_median" in failed and "gate_abs_max" not in failed


def test_a_wrong_selection_backward_fails_its_piece_alone(
        monkeypatch, capsys):
    """dk of the selection kernels' call scaled: the forward pieces all
    hold, the backward's piece does not."""
    from dlrover_tpu.ops import attention

    def patch(mp):
        real = attention._flash_select_bwd

        def scaled(*a):
            dq, dk, dv, none = real(*a)
            return dq, dk * 1.1, dv, none

        mp.setattr(attention.flash_attention_select_with_lse, "bwd", scaled,
                   raising=False)
        attention.flash_attention_select_with_lse.defvjp(
            attention._flash_select_fwd, scaled)

    try:
        failed = _pieces(monkeypatch, capsys, patch)
    finally:
        from dlrover_tpu.ops import attention as att
        att.flash_attention_select_with_lse.defvjp(
            att._flash_select_fwd, att._flash_select_bwd)
    assert failed == {"sel_attn_grad_rel_p99"}


def test_out_proj_std_scales_the_closing_projections():
    import jax
    import numpy as np

    config = _ctx().config
    base = family.build(config, one_device_mesh()).init_params(
        jax.random.key(0))
    stated = dict(config, assumed=dict(config["assumed"], out_proj_std=1e-4))
    scaled = family.build(stated, one_device_mesh()).init_params(
        jax.random.key(0))
    for group in ("dense", "layers"):
        for pos, lp in base[group].items():
            for name, w in lp.items():
                by = 1e-4 / 0.02 if name in (
                    "w_o", "w_down", "ws_down") else 1.0
                np.testing.assert_allclose(
                    scaled[group][pos][name], w * by, rtol=1e-6)


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


def test_flops_of_the_listed_configuration():
    config = load_json("configs", LISTED)
    # ISSUE 40's arithmetic, millions of parameters, at a quarter of the
    # heads: a full layer's attention 49.2 with its gate and indexer, a
    # window layer's 30.8
    full = dots3_flops.layer_matmul_params(config, "F")
    window = dots3_flops.layer_matmul_params(config, "S")
    assert full == (5120 * 1024 + 1024 * 32 * 192 + 5120 * 576
                    + 512 * 32 * 256 + 32 * 128 * 5120 + 5120 * 32
                    + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    assert round(full / 1e6, 1) == 49.2 and round(window / 1e6, 1) == 30.8
    # the held 8 of 256 of the 8 chosen: a quarter of an expert a token
    expert = 5120 * 256 + (1 + 0.25) * 3 * 5120 * 1536
    want = (2 * full + 3 * window + 3 * 5120 * 13824 + 4 * expert
            + 5120 * 19008)
    assert dots3_flops.active_matmul_params(config) == pytest.approx(want)
    # pairs the definition attends over, a head
    assert dots3_flops.causal_pairs(8192) == 33_558_528
    assert dots3_flops.selected_pairs(8192, 2048) == 14_681_088
    assert dots3_flops.selected_pairs(8192, 513) == 4_071_168
    assert dots3_flops.selected_pairs(8, 3) == 1 + 2 + 3 * 6
    assert dots3_flops.selected_pairs(100, 2048) == 5050
    sel = dots3_flops.attention_flops_per_call(
        batch=1, n_heads=32, qk_dim=192, v_dim=128, pairs=14_681_088)
    assert sel["fwd"] == 2 * 32 * 14_681_088 * 320
    assert sel["dq"] == 2 * 32 * 14_681_088 * 512
    assert sel["dkv"] == 2 * 32 * 14_681_088 * 640
    index = dots3_flops.index_flops_per_call(
        batch=1, seq=8192, heads=64, dim=128)
    # the two kernels that run: the forward one product, the one backward
    # kernel (PR 57) the scores again, dQ and dK
    assert index == {"fwd": 2 * 64 * 33_558_528 * 128,
                     "bwd": 3 * 2 * 64 * 33_558_528 * 128}
    assert dots3_flops._suffixed(dots3_flops._INDEX, "") == {
        "fwd": r"^dsa_index_fwd(\.\d+)?$", "bwd": r"^dsa_index_bwd(\.\d+)?$"}
    per_token = dots3_flops.flops_per_token(config, 8192)
    swa = dots3_flops.attention_flops_per_call(
        batch=1, n_heads=16, qk_dim=256, v_dim=128, pairs=4_071_168)
    probs = dots3_flops.probs_flops_per_call(
        batch=1, n_heads=32, qk_dim=192, pairs=14_681_088)
    attention = (3 * (2 * sel["fwd"] + 3 * swa["fwd"])
                 + 2 * (3 * index["fwd"] + probs)) / 8192
    assert per_token == pytest.approx(6.0 * want + attention)


def test_kernel_patterns_tell_the_kinds_apart():
    import re

    names = ["attention_fwd.3", "attention_fwd_swa.4", "attention_fwd_sel.2",
             "attention_bwd_dq_sel", "attention_bwd_dq_swa.12",
             "attention_bwd_dkv_sel.7", "attention_bwd_dkv_swa", "fusion.9",
             "attention_fwd_sel_x.1", "dsa_index_fwd.1", "dsa_probs.2"]

    def hits(kind):
        return [n for n in names if any(
            re.search(p, n)
            for p in dots3_flops.flash_patterns(kind).values())]

    assert hits("F") == ["attention_fwd_sel.2", "attention_bwd_dq_sel",
                         "attention_bwd_dkv_sel.7"]
    assert hits("S") == ["attention_fwd_swa.4", "attention_bwd_dq_swa.12",
                         "attention_bwd_dkv_swa"]
    sel = load_json("layer_metrics", "dsa_flash_ms.json")["patterns"]
    swa = load_json("layer_metrics", "swa_flash_ms.json")["patterns"]
    assert [n for n in names if any(re.search(p, n) for p in sel)] == [
        n for n in names if "_sel" in n]
    assert [n for n in names if any(re.search(p, n) for p in swa)] == [
        n for n in names if "_swa" in n]
    # the index kernels by name: the forward and the one backward, and no
    # longer the pair it replaced
    index = dots3_flops._suffixed(dots3_flops._INDEX, "")
    ran = ["dsa_index_fwd.1", "dsa_index_bwd", "dsa_index_bwd.3",
           "dsa_index_bwd_dq.2", "dsa_index_bwd_dk"]
    assert [n for n in ran if any(
        re.search(p, n) for p in index.values())] == ran[:3]


def test_the_index_roofline_reads_the_forward_and_the_one_backward():
    """``d3_dsa_index_roofline`` on a made-up trace of the listed cell on
    a v5e: two forward calls and one backward at exactly twice their
    least time read 50 %; the pair PR 57 replaced is not matched, so its
    seconds neither count nor dilute."""
    unit_s = 2.0 * 64 * 33_558_528 * 128 / 197e12
    ctx = made_up_v5e_ctx("dots3-ep32-1chip-steady", [
        ("dsa_index_fwd.1", 2 * unit_s), ("dsa_index_fwd.7", 2 * unit_s),
        ("dsa_index_bwd.3", 6 * unit_s), ("dsa_index_bwd_dq.4", 9 * unit_s),
        ("fusion.2", unit_s)])
    assert dots3_flops.read_index_roofline({}, ctx) == pytest.approx(50.0)
    assert len(ctx.logged) == 2 and all(
        "50.00 %" in line for line in ctx.logged)
    # the forward alone, as a program without the backward kernel has it
    ctx.trace.devices["d0"] = ctx.trace.devices["d0"][:2]
    assert dots3_flops.read_index_roofline({}, ctx) == pytest.approx(50.0)


def test_the_listed_metrics_are_this_cells_alone():
    import json
    import os

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    ours = [m for m in benchmark["per_layer"] if m["name"].startswith("d3_")]
    assert [m["name"] for m in ours] == [
        "d3_dsa_index_roofline", "d3_dsa_flash_roofline",
        "d3_swa_flash_roofline"]
    # shared readers that list this cell (or every cell) since PR 58, where
    # they were copies under names of this cell's; the selection's four
    # are keye-vl's too
    assert {"dsa_index_ms", "dsa_select_ms", "dsa_flash_ms", "dsa_loss_ms",
            "swa_flash_ms", "mla_proj_ms", "dense_mlp_ms", "moe_share_ms",
            "moe_experts_ms", "moe_dispatch_ms", "embed_ms", "hbm_peak_gib",
            "moe_live_rows", "live_rows_drift"} <= set(
                cell_metrics("dots3-ep32-1chip-steady"))
    for m in ours:
        assert m["workloads"] == ["dots3-ep32-1chip-steady"], m["name"]
        spec = load_json("layer_metrics", m["name"] + ".json")
        assert (spec["unit"], spec["better"], spec["source"], spec["layer"],
                spec["moves"]) == (m["unit"], m["better"], m["source"],
                                   m["layer"], m["moves"])


def test_new_readers_report_nothing_without_their_kernels():
    """On a program that lacks the kernels and scopes (the parent's), and
    off the chip, the readers return None and do not raise."""
    import importlib.util
    import os

    from benchmarks.harness import readers

    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    for name in ("d3_dsa_flash_roofline", "d3_swa_flash_roofline",
                 "d3_dsa_index_roofline", "dsa_index_ms",
                 "dsa_select_ms", "dsa_loss_ms", "mla_proj_ms",
                 "moe_share_ms"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(
            load_json("layer_metrics", name + ".json"), ctx) is None
    for name in ("dsa_flash_ms", "swa_flash_ms"):
        assert readers.trace_ms_per_step(
            load_json("layer_metrics", name + ".json"), ctx) is None
