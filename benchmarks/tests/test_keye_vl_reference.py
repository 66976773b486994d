"""The keye_vl family's plain reference against the program at a tiny size
on the CPU, as ``test_dots3_reference.py`` has it for ``dots3``; the
comparisons that decide ``correct`` shown to fail for each wrong program
the limits are there to catch; its FLOPs against a hand count; its
readers on a made-up trace."""

import importlib.util
import json
import os
import re
import time
import types

import pytest

from conftest import (BENCH, cell_metrics, load_json, made_up_v5e_ctx,
                      one_device_mesh)

from benchmarks.families import keye_vl as family
from benchmarks.harness import dots3_flops, keye_vl_flops
from benchmarks.jobs import finetune_loop

LISTED = "keye-vl-2.0-30b-a3b-ep8-1chip.json"
CELL = "keye-vl-ep8-1chip-steady"
#: the cell's own per-layer metrics, in BENCHMARK.json's order
READERS = ("kvl_dsa_index_roofline", "kvl_dsa_probs_roofline",
           "kvl_dsa_flash_roofline", "kvl_moe_experts_roofline")
#: shared readers that list this cell (or every cell) since PR 58: the
#: memory metric, which was a copy under this cell's name, and what the
#: full list of PR 54 had left to the traced run's log
SHARED = ("hbm_peak_gib", "dsa_index_ms", "dsa_select_ms", "dsa_flash_ms",
          "dsa_loss_ms", "attn_proj_ms", "moe_experts_ms", "moe_dispatch_ms",
          "embed_ms", "moe_live_rows", "live_rows_drift", "build_lower_s",
          "build_xla_s", "first_step_host_s", "step_dispatch_ms",
          "trainer_idle_ms")


def _ctx(cell_name="tiny-cpu-keye-vl-steady", seconds=0.5, seed=7):
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

    keys = iter(jax.random.split(jax.random.key(5), 64))
    lp = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "idx_k_norm",
                 "idx_k_bias"):
        lp[name] = lp[name] + 0.3 * jax.random.normal(
            next(keys), lp[name].shape)
    for name, by in (("router", 10.0), ("wq", 6.0), ("wo", 30.0),
                     ("w_down", 100.0), ("idx_wq", 10.0), ("idx_ww", 60.0)):
        lp[name] = lp[name] * by
    return dict(params, layers=lp)


def _built(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def test_the_selection_is_shorter_than_the_reference_check():
    for cell_name in ("tiny-cpu-keye-vl-steady", CELL):
        cell = load_json("workloads", cell_name + ".json")
        config = load_json("configs", cell["config"] + ".json")
        seq = cell["params"]["reference_seq"]
        assert config["sa_config"]["topk"] < seq == cell["params"]["seq"]
        rows = family.positions_for(config, 1, seq)
        assert (rows[0] != rows[2]).any()


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
    assert abs(family.reference_loss(params, tokens, config) - whole) < 2e-5


def test_every_width_of_the_listed_file_is_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"name": "Keye-VL-2.0-30B-A3B"' in line)
    listed = load_json("configs", LISTED)
    assert listed["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key
    assert set(listed["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}


# the wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes most of them
WRONG = {
    "a smaller selection": dict(sa_config=dict(topk=12)),
    "no selection": dict(sa_config=dict(topk=64)),
    "another theta": dict(rope_theta=100.0),
    "the sections in another order": dict(
        rope_scaling=dict(mrope_section=[4, 2, 2])),
    "not renormalised": dict(norm_topk_prob=False),
    "another eps": dict(rms_norm_eps=0.1),
}


def _failed(capsys) -> set:
    return set(re.findall(
        r"(\w+) [-\d.e+naif]+ \(limit [\d.e+-]+, FAILED\)",
        capsys.readouterr().out))


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    import math

    config = _ctx().config
    _, params, tokens = _built(config)
    changed = dict(config, **{
        key: dict(config[key], **value) if isinstance(value, dict) else value
        for key, value in WRONG[what].items()})
    wrong = family.build(changed, one_device_mesh())
    loss = family.compare(
        wrong.cfg, one_device_mesh(), params, tokens, config)
    assert math.isnan(loss)
    assert _failed(capsys)


def test_text_positions_fail_the_rotary_piece(capsys):
    """A program that turns every token by its index (the image spans
    taken for text) holds every piece a text holds, and fails where the
    three rows differ."""
    import math

    import jax.numpy as jnp

    config = _ctx().config
    fam, params, tokens = _built(config)
    real = family.program_fns

    def as_text(cfg, mesh, positions):
        b, s = positions.shape[1:]
        return real(cfg, mesh, jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (3, b, s)))

    family.program_fns = as_text
    try:
        loss = family.compare(
            fam.cfg, one_device_mesh(), params, tokens, config)
    finally:
        family.program_fns = real
    assert math.isnan(loss)
    assert "image_rope_rel_p99" in _failed(capsys)


def test_a_backward_over_another_selection_fails_the_gradient_pieces(capsys):
    """A program whose backward passes walk every causal key holds every
    forward piece, and fails the two that read gradients."""
    import math

    import jax.numpy as jnp

    config = _ctx().config
    fam, params, tokens = _built(config)
    real = family.program_fns

    def causal_backward(cfg, mesh, positions):
        whole, layer, grads = real(cfg, mesh, positions)
        return whole, layer, lambda *a: grads(
            *a[:-1], jnp.tril(jnp.ones_like(a[-1])))

    family.program_fns = causal_backward
    try:
        loss = family.compare(
            fam.cfg, one_device_mesh(), params, tokens, config)
    finally:
        family.program_fns = real
    assert math.isnan(loss)
    assert _failed(capsys) == {"sel_attn_grad_rel_p99", "index_grad_rel_p99"}


def test_out_proj_std_scales_the_closing_projections():
    import jax
    import numpy as np

    config = _ctx().config
    base = family.build(config, one_device_mesh()).init_params(
        jax.random.key(0))
    stated = dict(config, assumed=dict(config["assumed"], out_proj_std=1e-4))
    scaled = family.build(stated, one_device_mesh()).init_params(
        jax.random.key(0))
    for name, w in base["layers"].items():
        by = 1e-4 / 0.02 if name in ("wo", "w_down") else 1.0
        np.testing.assert_allclose(scaled["layers"][name], w * by, rtol=1e-6)


def test_rounding_is_seen_only_below_bfloat16():
    """``second_reading``'s sides at the tiny size: the reference rounded
    to float8 fails a limit, rounded to bfloat16 it passes all; the
    indexer's sums kept in bfloat16 fail the comparison on the side's own
    operands (and no other: bf16 operands read the same against the
    float32 reference)."""
    passed = family.second_reading(_ctx().config, seed=5, seq=64)
    assert (passed["float8_e4m3fn"], passed["bfloat16"]) == (False, True)
    assert not passed["scores_accumulated_in_bfloat16"]
    assert set(passed) == {
        "float8_e4m3fn", "bfloat16", "scores_accumulated_in_bfloat16",
        "angles_in_bfloat16"}


def test_correct_when_nothing_is_wrong():
    result = finetune_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}
    assert result["counters"]["live_rows"] > 0


def test_flops_of_the_listed_configuration():
    config = load_json("configs", LISTED)
    # ISSUE 54's arithmetic, millions of parameters
    attention = keye_vl_flops.attention_matmul_params(config)
    indexer = keye_vl_flops.indexer_matmul_params(config)
    assert attention == 2 * 2048 * 4096 + 2 * 2048 * 512
    assert indexer == 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert round(attention / 1e6, 3) == 18.874
    # the held 16 of 128 of the 8 chosen: one expert a token
    expert = 2048 * 128 + 1.0 * 3 * 2048 * 768
    assert keye_vl_flops.expert_matmul_params(config) == pytest.approx(expert)
    # pairs the definition attends over, a head
    assert dots3_flops.causal_pairs(16384) == 134_225_920
    pairs = dots3_flops.selected_pairs(16384, 2048)
    assert pairs == 31_458_304
    assert dots3_flops.selected_pairs(12288, 2048) == 23_069_696
    sel = dots3_flops.attention_flops_per_call(
        batch=1, n_heads=32, qk_dim=128, v_dim=128, pairs=pairs)
    index = dots3_flops.index_flops_per_call(
        batch=1, seq=16384, heads=16, dim=64)
    probs = dots3_flops.probs_flops_per_call(
        batch=1, n_heads=32, qk_dim=128, pairs=pairs)
    # the two kernels that run: the forward one product, the one backward
    # kernel (PR 57) the scores again, dQ and dK
    assert index == {"fwd": 2 * 16 * 134_225_920 * 64,
                     "bwd": 3 * 2 * 16 * 134_225_920 * 64}
    layer = (6.0 * (attention + expert) + 4.0 * indexer
             + (3 * sel["fwd"] + 3 * index["fwd"] + probs) / 16384)
    assert keye_vl_flops.flops_per_token(config, 16384) == pytest.approx(
        4 * layer + 6.0 * 2048 * 18992)
    # the least bytes: the float32 array's causal half leads
    moved = keye_vl_flops.index_bytes_per_call(
        batch=1, seq=16384, heads=16, dim=64)
    rows = 16384 * (2 * 1024 + 128 + 64)    # q, k, w; dq, dk, dw as large
    assert moved == {"fwd": 4 * 134_225_920 + rows,
                     "bwd": 4 * 134_225_920 + 2 * rows}
    assert keye_vl_flops.probs_bytes_per_call(
        batch=1, seq=16384, heads=32, kv_heads=4, dim=128) == (
            5 * 134_225_920 + 16384 * (2 * 128 * 36 + 128))


def test_the_index_roofline_reads_the_forward_and_the_one_backward():
    """``kvl_dsa_index_roofline`` on a made-up trace of the listed cell
    on a v5e: a forward and a backward call, each at exactly four times
    its least time (FLOPs bind both at 16 x 64), read 25 %; the pair
    PR 57 replaced is not matched."""
    flops = dots3_flops.index_flops_per_call(
        batch=1, seq=16384, heads=16, dim=64)
    moved = keye_vl_flops.index_bytes_per_call(
        batch=1, seq=16384, heads=16, dim=64)
    least = {k: max(flops[k] / 197e12, moved[k] / 819e9) for k in flops}
    assert all(least[k] == flops[k] / 197e12 for k in flops)
    ctx = made_up_v5e_ctx(CELL, [
        ("dsa_index_fwd.1", 4 * least["fwd"]),
        ("dsa_index_bwd", 4 * least["bwd"]),
        ("dsa_index_bwd_dk.4", 1.0), ("fusion.2", 0.5)])
    assert keye_vl_flops.read_index_roofline({}, ctx) == pytest.approx(25.0)
    assert sum("25.00 % of the bf16 peak" in line
               for line in ctx.logged) == 2


def test_the_listed_metrics_are_this_cells_alone():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    ours = [m for m in benchmark["per_layer"] if m["name"].startswith("kvl_")]
    assert [m["name"] for m in ours] == list(READERS)
    at = benchmark["per_layer"].index(ours[0])                  # one block
    assert benchmark["per_layer"][at:at + len(ours)] == ours
    assert set(SHARED) <= set(cell_metrics(CELL))
    listed, = [w for w in benchmark["workloads"] if w["name"] == CELL]
    held, = [c for c in benchmark["configs"]
             if c["file"] == "benchmarks/configs/" + LISTED]
    assert listed["config"] == held["name"]
    for m in ours:
        assert m["workloads"] == [CELL], m["name"]
        spec = load_json("layer_metrics", m["name"] + ".json")
        assert (spec["unit"], spec["better"], spec["source"], spec["layer"],
                spec["moves"]) == (m["unit"], m["better"], m["source"],
                                   m["layer"], m["moves"])
    # no file of a metric that the list has no room for
    assert sorted(
        f[:-len(".json")] for f in os.listdir(
            os.path.join(BENCH, "layer_metrics"))
        if f.startswith("kvl_") and f.endswith(".json")) == sorted(READERS)


def test_new_readers_report_nothing_without_their_kernels():
    """On a program that lacks the kernels and scopes (the parent's), and
    off the chip, the trace's readers return None and do not raise."""
    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    for name in READERS:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(
            load_json("layer_metrics", name + ".json"), ctx) is None, name
