"""The smallthinker family's plain reference against the program at a tiny
size on the CPU, as ``test_kimi_linear_reference.py`` has it for
``kimi_linear``; the comparisons that decide ``correct`` shown to fail
for each wrong program the limits are there to catch; its FLOPs against
a hand count; its readers on a made-up trace."""

import dataclasses
import math
import time
import types

import pytest

from conftest import BENCH, cell_metrics, load_json, one_device_mesh

from benchmarks.families import smallthinker as family
from benchmarks.harness import smallthinker_flops
from benchmarks.jobs import finetune_loop


def _ctx(cell_name="tiny-cpu-smallthinker-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _weighty(params):
    """At 64 wide and sigma 0.02 the scores are flat, attention adds
    next to nothing and the two norms of a layer are the same ones, so a
    wrong rotary or a router on the other norm's output would not show:
    scores of order one, an attention output that weighs, norms apart
    (the published widths give the first two by themselves)."""
    import jax

    keys = iter(jax.random.split(jax.random.key(5), 16))

    def slab(lp):
        lp = dict(lp, wq=lp["wq"] * 20.0, wk=lp["wk"] * 5.0,
                  wo=lp["wo"] * 40.0, router=lp["router"] * 40.0,
                  w_down=lp["w_down"] * 120.0)
        for name in ("attn_norm", "mlp_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
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


def test_the_rehearsal_window_is_shorter_than_its_reference_check():
    ctx = _ctx()
    assert (ctx.config["sliding_window_size"]
            < ctx.cell["params"]["reference_seq"])
    listed = load_json("workloads", "smallthinker-ep4-1chip-steady.json")
    config = load_json("configs", listed["config"] + ".json")
    assert config["sliding_window_size"] < listed["params"]["reference_seq"]


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


def test_the_references_attention_blocks_do_not_change_it(monkeypatch):
    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(family, "Q_BLOCK", 16)
    monkeypatch.setattr(family, "CE_BLOCK", 32)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 1e-6


# the wrong programs the limits are there to catch, each held to the
# reference of the configuration as it stands; at random init the loss
# alone passes every one of them
WRONG = {
    "a full mask where a window is": dict(
        sliding_window_layout=[0] * 8),
    "rotary on a NoPE layer": dict(rope_layout=[1] * 8),
    "another window": dict(sliding_window_size=24),
    "not renormalised": dict(norm_topk_prob=False),
    "another eps": dict(rms_norm_eps=0.1),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    import jax

    config = _ctx().config
    wrong = family.build(dict(config, **WRONG[what]), one_device_mesh())
    _, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    ok = family._compare(
        wrong.cfg, one_device_mesh(), params, tokens, config, want)
    assert not ok
    assert "FAILED" in capsys.readouterr().out
    assert math.isfinite(want["ce"])


@pytest.mark.parametrize("what", ["silu for relu", "routing on u"])
def test_a_wrong_expert_layer_fails_the_comparison(what, monkeypatch, capsys):
    """SiLU where ReLU is, and a router that reads the feed-forward's
    input as the other families' does: the expert output, and the
    routers' agreement, say so."""
    from dlrover_tpu.models import moe, smallthinker

    config = _ctx().config
    fam, params, tokens = _built(config)
    if what == "silu for relu":
        real = smallthinker.SmallThinkerConfig.as_moe
        monkeypatch.setattr(
            smallthinker.SmallThinkerConfig, "as_moe",
            lambda self: dataclasses.replace(real(self), expert_act="silu"))
    else:
        real = moe.moe_mlp
        monkeypatch.setattr(
            moe, "moe_mlp",
            lambda cfg, lp, y, mesh=None, route_on=None: real(
                cfg, lp, y, mesh))
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        fam.cfg, one_device_mesh(), params, tokens, config, want)
    out = capsys.readouterr().out
    assert "expert_rel_median" in out and "FAILED" in out


def _failed(out: str):
    """The names of the limits a comparison's line says FAILED."""
    line = next(l for l in out.splitlines() if "program against" in l)
    return {part.split(":")[-1].split()[0] for part in line.split(";")
            if "FAILED" in part}


def test_a_wrong_attention_backward_fails_its_piece_alone(
        monkeypatch, capsys):
    """The forward as it should be, the backward under a band eight keys
    too wide (a band walk that reads one block too many): no forward
    piece and no loss sees it; (g) does, for the window layer alone."""
    import jax

    from dlrover_tpu.ops import attention

    real = attention.flash_attention

    def wrong(q, k, v, causal=True, mesh=None, window=None):
        @jax.custom_vjp
        def f(q, k, v):
            return real(q, k, v, causal=causal, mesh=mesh, window=window)

        def bwd(res, g):
            wider = None if window is None else window + 8
            return jax.vjp(lambda *a: real(
                *a, causal=causal, mesh=mesh, window=wider), *res)[1](g)

        f.defvjp(lambda q, k, v: (f(q, k, v), (q, k, v)), bwd)
        return f(q, k, v)

    monkeypatch.setattr(attention, "flash_attention", wrong)
    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        fam.cfg, one_device_mesh(), params, tokens, config, want)
    assert _failed(capsys.readouterr().out) == {"window_attn_grad_rel_p99"}


def test_a_router_in_bfloat16_fails_its_piece_alone(monkeypatch, capsys):
    """The router's logits through bfloat16 where float32 is stated: the
    routers still agree on most pairs ((e) passes), but not on all when
    both read the same input ((h))."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import moe

    def route(cfg, router, yt, bias=None):
        logits = (yt.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)
                  ).astype(jnp.float32)
        top_z, top_e = jax.lax.top_k(logits, cfg.experts_per_token)
        return (jax.nn.softmax(logits, -1), jax.nn.softmax(top_z, -1), top_e)

    monkeypatch.setattr(moe, "route", route)
    config = _ctx().config
    fam, params, tokens = _built(config, seq=64, batch=8)
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        fam.cfg, one_device_mesh(), params, tokens, config, want)
    assert "router_same_input_min" in _failed(capsys.readouterr().out)


def test_out_proj_std_scales_the_two_closing_projections():
    """``assumed.out_proj_std``: wo and w_down at that sigma, every other
    leaf the program's init; without the key the program's init whole."""
    import jax
    import numpy as np

    config = _ctx().config
    small = dict(config, assumed=dict(config["assumed"], out_proj_std=1e-4))
    key = jax.random.key(3)
    plain = family.build(config, one_device_mesh()).init_params(key)
    scaled = family.build(small, one_device_mesh()).init_params(key)
    for pos, lp in plain["layers"].items():
        for name, w in lp.items():
            got = np.asarray(scaled["layers"][pos][name], np.float32)
            if name in ("wo", "w_down"):
                assert abs(got.std() / 1e-4 - 1) < 0.05
                assert np.corrcoef(got.ravel(), np.asarray(w).ravel())[0, 1] > 0.999
            else:
                assert np.array_equal(got, np.asarray(w, np.float32))
    assert np.array_equal(np.asarray(scaled["embed"]), np.asarray(plain["embed"]))


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


def test_flops_of_the_listed_configuration():
    config = load_json("configs", "smallthinker-21b-a3b-ep4-1chip.json")
    sizes = family._sizes(config)
    assert sizes["window_layout"] == (0, 1, 1, 1) * 2 == sizes["rope_layout"]
    # ISSUE 37's arithmetic: q and o 9.175 M each, k and v 1.311, router
    # 0.164, an expert 5.898; the held 16 of 64 of the 6 chosen: 1.5 a token
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attn == 20_971_520 and 3 * 2560 * 768 == 5_898_240
    layer = attn + 2560 * 64 + 1.5 * 3 * 2560 * 768
    want = 8 * layer + 2560 * 37984
    assert smallthinker_flops.active_matmul_params(**sizes) == pytest.approx(
        want)
    # pairs under the mask itself, a head
    assert smallthinker_flops.band_pairs(16384) == 134_225_920
    assert smallthinker_flops.band_pairs(16384, 4096) == 58_722_304
    assert smallthinker_flops.band_pairs(4096, 4096) == 4096 * 4097 // 2
    assert smallthinker_flops.band_pairs(8, 3) == 1 + 2 + 3 * 6
    # 14336 FLOPs a pair over the 28 heads, forward: 1.92 and 0.84 TFLOP
    full = smallthinker_flops.attention_flops_per_call(
        batch=1, n_heads=28, head_dim=128, pairs=134_225_920)
    window = smallthinker_flops.attention_flops_per_call(
        batch=1, n_heads=28, head_dim=128, pairs=58_722_304)
    assert full["fwd"] == 14336 * 134_225_920
    assert window["fwd"] == pytest.approx(0.8418e12, rel=1e-3)
    assert full["dq"] == 1.5 * full["fwd"] and full["dkv"] == 2 * full["fwd"]
    per_token = smallthinker_flops.flops_per_token(seq=16384, **sizes)
    attention = 3 * (2 * full["fwd"] + 6 * window["fwd"]) / 16384
    assert per_token == pytest.approx(6.0 * want + attention)


def test_kernel_patterns_tell_the_two_kinds_apart():
    import re

    names = ["attention_fwd.3", "attention_fwd_swa.4", "attention_bwd_dq",
             "attention_bwd_dq_swa.12", "attention_bwd_dkv.7",
             "attention_bwd_dkv_swa", "fusion.9", "attention_fwd_swa_x.1"]

    def hits(window):
        return [n for n in names if any(
            re.search(p, n) for p in
            smallthinker_flops.kernel_patterns(window).values())]

    assert hits(True) == ["attention_fwd_swa.4", "attention_bwd_dq_swa.12",
                          "attention_bwd_dkv_swa"]
    assert hits(False) == ["attention_fwd.3", "attention_bwd_dq",
                           "attention_bwd_dkv.7"]
    # swa_flash_ms reads the window kernels, flash_attn_ms both kinds
    swa = load_json("layer_metrics", "swa_flash_ms.json")["patterns"]
    both = load_json("layer_metrics", "flash_attn_ms.json")["patterns"]
    assert [n for n in names if any(re.search(p, n) for p in swa)] == [
        n for n in names if "_swa" in n]
    assert sum(any(re.search(p, n) for p in both) for n in names) == 7
    # the scopes of st_moe_share_ms are moe_share_ms's less moe_shared
    ours = load_json("layer_metrics", "st_moe_share_ms.json")
    theirs = load_json("layer_metrics", "moe_share_ms.json")
    assert ours["scopes"] == [s for s in theirs["scopes"] if s != "moe_shared"]
    assert ours["patterns"] == theirs["patterns"]
    # attn_proj_ms and the other shared readers list this cell since PR 58
    # (st_attn_proj_ms and the like were copies of them)
    assert {"attn_proj_ms", "swa_flash_ms", "moe_experts_ms",
            "moe_dispatch_ms", "embed_ms", "hbm_peak_gib", "moe_live_rows",
            "live_rows_drift"} <= set(
                cell_metrics("smallthinker-ep4-1chip-steady"))


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
    for name in ("swa_flash_roofline", "full_flash_roofline",
                 "st_moe_share_ms"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(
            load_json("layer_metrics", name + ".json"), ctx) is None
    assert readers.trace_ms_per_step(
        load_json("layer_metrics", "swa_flash_ms.json"), ctx) is None
