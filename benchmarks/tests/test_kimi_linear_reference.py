"""The kimi_linear family's plain reference against the program at a tiny
size on the CPU, as ``test_xing4_reference.py`` has it for ``xing4``; the
comparisons that decide ``correct`` shown to fail where a term is
dropped; its FLOPs against a hand count; its readers on a recorded scope
table."""

import math
import time
import types

import pytest

from conftest import BENCH, cell_metrics, load_json, one_device_mesh

from benchmarks.families import kimi_linear as family
from benchmarks.harness import hlo_scopes, kimi_linear_flops
from benchmarks.jobs import train_loop


def _ctx(cell_name="tiny-cpu-kimi-linear-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _built(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = fam.init_params(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


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


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 1), ("moe_renormalize", False),
    ("rms_norm_eps", 0.1),
])
def test_a_dropped_term_fails_the_comparison(key, value, capsys):
    """The program built with a term changed, held to the reference of
    the configuration as it stands: the hook says no (the loss alone, at
    random init, would pass: the CE stays ln V + d sigma^2 / 2)."""
    import jax

    config = _ctx().config
    wrong = family.build(dict(config, **{key: value}), one_device_mesh())
    params = wrong.init_params(jax.random.key(3))
    _, _, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    ok = family._compare(wrong.cfg, one_device_mesh(), params, tokens, want)
    assert not ok
    assert "FAILED" in capsys.readouterr().out
    assert math.isfinite(want["ce"])


def test_a_moved_layer_fails_the_comparison():
    """Held to a residual that read the latent layer third and not
    fourth (the same leaves in another order), the program fails (a)."""
    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    got = family.program_pieces(fam.cfg, one_device_mesh(), params, tokens)
    n = fam.cfg.n_experts
    assert family._report("as configured", family.readings(got, want, n))
    layers = list(family.layers_of(params))
    layers[2], layers[3] = layers[3], layers[2]
    x = params["embed"][tokens]
    for lp in layers:
        x = family._ref_block(x, lp, config)
    moved = family.readings(got, dict(want, hidden=x), n)
    assert moved["hidden_rel_median"] > family.LIMITS["hidden_rel_median"]


def test_rounding_is_seen_only_below_bfloat16():
    """``second_reading``'s two sides at the tiny size: the reference
    rounded to float8 fails a limit, rounded to bfloat16 it passes all."""
    passed = family.second_reading(_ctx().config, seed=5, seq=64)
    assert passed == {"float8_e4m3fn": False, "bfloat16": True}


def test_correct_when_nothing_is_wrong():
    result = train_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}


def test_flops_of_the_listed_configuration():
    config = load_json("configs", "kimi-linear-48b-a3b-1chip.json")
    sizes = family._sizes(config)
    assert (sizes["kda_layers"], sizes["full_attn_layers"]) == (
        (1, 2, 3, 5), (4,))
    assert (sizes["n_dense_layers"], sizes["n_layers"]) == (1, 5)
    # ISSUE 33's arithmetic: q, k, v 28.31 M, decay and gate 0.82 each,
    # step 0.07, o 9.44; latent q 14.16, kva 1.33, kvb 4.19, o 9.44
    kda = (3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 4096 * 2304)
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    assert kda == 39_460_864 and latent == 29_114_368
    assert kimi_linear_flops.kda_matmul_params(
        dim=2304, kda_heads=32, kda_head_dim=128) == kda
    assert kimi_linear_flops.latent_matmul_params(
        dim=2304, n_heads=32, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128) == latent
    # router, shared expert, and the held 32 of 256 of the 8 chosen: one
    # expert a token
    expert = 2304 * 256 + 3 * 2304 * 1024 + 1.0 * 3 * 2304 * 1024
    want = (4 * kda + latent + 3 * 2304 * 9216 + 4 * expert
            + 2304 * 20480)
    assert kimi_linear_flops.active_matmul_params(**sizes) == pytest.approx(
        want)
    # a chunk of a head, forward: two decay products over 10 of 16
    # sub-block pairs, the solve against 256 columns, three whole
    # products and one triangular one
    chunk = (2 * 10 * 2 * 16 * 16 * 128 + 64 * 64 * 256
             + 3 * 2 * 64 * 128 * 128 + 64 * 64 * 128)
    assert chunk == 9_175_040
    assert kimi_linear_flops.kda_chunk_flops(chunk=64, dk=128, dv=128) == chunk
    step = kimi_linear_flops.kda_chunk_flops_per_step(
        tokens=8192, chunk=64, **sizes)
    assert step == 3 * 4 * 32 * 128 * chunk
    per_token = kimi_linear_flops.flops_per_token(seq=8192, chunk=64, **sizes)
    attn = 3.0 * 1 * 32 * 8192 * (192 + 128)
    assert per_token == pytest.approx(6.0 * want + attn + step / 8192)


def test_scope_table_finds_the_familys_scopes():
    text = '''
ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.5 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/kda_chunk/checkpoint/while/body/dot_general" stack_frame_id=3}
  %fusion.6 = f32[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/kda_gate/softplus"}
  %fusion.7 = bf16[8,4]{1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/mla_proj/dot_general"}
  %fusion.8 = bf16[8,4]{1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/moe_shared/dot_general"}
  ROOT %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp()/add"}
}'''
    table = hlo_scopes.op_names(text)
    kda = load_json("layer_metrics", "kda_ms.json")["scopes"]
    chunk = load_json("layer_metrics", "kda_chunk_ms.json")["scopes"]
    assert chunk == load_json(
        "layer_metrics", "kda_chunk_roofline.json")["scopes"]
    assert hlo_scopes._in_scope(table["fusion.5"], kda)
    assert hlo_scopes._in_scope(table["fusion.5"], chunk)
    assert hlo_scopes._in_scope(table["fusion.6"], kda)
    assert not hlo_scopes._in_scope(table["fusion.6"], chunk)
    assert not hlo_scopes._in_scope(table["fusion.7"], kda)
    # moe_share_ms lists this cell since PR 58 (kimi_moe_share_ms was a
    # copy of it)
    share = load_json("layer_metrics", "moe_share_ms.json")
    assert hlo_scopes._in_scope(table["fusion.8"], share["scopes"])
    assert not hlo_scopes._in_scope(table["add.2"], kda + share["scopes"])
    assert "moe_share_ms" in cell_metrics("kimi-linear-1chip-steady")


def test_new_readers_report_nothing_without_their_scopes():
    """On a program that lacks the scopes (the parent's), and off the
    chip, the readers return None and do not raise."""
    import importlib.util
    import os

    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    for name in ("kda_ms", "kda_chunk_ms", "kda_chunk_roofline",
                 "moe_share_ms"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(
            load_json("layer_metrics", name + ".json"), ctx) is None
