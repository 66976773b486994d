"""The xing4 family's plain reference against the program at a tiny size
on the CPU, as ``test_olmoe_reference.py`` has it for ``olmoe``; the
comparisons that decide ``correct`` shown to fail where a term is
dropped; its FLOPs and bytes against a hand count; its readers on a
recorded scope table."""

import time
import types

import pytest

from conftest import load_json, one_device_mesh

from benchmarks.families import xing4 as family
from benchmarks.harness import hlo_scopes, xing4_flops
from benchmarks.jobs import train_loop


def _ctx(cell_name="tiny-cpu-xing4-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _weighty(params):
    """At its init the model keeps its streams equal copies (``H_post``
    is one for every stream), where no ``H_res`` can show, and its
    scores near zero, where no temperature can: stream coefficients and
    queries large enough that both weigh."""
    import jax
    import jax.numpy as jnp

    def slab(lp):
        lp = dict(lp, w_qb=lp["w_qb"] * 20.0, w_o=lp["w_o"] * 10.0)
        for i, sub in enumerate(("hc_attn", "hc_mlp")):
            lp[f"{sub}_alpha"] = jnp.full_like(lp[f"{sub}_alpha"], 0.7)
            lp[f"{sub}_phi"] = lp[f"{sub}_phi"] * 20.0
            lp[f"{sub}_bias"] = lp[f"{sub}_bias"] + 0.5 * jax.random.normal(
                jax.random.key(i), lp[f"{sub}_bias"].shape)
        return lp

    mtp = dict(params["mtp"], block=slab(params["mtp"]["block"]))
    return dict(params, dense=slab(params["dense"]),
                layers=slab(params["layers"]), mtp=mtp)


def _built(config, seq=64, batch=2, weighty=False):
    import jax

    fam = family.build(config, one_device_mesh())
    params = fam.init_params(jax.random.key(3))
    if weighty:
        params = _weighty(params)
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


_NO_TEMPERATURE = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 0,
                   "mscale_all_dim": 0,
                   "original_max_position_embeddings": 32, "type": "yarn"}


@pytest.mark.parametrize("key,value", [
    ("routed_scaling_factor", 1), ("norm_topk_prob", False),
    ("mhc_h_res_clamp_max", -1.0), ("hc_sinkhorn_iters", 1),
    ("rope_scaling", _NO_TEMPERATURE),
])
def test_a_dropped_term_fails_the_comparison(key, value, capsys):
    """The program built with a term dropped, held to the reference of
    the configuration as it stands: the hook returns NaN (the loss alone,
    at random init, would pass: both CEs stay ln V + d sigma^2 / 2)."""
    import math

    config = _ctx().config
    wrong = family.build(dict(config, **{key: value}), one_device_mesh())
    _, params, tokens = _built(config, weighty=True)
    terms = family.reference_terms(params, tokens, config)
    from dlrover_tpu.models import xing4

    ok = family._compare(xing4, wrong.cfg, one_device_mesh(), params, tokens,
                         config, terms)
    assert not ok
    assert "FAILED" in capsys.readouterr().out
    assert math.isfinite(terms["loss"])


def test_correct_when_nothing_is_wrong():
    result = train_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}


def test_flops_of_the_listed_configuration():
    config = load_json("configs", "xing4.0-29b-a4b-ep8-1chip.json")
    sizes = family._sizes(config)
    assert (sizes["n_dense_layers"], sizes["mtp_depth"]) == (1, 1)
    n = sizes["n_moe_layers"]
    assert n >= 4     # the guide's floor
    # ISSUE 31's arithmetic: W_qa 2.75 M, W_qb 4.72, W_kva 2.06, W_kvb
    # 4.19, W_o 14.68
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                 + 512 * 32 * 256 + 4096 * 3584)
    assert attention == 28_409_856
    assert xing4_flops.attention_matmul_params(
        dim=3584, n_heads=32, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128) == attention
    phi = 2 * 4 * 3584 * 24
    dense = attention + phi + 3 * 3584 * 9216
    # router, shared expert, and the held 8 of 64 of the 4 chosen: half
    # an expert a token
    expert = (attention + phi + 3584 * 64 + 3 * 3584 * 1024
              + 0.5 * 3 * 3584 * 1024)
    want = (dense + (n + 1) * expert + 2 * 3584 * 3584
            + 2 * 3584 * 16384)
    assert xing4_flops.active_matmul_params(**sizes) == pytest.approx(want)
    per_token = xing4_flops.flops_per_token(seq=4096, **sizes)
    attn = 3.0 * (n + 2) * 32 * 4096 * (192 + 128)
    assert per_token == pytest.approx(6.0 * want + attn)
    # a sublayer: 14 slabs forward (twice under remat), 23 backward
    assert xing4_flops.hc_mix_bytes_per_step(
        tokens=8192, dim=3584, hc_mult=4, sublayers=1) == (
            (14 * 2 + 23) * 8192 * 3584 * 2)
    calls = xing4_flops.attention_flops_per_call(
        batch=2, seq=4096, n_heads=32, qk_dim=192, v_dim=128)
    half = 2 * 32 * 4096 * 4096 / 2
    assert calls == {"fwd": 2 * half * 320, "dq": 2 * half * 512,
                     "dkv": 2 * half * 640}


def test_scope_table_finds_the_familys_scopes():
    text = '''
ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.5 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/hc_mix/mul" stack_frame_id=3}
  %fusion.6 = f32[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/hc_coeff/div"}
  %fusion.7 = bf16[8,4]{1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(mtp)/checkpoint/mla_proj/dot_general"}
  %fusion.8 = bf16[8,4]{1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp()/while/body/checkpoint/moe_shared/dot_general"}
  ROOT %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp()/add"}
}'''
    table = hlo_scopes.op_names(text)
    mix = load_json("layer_metrics", "hc_mix_ms.json")["scopes"]
    assert hlo_scopes._in_scope(table["fusion.5"], mix)
    assert hlo_scopes._in_scope(table["fusion.6"], mix)
    assert not hlo_scopes._in_scope(table["fusion.7"], mix)
    # the multi-token module cuts across the other metrics
    for name in ("mtp_ms", "mla_proj_ms"):
        scopes = load_json("layer_metrics", name + ".json")["scopes"]
        assert hlo_scopes._in_scope(table["fusion.7"], scopes)
    share = load_json("layer_metrics", "moe_share_ms.json")["scopes"]
    assert hlo_scopes._in_scope(table["fusion.8"], share)
    assert not hlo_scopes._in_scope(table["add.2"], mix + share)


def test_new_readers_report_nothing_without_their_scopes():
    """On a program that lacks the scopes (the parent's), and off the
    chip, the readers return None and do not raise."""
    import importlib.util
    import os

    from conftest import BENCH

    ctx = _ctx()
    ctx.trace = types.SimpleNamespace(
        devices={"d0": [(0.0, 10.0, "fusion.1", "")]}, spans=[(0, 10, "step")],
        window_ns=(0.0, 10.0))
    ctx.step_op_names = {"fusion.1": "jit(step)/add"}
    ctx.counters = {}
    for name in ("mla_proj_ms", "hc_mix_ms", "hc_mix_roofline",
                 "moe_share_ms", "mla_flash_roofline"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(
            load_json("layer_metrics", name + ".json"), ctx) is None
