"""The olmoe family's plain reference against the program at a tiny size
on the CPU, as ``test_reference.py`` has it for ``llama``; the family's
own terms shown to move the reference; its FLOPs and its scope reader."""

import time
import types

import pytest

from conftest import load_json, one_device_mesh

from benchmarks.families import olmoe as family
from benchmarks.harness import hlo_scopes, moe_flops
from benchmarks.jobs import train_loop


def _ctx(cell_name="tiny-cpu-olmoe-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _params_and_tokens(config, seq=64, batch=2):
    import jax

    fam = family.build(config, one_device_mesh())
    params = fam.init_params(jax.random.key(3))
    # norm weights away from one and a router that spreads its
    # probabilities, so that each of the family's terms weighs
    keys = iter(jax.random.split(jax.random.key(5), 4))
    layers = dict(params["layers"])
    for name in ("q_norm", "k_norm"):
        layers[name] = layers[name] + 0.3 * jax.random.normal(
            next(keys), layers[name].shape)
    layers["router"] = layers["router"] * 40.0
    layers["w_down"] = layers["w_down"] * 300.0
    layers["wq"] = layers["wq"] * 5.0
    params = dict(params, layers=layers, lm_head=params["lm_head"] * 10.0)
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def test_reference_agrees_with_program_in_float32():
    import jax

    config = _ctx().config
    fam, params, tokens = _params_and_tokens(config)
    program = float(jax.jit(fam.loss_fn)(params, tokens))
    reference = family.reference_loss(params, tokens, config)
    # both in float32 here, so they agree to rounding; on the chip the
    # program computes in bfloat16 and the job allows REFERENCE_TOLERANCE
    assert abs(program - reference) < 1e-5


@pytest.mark.parametrize("term", ["norm_topk_prob", "q_norm", "aux_loss"])
def test_reference_sees_the_familys_terms(term):
    """Renormalised weights, q and k left un-normed, or the aux loss left
    out each move the reference by more than float32 rounding."""
    config = _ctx().config
    _, params, tokens = _params_and_tokens(config)
    base = family.reference_loss(params, tokens, config)
    if term == "norm_topk_prob":
        moved = family.reference_loss(
            params, tokens, dict(config, norm_topk_prob=True))
    elif term == "q_norm":
        moved = family.reference_loss(params, tokens, config, qk_norm=False)
    else:
        assumed = dict(config["assumed"], router_aux_loss_coef=0.0)
        moved = family.reference_loss(
            params, tokens, dict(config, assumed=assumed))
    assert abs(moved - base) > 1e-4


def test_correct_when_nothing_is_wrong():
    result = train_loop.run(_ctx())
    assert result["correct"] and result["failed"] == 0
    assert set(result["end_to_end"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"}


def test_flops_of_the_listed_configuration():
    config = load_json("configs", "olmoe-1b-7b-0125-1chip.json")
    sizes = family._sizes(config)
    # a layer's active matmul parameters: 4 x 2048^2 of attention, the
    # router's 2048 x 64, 8 experts of 3 x 2048 x 1024 (ISSUE 27: 67.2 M,
    # 50.3 M of them the experts'); the head 2048 x 50304
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert per_layer == 67_239_936
    assert moe_flops.moe_decoder_active_matmul_params(**sizes) == (
        3 * per_layer + 2048 * 50304)
    per_token = moe_flops.moe_decoder_flops_per_token(seq=4096, **sizes)
    assert per_token == pytest.approx(1.979e9, rel=1e-3)
    assert moe_flops.grouped_matmul_flops(65536, 2048, 1024) == 2.0 * 2**37


def test_scope_table_from_hlo_text():
    text = '''
ENTRY %main (p: f32[8]) -> f32[8] {
  %fusion.5 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/moe_dispatch/gather" stack_frame_id=3}
  %ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%a), metadata={op_name="ragged-dot-none"}
  ROOT %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp(moe_combine)/add"}
  %while.3 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while"}
}'''
    table = hlo_scopes.op_names(text)
    assert table["fusion.5"].endswith("moe_dispatch/gather")
    assert hlo_scopes._in_scope(table["fusion.5"], ["moe_dispatch"])
    assert hlo_scopes._in_scope(table["add.2"], ["moe_combine"])
    assert not hlo_scopes._in_scope(table["while.3"], ["moe_dispatch"])
    assert not hlo_scopes._in_scope(
        table["ragged-dot-none.1"], ["moe_experts"])
