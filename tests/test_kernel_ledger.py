"""Kernel ledger (profiler/kernel_ledger.py): HLO-walk site extraction
(canned HLO: dot flops, fusion-body dedup, ENTRY reset, collectives),
census-vocabulary classification from named-scope op_name paths,
attribution invariants (shares sum to 1.0, measured seconds distributed
not invented), the top-k >=80 % cut, the cumulative ledger + /metrics
lines, the ``kernel`` trace-spine lane, and end-to-end attribution of a
real compiled llama grad step."""

import math
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.profiler import kernel_ledger as kl

jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# classification: named scopes -> census vocabulary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opcode,target,op_name,want", [
    # named-scope markers planted at the ops' custom_vjp boundaries
    ("dot", "", "jit(step)/attention_fwd/dot_general", "attention.fwd"),
    ("dot", "", "jit(step)/attention_bwd/dot_general", "attention.bwd"),
    ("dot", "", "jit(step)/fused_ce_fwd/pad", "ce.fwd"),
    ("dot", "", "jit(step)/chunked_ce_fwd/while/dot", "ce.fwd"),
    ("fusion", "", "jit(step)/fused_ce_bwd/mul", "ce.bwd"),
    ("fusion", "", "jit(step)/chunked_ce_bwd/scan/add", "ce.bwd"),
    ("fusion", "", "jit(step)/optimizer_update/add", "optimizer"),
    # Pallas kernels classify by source path, never a host bucket
    ("custom-call", "tpu_custom_call",
     "jit(step)/attention_fwd/pallas_call", "attention.fwd"),
    ("custom-call", "tpu_custom_call",
     "jit(step)/fused_ce_bwd/pallas_call", "ce.bwd"),
    ("custom-call", "tpu_custom_call", "jit(step)/mystery", "pallas"),
    ("custom-call", "SomeLib", "", "custom_call.SomeLib"),
    # unscoped reference-path fallbacks: einsum specs + AD transpose
    ("dot", "", "jit(f)/einsum[spec=bqhd,bkhd->bhqk]", "attention.fwd"),
    ("dot", "", "jit(f)/transpose(jvp(einsum))[spec=bhqk,bkhd->bqhd]",
     "attention.bwd"),
    ("dot", "", "jit(f)/lm_head/dot_general", "ce.fwd"),
    # collectives map onto the SC001 census vocabulary
    ("all-reduce", "", "jit(step)/psum", "comm.all-reduce"),
    ("all-gather-start", "", "", "comm.all-gather"),
    ("reduce-scatter", "", "dcn_bucket_3/psum_scatter",
     "comm.dcn_bucket"),
    # pp executor scopes: stage handoff + slab compute
    ("collective-permute", "", "jit(step)/pp_send_recv/ppermute",
     "comm.pp_send_recv"),
    ("dot", "", "jit(step)/stage_fwd/scan/dot_general", "stage.fwd"),
    ("fusion", "", "jit(step)/stage_bwd/scan/mul", "stage.bwd"),
    ("dot", "", "jit(step)/transpose(stage_fwd)/dot", "stage.bwd"),
    # specific op markers win over the enclosing stage scope
    ("dot", "", "jit(step)/stage_fwd/attention_fwd/dot",
     "attention.fwd"),
    ("dot", "", "jit(f)/mlp/dot_general", "matmul"),
    ("fusion", "", "jit(f)/gelu", "other"),
])
def test_classify_site(opcode, target, op_name, want):
    assert kl.classify_site(opcode, target, op_name) == want


# ---------------------------------------------------------------------------
# HLO walk on canned text
# ---------------------------------------------------------------------------

CANNED_HLO = """\
HloModule jit_step

%fused_computation (param_0.1: f32[64,32]) -> f32[64,32] {
  %param_0.1 = f32[64,32]{1,0} parameter(0)
  %multiply.0 = f32[64,32]{1,0} multiply(%param_0.1, %param_0.1)
  %dot.9 = f32[64,64]{1,0} dot(f32[64,32]{1,0} %multiply.0, f32[64,32]{1,0} %param_0.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(step)/attention_fwd/dot_general"}
}

ENTRY %main.12 (Arg_0.1: f32[64,128], Arg_1.2: f32[128,32]) -> f32[] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,32]{1,0} parameter(1)
  %dot.4 = f32[64,32]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,32]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mlp/dot_general"}
  %tanh_fusion = f32[64,32]{1,0} fusion(f32[64,32]{1,0} %dot.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/tanh"}
  %all-reduce.1 = f32[64,32]{1,0} all-reduce(f32[64,32]{1,0} %tanh_fusion), replica_groups={}, metadata={op_name="jit(step)/psum"}
  ROOT %reduce.2 = f32[] reduce(f32[64,32]{1,0} %all-reduce.1, f32[] %Arg_1.2), dimensions={0,1}
}
"""


def test_iter_sites_canned():
    sites = list(kl.iter_sites(CANNED_HLO))
    by_op = {}
    for s in sites:
        by_op.setdefault(s.op, []).append(s)

    # fused-body dots ARE counted (their flops are real work) but the
    # body's elementwise ops are not (the calling fusion owns the bytes)
    assert len(by_op["attention.fwd"]) == 1
    assert not any(s.opcode == "multiply" for s in sites)

    # ENTRY resets fused-body mode: the entry's fusion / collective /
    # reduce sites are all attributed
    assert len(by_op["matmul"]) == 1
    assert any(s.opcode == "fusion" for s in by_op["other"])
    assert len(by_op["comm.all-reduce"]) == 1

    # parameters never yield sites
    assert not any(s.opcode == "parameter" for s in sites)

    # dot flops: 2 * out_elems * contracted = 2 * (64*32) * 128
    dot = by_op["matmul"][0]
    assert dot.flops == 2.0 * 64 * 32 * 128
    # bytes: result + operands (3 * f32[64,.] shapes worth)
    assert dot.bytes == 4 * (64 * 32 + 64 * 128 + 128 * 32)


def test_attribute_step_invariants():
    rows = kl.attribute_step(None, 0.25, hlo_text=CANNED_HLO)
    assert abs(sum(r["share"] for r in rows) - 1.0) <= 1e-4
    assert abs(sum(r["seconds"] for r in rows) - 0.25) <= 1e-3
    # sorted by seconds descending
    secs = [r["seconds"] for r in rows]
    assert secs == sorted(secs, reverse=True)
    # the measured step time is distributed, never invented
    assert kl.attribute_step(None, 0.0, hlo_text=CANNED_HLO)
    assert all(
        r["seconds"] == 0.0
        for r in kl.attribute_step(None, 0.0, hlo_text=CANNED_HLO)
    )


# ---------------------------------------------------------------------------
# zero-sized operand guards (degenerate [0,...] slices, scalar psums)
# ---------------------------------------------------------------------------

ZERO_HLO = """\
HloModule jit_zero

ENTRY %main (Arg_0.1: f32[0,128], Arg_1.2: f32[128,32]) -> f32[] {
  %Arg_0.1 = f32[0,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,32]{1,0} parameter(1)
  %dot.1 = f32[0,32]{1,0} dot(f32[0,128]{1,0} %Arg_0.1, f32[128,32]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/mlp/dot_general"}
  %reduce.1 = f32[] reduce(f32[0,32]{1,0} %dot.1, f32[] %Arg_1.2), dimensions={0,1}
  ROOT %all-reduce.1 = f32[] all-reduce(f32[] %reduce.1), replica_groups={}, metadata={op_name="jit(step)/psum"}
}
"""

ALL_ZERO_HLO = """\
HloModule jit_allzero

ENTRY %main (Arg_0.1: f32[0,128]) -> f32[0,128] {
  %Arg_0.1 = f32[0,128]{1,0} parameter(0)
  ROOT %all-reduce.1 = f32[0,128]{1,0} all-reduce(f32[0,128]{1,0} %Arg_0.1), replica_groups={}, metadata={op_name="jit(step)/psum"}
}
"""


def test_zero_sized_dot_scores_zero_work():
    sites = {s.opcode: s for s in kl.iter_sites(ZERO_HLO)}
    dot = sites["dot"]
    # a 0-row dot output is zero WORK — it must not borrow the scalar
    # fallback (the old `or 1.0`) and claim 2*1*128 flops
    assert dot.flops == 0.0
    # only the non-degenerate operand carries bytes
    assert dot.bytes == 4 * 128 * 32
    # scalar psum: f32[] result + f32[] operand = 8 bytes, finite cost
    ar = sites["all-reduce"]
    assert ar.bytes == 8.0
    assert ar.cost > 0.0
    assert math.isfinite(ar.cost)


def test_first_shape_elems_none_vs_zero():
    # no parseable shape -> None (callers fall back to the scalar 1);
    # a real zero-sized dim -> 0.0, which must stay 0, not become 1
    assert kl._first_shape_elems("no shape here", range(8)) is None
    assert kl._first_shape_elems("f32[0,32]{1,0}", range(8)) == 0.0
    assert kl._first_shape_elems("f32[]", range(8)) == 1.0


def test_all_zero_cost_program_attributes_without_dividing():
    # every site zero-sized -> total roofline cost 0: shares come back
    # all-zero instead of raising ZeroDivisionError
    rows = kl.attribute_step(None, 0.25, hlo_text=ALL_ZERO_HLO)
    assert rows
    assert all(
        r["share"] == 0.0 and r["seconds"] == 0.0 for r in rows
    )


def test_top_k_cut():
    rows = [
        {"op": f"op{i}", "share": s, "seconds": s, "flops": 0.0,
         "bytes": 0.0, "sites": 1}
        for i, s in enumerate([0.5, 0.25, 0.15, 0.06, 0.04])
    ]
    cut = kl.top_k(rows, min_share=0.8)
    named = [r for r in cut if r["op"] != "other"]
    # smallest prefix covering 80 %: 0.5 + 0.25 + 0.15
    assert [r["op"] for r in named] == ["op0", "op1", "op2"]
    assert sum(r["share"] for r in named) >= 0.8
    # the tail folds into a loud "other" row, shares still sum to 1.0
    assert cut[-1]["op"] == "other"
    assert cut[-1]["share"] == pytest.approx(0.1)
    assert sum(r["share"] for r in cut) == pytest.approx(1.0)
    # max_k caps the prefix even when min_share is not yet reached
    tiny = kl.top_k(rows, min_share=0.99, max_k=2)
    assert len([r for r in tiny if r["op"] != "other"]) == 2


# ---------------------------------------------------------------------------
# end to end: a real compiled llama grad step
# ---------------------------------------------------------------------------


def test_real_llama_step_attribution():
    from dlrover_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(ce_chunk_size=64)
    params = llama.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 10), 0,
                              cfg.vocab_size)

    grad = jax.jit(jax.grad(lambda p: llama.loss_fn(p, toks, cfg)))
    compiled = grad.lower(params).compile()
    rows = kl.attribute_step(compiled, 0.1)

    ops = {r["op"] for r in rows}
    # the named scopes land in the compiled metadata: both attention
    # directions and both CE directions get their own named blame
    assert {"attention.fwd", "attention.bwd", "ce.fwd", "ce.bwd"} <= ops
    assert abs(sum(r["share"] for r in rows) - 1.0) <= 1e-4
    # a >=80 % top-k cut always exists (shares sum to 1.0)
    cut = kl.top_k(rows)
    assert sum(r["share"] for r in cut) >= 0.8 or len(cut) == len(rows)


# ---------------------------------------------------------------------------
# ledger singleton, /metrics, trace lane
# ---------------------------------------------------------------------------


def _rows(**shares):
    return [
        {"op": op, "share": s, "seconds": s * 0.1, "flops": 0.0,
         "bytes": 0.0, "sites": 1}
        for op, s in shares.items()
    ]


def test_ledger_accumulates_and_exports():
    led = kl.KernelLedger()
    led.record_breakdown(_rows(**{"attention.fwd": 0.6, "ce.bwd": 0.4}))
    led.record_breakdown(_rows(**{"attention.fwd": 0.7, "ce.bwd": 0.3}))
    totals = led.totals()
    assert totals["attention.fwd"] == pytest.approx(0.13)
    assert totals["ce.bwd"] == pytest.approx(0.07)
    lines = led.prometheus_lines()
    assert any(
        l.startswith('dlrover_tpu_kernel_seconds_total{op="attention.fwd"}')
        for l in lines
    )
    # last_share reflects the most recent breakdown, not the sum
    assert 'dlrover_tpu_kernel_share{op="attention.fwd"} 0.700000' in lines
    led.clear()
    assert led.prometheus_lines() == []


def test_metrics_endpoint_serves_kernel_lines():
    """The worker /metrics endpoint (profiler/comm.py) carries the
    kernel rows next to the comm ledger's."""
    from dlrover_tpu.profiler.comm import (
        start_metrics_server,
        stop_metrics_server,
    )

    kl.kernel_ledger.clear()
    kl.kernel_ledger.record_breakdown(_rows(**{"attention.fwd": 1.0}))
    _, port = start_metrics_server(0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert 'dlrover_tpu_kernel_seconds_total{op="attention.fwd"}' \
            in body
        assert 'dlrover_tpu_kernel_share{op="attention.fwd"}' in body
    finally:
        stop_metrics_server()
        kl.kernel_ledger.clear()


def test_capture_step_records_into_ledger():
    kl.kernel_ledger.clear()
    try:
        rows = kl.capture_step(None, 0.5, hlo_text=CANNED_HLO)
        assert rows == kl.kernel_ledger.last_breakdown()
        assert sum(kl.kernel_ledger.totals().values()) == pytest.approx(
            0.5, abs=1e-3
        )
    finally:
        kl.kernel_ledger.clear()


def test_measure_step_median():
    calls = []

    def fake_run():
        calls.append(1)

    s = kl.measure_step(fake_run, n=3)
    assert len(calls) == 3
    assert s >= 0.0
