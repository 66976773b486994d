"""``harness/step_phases.py`` on a trace and a step's text made by hand
(every number below can be added up from the table), and its leaf rule
on the trace recorded on the chip (``test_trace_reduce.py``'s)."""

import gzip
import json
import os
import types

import pytest

from conftest import HERE

from benchmarks.harness import step_phases, trace_reduce

MS = 1e6    # the trace's clock is nanoseconds; the table below is ms

# name, start, end, op_name: two steps of 200 ms, one layer loop each way
OPS = [
    ("while.1", 0, 100, "jit(step)/jvp()/while"),
    ("slice.1", 0, 10, "jit(step)/jvp()/while/body/dynamic_slice"),
    ("mark.0", 10, 10, ""),     # no length: the chip's line has such
    ("mlp.f", 10, 50,
     "jit(step)/jvp()/while/body/closed_call/dense_mlp/dot_general"),
    ("proj.f", 50, 90,
     "jit(step)/jvp()/while/body/closed_call/attn_proj/transpose"),
    ("stack.1", 90, 100, "jit(step)/jvp()/while/body/dynamic_update_slice"),
    ("while.2", 100, 300, "jit(step)/transpose(jvp())/while"),
    ("mlp.r", 100, 140,
     "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/dense_mlp/dot_general"),
    ("mlp.b", 140, 220,
     "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "dense_mlp/dot_general"),
    ("copy.7", 220, 230, ""),
    ("grouped_matmul.3", 230, 260, ""),
    ("add.9", 260, 300, "jit(step)/transpose(jvp())/add_any"),
    ("adam.1", 300, 340, "jit(step)/optimizer_update/mul"),
    ("scale.1", 340, 350, "jit(step)/grad_finish/mul"),
]
SCOPES = frozenset({"dense_mlp", "attn_proj", "optimizer_update",
                    "grad_finish", "norm"})


def _hlo_text():
    lines = ["ENTRY %main {"]
    for name, _, _, op_name in OPS:
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        lines.append(f"  %{name} = f32[8]{{0}} fusion(%p){meta}")
    return "\n".join(lines + ["}"])


@pytest.fixture
def ctx(monkeypatch):
    from benchmarks.harness import hlo_scopes

    ops = [(s * MS, e * MS, name, f"%{name} = f32[8]{{0}} fusion(%p)")
           for name, s, e, _ in OPS]
    trace = trace_reduce.Trace(
        {"/device:TPU:0": sorted(ops)},
        [(0.0, 200 * MS, "step"), (200 * MS, 400 * MS, "step")])
    logged = []
    monkeypatch.setattr(step_phases, "program_scopes", lambda: SCOPES)
    return types.SimpleNamespace(
        trace=trace, log=logged.append, logged=logged,
        step_phase_names=hlo_scopes.op_names(_hlo_text()))


def _spec(name):
    with open(os.path.join(HERE, "..", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_leaves_are_what_encloses_nothing(ctx):
    (dev,) = step_phases.leaves(ctx)
    assert [l.name for l in dev] == [
        "slice.1", "mlp.f", "proj.f", "stack.1", "mlp.r", "mlp.b", "copy.7",
        "grouped_matmul.3", "add.9", "adam.1", "scale.1"]
    busy_s, _ = trace_reduce.busy_and_window_s(ctx.trace)
    assert busy_s == pytest.approx(0.350)
    assert "leaves_union_s=0.350000 busy_s=0.350000" in ctx.logged[0]


def test_overlapping_operations_are_both_leaves():
    ops = [(0, 10, "a", ""), (5, 15, "b", ""), (20, 40, "while.3", ""),
           (20, 30, "c", ""), (30, 40, "d", ""), (50, 60, "call.1", ""),
           (70, 90, "fusion.2", "%fusion.2 = f32[] fusion(%while.3)"),
           (70, 71, "custom-call.5", "")]
    # a fusion that opens with a short custom call is no while around it;
    # a call around nothing is what ran
    assert [o[2] for o in step_phases.leaf_ops(ops)] == [
        "a", "b", "c", "d", "call.1", "fusion.2", "custom-call.5"]
    assert [o[2] for o in step_phases.leaf_ops(
        [(0, 9, "x.1", "%x.1 = (f32[]) while(%t), body=%b"), (1, 2, "y", "")]
    )] == ["y"]


@pytest.mark.parametrize("metric,ms", [
    ("fwd_ms", 50.0),         # slice.1 + mlp.f + proj.f + stack.1, a step
    ("remat_fwd_ms", 20.0),   # mlp.r
    ("bwd_ms", 60.0),         # mlp.b + add.9
    ("optimizer_ms", 25.0),   # adam.1 + scale.1
])
def test_the_phases(ctx, metric, ms):
    assert step_phases.read_phase(_spec(metric), ctx) == pytest.approx(ms)
    table = step_phases.phase_ms(ctx)
    assert table[step_phases.NO_OP_NAME] == pytest.approx(20.0)
    assert sum(table.values()) == pytest.approx(175.0)   # 350 ms, 2 steps
    assert any("remainder=0.000" in line or "remainder=-0.000" in line
               for line in ctx.logged)


def test_lax_transpose_is_not_the_backward():
    assert step_phases.phase_of("jit(step)/jvp()/attn_proj/transpose") == "fwd"
    assert step_phases.phase_of(
        "jit(step)/transpose(jvp(attn_proj))/transpose") == "bwd"
    assert step_phases.phase_of("") == step_phases.NO_OP_NAME


def test_the_scans_own_and_what_nothing_names(ctx):
    assert step_phases.read_layer_scan({}, ctx) == pytest.approx(10.0)
    # copy.7 + add.9; grouped_matmul.3 is found by name
    # (moe_experts_ms.json's patterns), slice.1 and stack.1 are the
    # scan's own, the rest is under a scope
    assert step_phases.read_unscoped({}, ctx) == pytest.approx(25.0)
    assert step_phases.read_scopes(
        {"scopes": ["dense_mlp"]}, ctx) == pytest.approx(80.0)
    assert step_phases.read_scopes({"scopes": ["norm"]}, ctx) is None
    text = "\n".join(ctx.logged)
    assert ("scope dense_mlp: fwd=20.000 remat_fwd=20.000 bwd=40.000 "
            "optimizer=0.000 all=80.000") in text
    assert "scope optimizer_update: " in text and "scope norm" not in text
    assert ("unscoped: 25.000 ms a step, of it without op_name 20.000; "
            "beside it the scans' own 10.000") in text
    assert ("unscoped op add.9: 20.000 ms a step phase=bwd "
            "op_name=jit(step)/transpose(jvp())/add_any") in text
    assert "unscoped op grouped_matmul.3" not in text


def test_what_xla_made_adopts_a_name():
    text = """\
%fused_computation.7 (p0: f32[8], p1: f32[8]) -> (bf16[8], bf16[8]) {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp()/attn_proj/mul"}
  %sub.1 = f32[8]{0} subtract(%mul.1, %p0), metadata={op_name="jit(step)/jvp()/attn_proj/sub"}
  %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step)/jvp()/attn_proj/mul"}
  %convert.1 = bf16[8]{0} convert(%sub.1)
  %convert.2 = bf16[8]{0} convert(%add.1)
  ROOT %tuple.1 = (bf16[8]{0}, bf16[8]{0}) tuple(%convert.1, %convert.2)
}

%fused_computation.8 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %copy.3 = f32[8]{0} copy(%p0)
}

%fused_computation.9 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %fusion.8 = (bf16[8]{0}, bf16[8]{0}) fusion(%p0, %p0), kind=kLoop, calls=%fused_computation.7
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %subtract_convert_fusion.8 = (bf16[8]{0}, bf16[8]{0}) fusion(%a, %a), kind=kLoop, calls=%fused_computation.7
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.8
  %fusion.10 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/jvp()/norm/mul"}
  %fusion.11 = f32[8]{0} fusion(%a), kind=kCustom, calls=%fused_computation.9
  %copy.5 = f32[8]{0} copy(%fusion.10)
  %copy.6 = f32[8]{0} copy(%copy.5)
  ROOT %copy.4 = f32[8]{0} copy(%fusion.9)
}
"""
    mul, sub = "jit(step)/jvp()/attn_proj/mul", "jit(step)/jvp()/attn_proj/sub"
    adopted = step_phases.adopted_op_names(text)
    # inside fusion 7 the converts take their operands' (no event of a
    # trace: harmless); of the step's own instructions:
    assert {k: v for k, v in adopted.items()
            if k not in ("convert.1", "convert.2", "tuple.1")} == {
        "subtract_convert_fusion.8": mul,   # most of what it fuses
        "fusion.8": mul,
        "fusion.11": mul,                   # through the fusion nested in it
        "copy.5": "jit(step)/jvp()/norm/mul",   # what made its operand
        "copy.6": "jit(step)/jvp()/norm/mul",
    }   # fusion.9 and copy.4 find nothing: a copy of an argument
    assert adopted["convert.1"] == sub


def test_a_program_without_the_list_or_the_text_reports_nothing(
        ctx, monkeypatch):
    monkeypatch.setattr(step_phases, "program_scopes", lambda: None)
    assert step_phases.read_unscoped({}, ctx) is None
    assert step_phases.read_layer_scan({}, ctx) == pytest.approx(10.0)
    bare = types.SimpleNamespace(
        trace=ctx.trace, log=ctx.log, step_phase_names=None)
    for read in (step_phases.read_layer_scan, step_phases.read_unscoped):
        assert read({}, bare) is None
    assert step_phases.read_phase(_spec("fwd_ms"), bare) is None


def test_leaf_rule_on_the_chip_trace(tmp_path):
    path = tmp_path / "recorded.xplane.pb"
    with gzip.open(os.path.join(
            HERE, "data", "mistral7b-d5-steady-5steps.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = trace_reduce.load(str(path), ("batch", "step", "save"))
    lo, hi = trace.window_ns
    (ops,) = trace.devices.values()
    inside = [o for o in ops if o[1] > lo and o[0] < hi]
    leaves = step_phases.leaf_ops(inside)
    names = {o[2] for o in leaves}
    assert not any(n.startswith("while") for n in names)
    assert "attention_bwd.21" in names    # opens with an event of no length
    union = sum(e - s for s, e in trace_reduce._union(
        trace_reduce._clip(leaves, lo, hi)))
    busy_s, _ = trace_reduce.busy_and_window_s(trace)
    assert union / 1e9 == pytest.approx(busy_s, rel=0.005)
