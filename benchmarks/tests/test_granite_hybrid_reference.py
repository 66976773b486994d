"""The granite_hybrid family's plain reference against the program at a
tiny size on the CPU, as ``test_qwen3_next_reference.py`` has it for
``qwen3_next``; the comparisons that decide ``correct`` shown to fail for
wrong programs; ``harness/granite_hybrid_flops.py`` against hand counts;
the configuration against the catalog; the new readers on a program that
lacks their scopes."""

import json
import os
import time
import types

import pytest

from conftest import BENCH, ROOT, cell_metrics, load_json, one_device_mesh

from benchmarks.families import granite_hybrid as family
from benchmarks.harness import granite_hybrid_flops as flops

LISTED = "granite-4.0-h-small-ep8-1chip.json"
CELL = "granite4h-ep8-1chip-steady"
#: the cell's own per-layer metrics, in BENCHMARK.json's order
METRICS = ("g4h_ssm_ms", "g4h_ssm_proj_ms", "g4h_ssm_chunk_ms",
           "g4h_ssm_chunk_roofline", "g4h_attn_proj_ms",
           "g4h_moe_experts_roofline")
#: readers every family shares, which list this cell (or every cell) since
#: PR 58, where they were copies under names of this cell's
SHARED = ("moe_share_ms", "moe_dispatch_ms", "moe_experts_ms", "embed_ms",
          "hbm_peak_gib", "moe_live_rows", "live_rows_drift",
          "build_lower_s", "build_xla_s", "first_step_host_s",
          "step_dispatch_ms", "trainer_idle_ms")
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size", "mamba_n_heads", "num_attention_heads",
           "num_key_value_heads"]


def _ctx(cell_name="tiny-cpu-granite-hybrid-steady", seconds=0.5, seed=7):
    import jax

    cell = load_json("workloads", cell_name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    return types.SimpleNamespace(
        cell=cell, config=config, seed=seed, seconds=seconds,
        t_start=time.perf_counter(), devices=jax.devices()[: config["chips"]],
        trace_dir="", log=lambda msg: None, family=family,
    )


def _built(config, seq=64):
    """The family, weights whose branches weigh (the closing projections
    at sigma, not 1e-4) and one batch."""
    import jax
    import jax.numpy as jnp

    loud = dict(config, assumed={
        k: v for k, v in config["assumed"].items() if k != "out_proj_std"})
    fam = family.build(config, one_device_mesh())
    params = family.build(loud, one_device_mesh()).init_params(
        jax.random.key(11))
    tokens = jax.random.randint(
        jax.random.key(12), (2, seq), 0, fam.cfg.vocab_size, dtype=jnp.int32)
    return fam, params, tokens


def test_reference_agrees_with_program_in_float32(capsys):
    config = _ctx().config
    fam, params, tokens = _built(config)
    want = family.reference_pieces(params, tokens, config)
    assert family._compare(fam.cfg, one_device_mesh(), params, tokens,
                           config, want)
    out = capsys.readouterr().out
    assert "FAILED" not in out
    for name in family.LIMITS:
        assert name in out, name
    for leaf in ("y",) + family.MAMBA_LEAVES:
        assert f"; d {leaf} " in out, leaf
    import jax

    plain = float(jax.jit(lambda p, t: family.plain_loss(p, t, config))(
        params, tokens))
    assert abs(want["loss"] - plain) < 2e-6


def test_the_pieces_backward_is_the_blocks_own_vjp():
    """``block_backward`` (a piece a program) against ``jax.vjp`` of the
    whole reference block, both kinds of layer."""
    import jax
    import jax.numpy as jnp

    config = _ctx().config
    _, params, _ = _built(config)
    x = jax.random.normal(jax.random.key(1), (2, 64, 64))
    dx = jax.random.normal(jax.random.key(2), (2, 64, 64))
    back = family.block_backward(config)
    for lp in list(family.layers_of(params))[1:3]:
        want = jax.vjp(lambda x: family._ref_block(x, lp, config)[0], x)[1](
            dx)[0]
        got = back(x, family._ref_block(x, lp, config)[-1], lp, dx)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
            jnp.max(jnp.abs(want)))


def test_the_recurrences_blocks_do_not_change_it(monkeypatch):
    config = _ctx().config
    _, params, tokens = _built(config)
    whole = family.reference_loss(params, tokens, config)
    monkeypatch.setattr(family, "T_BLOCK", 16)
    assert abs(family.reference_loss(params, tokens, config) - whole) < 2e-5


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(BENCH, "families", "granite_hybrid.py")
    tree = ast.parse(open(path).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not any("dlrover_tpu" in ast.unparse(n) for n in top)
    uses = {f.name for f in tree.body if isinstance(f, ast.FunctionDef)
            and "dlrover_tpu" in ast.unparse(f)}
    assert uses == {"build", "programs", "program_pieces", "second_reading"}


def test_every_width_of_the_listed_file_is_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"granite-4.0-h-small"' in line)
    listed = load_json("configs", LISTED)
    assert listed["source"] == entry["source_url"]
    assert listed["reduced"] == REDUCED
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key
    assert listed["layer_types"] == entry["config"]["layer_types"][:10]
    assert (listed["num_hidden_layers"], listed["num_local_experts"],
            listed["vocab_size"], listed["mamba_n_heads"],
            listed["num_attention_heads"], listed["num_key_value_heads"]
            ) == (10, 9, 100352 // 8, 32, 8, 2)


# wrong programs the limits are there to catch, each held to the reference
# of the configuration as it stands; at random init the loss alone passes
# most of them
WRONG = {
    "the softmax at 128^-1/2": dict(attention_multiplier=0.25),
    "another residual multiplier": dict(residual_multiplier=0.3),
    "another eps": dict(rms_norm_eps=0.1),
    "logits not scaled": dict(logits_scaling=1.0),
    "the embedding not multiplied": dict(embedding_multiplier=1.0),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_fails_the_comparison(what, capsys):
    config = _ctx().config
    _, params, tokens = _built(config)
    wrong = family.build(dict(config, **WRONG[what]), one_device_mesh())
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        wrong.cfg, one_device_mesh(), params, tokens, config, want)
    assert "FAILED" in capsys.readouterr().out


def state_reset_each_chunk(real, where: str):
    """``ops/ssd.py``'s ``ssd`` with the state set to zero at every chunk
    (each chunk a sequence of its own): in its values and its gradient
    (``where`` "both"), in the values alone ("forward") or in the gradient
    alone ("backward": the state's cotangent is dropped at every chunk)."""
    import jax

    def wrong(x, dt, A, B, C, D, *, chunk, **kw):
        b, s = x.shape[:2]

        def cut(a):
            return a.reshape(b * (s // chunk), chunk, *a.shape[2:])

        right = real(x, dt, A, B, C, D, chunk=chunk, **kw)
        reset = real(cut(x), cut(dt), A, cut(B), cut(C), D, chunk=chunk,
                     **kw).reshape(x.shape)
        value, grad = {"both": (reset, reset), "forward": (reset, right),
                       "backward": (right, reset)}[where]
        return grad + jax.lax.stop_gradient(value - grad)

    return wrong


@pytest.mark.parametrize("what,fails", [
    ("the gate after the norm", {"ssm_rel_median", "ssm_vjp_rel_max"}),
    ("the convolution's bias left out", {"scan_rel_median"}),
    ("a lookup that takes no gradient", {"table_grad_rel"}),
    ("the cumulative decay in bfloat16", {"ssm_vjp_rel_max"}),
    ("the state reset at every chunk: both",
     {"carry_fwd_rel_max", "carry_bwd_rel_max"}),
    ("the state reset at every chunk: forward", {"carry_fwd_rel_max"}),
    ("the state reset at every chunk: backward", {"carry_bwd_rel_max"}),
])
def test_a_wrong_piece_fails_its_own_limit(what, fails, monkeypatch, capsys):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import granite_hybrid
    from dlrover_tpu.ops import rms_norm, ssd

    config = _ctx().config
    fam, params, tokens = _built(config)
    # a bias and decays off their init, so that leaving them out shows
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(13), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.3 * jax.random.normal(k, a.shape) if a.ndim == 2 and a.shape[
            -1] < 64 or a.shape[-1:] == (64,) and a.ndim == 2 else a
        for a, k in zip(leaves, keys)])
    if what == "the gate after the norm":
        monkeypatch.setattr(
            granite_hybrid, "gated_norm", lambda y, z, w, eps: (
                rms_norm(y, w, eps) * jax.nn.silu(z)).astype(y.dtype))
    elif what == "the convolution's bias left out":
        real = granite_hybrid.conv_bias_silu
        monkeypatch.setattr(
            granite_hybrid, "conv_bias_silu",
            lambda x, w, b: real(x, w, jnp.zeros_like(b)))
    elif what == "a lookup that takes no gradient":
        real = granite_hybrid._embed
        monkeypatch.setattr(
            granite_hybrid, "_embed", lambda cfg, p, t, mesh: real(
                cfg, jax.lax.stop_gradient(p), t, mesh))
    elif what.startswith("the state reset at every chunk"):
        # at a state width of 16 the skip D x outweighs the state, which
        # at the listed sizes' width of 128 outweighs it: steps of about
        # 0.2, so that a chunk's state reaches well into the next, no skip
        params["layers"] = {pos: dict(slab, **({
            "dt_bias": slab["dt_bias"] - 3.0, "d_skip": 0 * slab["d_skip"],
        } if "dt_bias" in slab else {}))
            for pos, slab in params["layers"].items()}
        monkeypatch.setattr(ssd, "ssd", state_reset_each_chunk(
            ssd.ssd, what.split(": ")[1]))
    else:
        real = ssd.chunk_sums
        monkeypatch.setattr(
            ssd, "chunk_sums", lambda a, chunk, reverse=False: real(
                a.astype(jnp.bfloat16), chunk, reverse).astype(jnp.float32))
    want = family.reference_pieces(params, tokens, config)
    assert not family._compare(
        fam.cfg, one_device_mesh(), params, tokens, config, want)
    out = capsys.readouterr().out
    failed = {name for name in family.LIMITS
              if f"{name} " in out and "FAILED" in out.split(
                  f"{name} ")[1].split(";")[0]}
    assert fails <= failed, (fails, failed)


def test_the_scans_count_against_a_hand_count():
    sizes = dict(tokens=256, heads=2, p=64, n=128, chunk=256)
    pairs, whole = 256 * 257 // 2, 2 * 256 * 128 * 64
    got = flops.ssd_chunk_flops(**sizes)
    assert got["fwd"] == 2 * 128 * pairs + 2 * (2 * 64 * pairs + 2 * whole)
    assert got["bwd"] == 6 * 128 * pairs + 2 * (4 * 64 * pairs + 5 * whole)
    moved = flops.ssd_chunk_bytes(**sizes)
    operands = 2 * (2 * 64 + 2 * 128) + 4 * 2
    states = 4 * 2 * 64 * 128 / 256
    assert moved["fwd"] == 256 * (operands + 2 * 2 * 64 + states)
    assert moved["bwd"] == 256 * (2 * operands + 2 * 2 * 64 + states)
    # the issue's 16.8 MFLOP of products a head a chunk, the whole squares
    assert 2 * 256 * 256 * 64 + 2 * whole == 16_777_216


def test_flops_of_the_listed_configuration():
    listed = load_json("configs", LISTED)
    assert flops.kinds_of(listed) == list("MMMMMAMMMM")
    assert flops.head_dim(listed) == 128
    assert flops.mamba_matmul_params(listed) == 4096 * 4384 + 2048 * 4096
    assert flops.attention_matmul_params(listed) == (
        2 * 4096 * 1024 + 2 * 4096 * 256)
    assert flops.expert_matmul_params(listed) == pytest.approx(
        4096 * 72 + 3 * 4096 * 1536 + 10 * 9 / 72 * 3 * 4096 * 768)
    per_token = flops.flops_per_token(listed, 16384)
    matmuls = 6 * flops.active_matmul_params(listed)
    attention = 3 * 8 * 16384 * 256
    assert matmuls + attention < per_token < 1.05 * (matmuls + attention)
    assert flops.expected_first_loss(listed) == pytest.approx(9.4529, abs=2e-4)


def test_param_count_is_the_cuts_arithmetic():
    fam = family.build(load_json("configs", LISTED), one_device_mesh())
    assert fam.param_count == 1_340_223_584
    assert round(6 * fam.param_count / 1e9, 2) == 8.04
    assert fam.cfg.pattern_string == "MMMMMAMMMM"
    assert fam.train_config == {"learning_rate": 1e-06, "warmup_steps": 100}


def test_the_listed_metrics_are_this_cells_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    ours = [m for m in benchmark["per_layer"] if m["name"].startswith("g4h_")]
    assert tuple(m["name"] for m in ours) == METRICS
    # one block (not "the last": a later cell appends after it)
    at = benchmark["per_layer"].index(ours[0])
    assert benchmark["per_layer"][at:at + len(ours)] == ours
    assert set(SHARED) <= set(cell_metrics(CELL))
    for m in ours:
        assert m["workloads"] == [CELL], m["name"]
        spec = load_json("layer_metrics", m["name"] + ".json")
        assert (spec["unit"], spec["better"], spec["source"], spec["layer"],
                spec["moves"]) == (m["unit"], m["better"], m["source"],
                                   m["layer"], m["moves"])
    listed, = [w for w in benchmark["workloads"] if w["name"] == CELL]
    assert listed["chips"] == 1 and benchmark["workloads"].index(listed) == 9
    held, = [c for c in benchmark["configs"]
             if c["file"] == "benchmarks/configs/" + LISTED]
    assert held["reduced"] == REDUCED and listed["config"] == held["name"]
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
    for name in ("g4h_ssm_ms", "g4h_ssm_proj_ms", "g4h_ssm_chunk_ms",
                 "g4h_ssm_chunk_roofline", "g4h_attn_proj_ms",
                 "moe_share_ms", "moe_experts_ms",
                 "g4h_moe_experts_roofline", "moe_dispatch_ms",
                 "embed_ms"):
        spec = load_json("layer_metrics", name + ".json")
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        module_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        assert module.read(spec, ctx) is None, name
