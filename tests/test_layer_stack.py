"""``models/stack.py``: the layout of a stack of layers, the walk over it
and the recompute decision, each against what the families' own copies
did before it; and that the copies are gone."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.models import (
    dots3, granite_hybrid, keye_vl, kimi_linear, minicpm_sala, qwen3_next,
    smallthinker, stack)
from dlrover_tpu.models.stack import Part

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "dlrover_tpu", "models")

F, S = "F", "S"


# ---------------------------------------------------------------------------
# The layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kinds,head,whole,want", [
    # smallthinker, published: F W W W thirteen times
    ((0, 1, 1, 1) * 13, 0, True, (Part((0, 1, 1, 1), 13),)),
    # dots3: the tiny layout, the published one, two dense layers
    ("FFSSS", 1, False, (Part((F,)), Part((F, S, S, S), 1))),
    (F + "FSSS" * 11 + F, 1, False,
     (Part((F,)), Part((F, S, S, S), 11), Part((F,)))),
    ("FSFSFS", 2, False, (Part((F,)), Part((S,)), Part((F, S), 2))),
    # no period but the whole depth
    ("FFSFS", 0, False, (Part((F, F, S, F, S), 1),)),
    ("FFSFS", 0, True, (Part((F, F, S, F, S), 1),)),
    # a period that does not divide the depth: a tail, or the whole depth
    ("FSFSF", 0, False, (Part((F, S), 2), Part((F,)))),
    ("FSFSF", 0, True, (Part((F, S, F, S, F), 1),)),
    # every layer a layer of its own
    ("FS", 2, False, (Part((F,)), Part((S,)))),
    ("", 0, False, ()),
])
def test_periodic_finds_the_shortest_period_between_a_head_and_a_tail(
        kinds, head, whole, want):
    parts = stack.periodic(kinds, head, whole)
    assert parts == want
    assert sum(p.n_layers for p in parts) == len(kinds)


def test_runs_are_stacked_parts_of_one_position():
    cfg = kimi_linear.KimiLinearConfig()
    parts = stack.runs(cfg.pattern)
    assert parts == cfg.layout and len(parts) == 15
    assert all(len(p.kinds) == 1 and p.repeats >= 1 for p in parts)
    assert [p.repeats for p in parts[:4]] == [1, 2, 1, 3]
    assert cfg.runs[:3] == (("kda", "dense", 1), ("kda", "moe", 2),
                            ("mla", "moe", 1))
    # a run of one layer is still a stack of one row
    assert stack.runs("KKLK") == (
        Part(("K",), 2), Part(("L",), 1), Part(("K",), 1))


@pytest.mark.parametrize("parts,kinds", [
    (smallthinker.SmallThinkerConfig().layout,
     smallthinker.SmallThinkerConfig().kinds),
    (dots3.Dots3Config().layout, dots3.Dots3Config().layer_kinds),
    (kimi_linear.KimiLinearConfig().layout,
     kimi_linear.KimiLinearConfig().pattern),
    (stack.periodic("ffFSFSF", head=2), tuple("ffFSFSF")),
    (qwen3_next.Qwen3NextConfig().layout, qwen3_next.Qwen3NextConfig().kinds),
    (minicpm_sala.MiniCPMSalaConfig().layout,
     minicpm_sala.MiniCPMSalaConfig().kinds),
    (granite_hybrid.GraniteHybridConfig().layout,
     granite_hybrid.GraniteHybridConfig().kinds),
    (keye_vl.KeyeVLConfig().layout, keye_vl.KeyeVLConfig().pattern_string),
], ids=["smallthinker", "dots3", "kimi_linear", "head_and_tail",
        "qwen3_next", "minicpm_sala", "granite_hybrid", "keye_vl"])
def test_locate_finds_every_layer_once_and_in_order(parts, kinds):
    seen = [stack.locate(parts, layer) for layer in range(len(kinds))]
    assert len(set(seen)) == len(kinds) and seen == sorted(
        seen, key=lambda at: (at[0], at[2], at[1]))
    for layer, (part, position, row) in enumerate(seen):
        assert parts[part].kinds[position] == kinds[layer]
        assert row < (parts[part].repeats or 1)
    with pytest.raises(IndexError):
        stack.locate(parts, len(kinds))


def test_the_families_read_their_layouts_from_it():
    st = smallthinker.SmallThinkerConfig()
    assert (st.period, st.layout[0].repeats) == (4, 13)
    d3 = dots3.Dots3Config()
    assert stack.locate(d3.layout, 0) == (0, 0, 0)
    assert stack.locate(d3.layout, 1) == (1, 0, 0)
    assert stack.locate(d3.layout, 44) == (1, 3, 10)
    assert stack.locate(d3.layout, 45) == (2, 0, 0)
    none = dots3.Dots3Config.tiny(layer_kinds=(F, S), n_dense_layers=2)
    assert (none.period, none.n_periods, none.tail_kinds) == (1, 0, ())


# ---------------------------------------------------------------------------
# The walk
# ---------------------------------------------------------------------------

def _toy(kinds, head, key=0):
    """Per-layer parameters of a toy block, and the same dealt out to
    ``periodic(kinds, head)``'s parts."""
    parts = stack.periodic(kinds, head)
    layers = [{"w": w, "b": b} for w, b in zip(
        jax.random.normal(jax.random.key(key), (len(kinds), 3, 3)) * 0.5,
        jax.random.normal(jax.random.key(key + 1), (len(kinds), 3)))]

    def trees(layers):
        out, at = [], 0
        for part in parts:
            if part.repeats is None:
                out.append(layers[at])
            else:
                p = len(part.kinds)
                out.append(tuple(
                    jax.tree.map(lambda *rows: jnp.stack(rows),
                                 *layers[at + i:at + part.n_layers:p])
                    for i in range(p)))
            at += part.n_layers
        return out

    return parts, layers, trees


def _toy_block(kind, lp, x):
    x = jnp.tanh(x @ lp["w"] + lp["b"]) * (2.0 if kind == F else 0.5) + x
    return x, jnp.sum(x * x, axis=-1)


@pytest.mark.parametrize("kinds,head", [
    ("FFSSFSSFSSF", 1),    # a head, three periods of three, a tail
    ("FSFS", 0),           # the scan alone
    ("FS", 2),             # no scan
])
@pytest.mark.parametrize("outs", [True, False])
def test_walk_is_the_plain_loop_over_layers(kinds, head, outs):
    parts, layers, trees = _toy(kinds, head)
    x0 = jax.random.normal(jax.random.key(7), (2, 3))

    def each(kind, lp, x):
        x, out = _toy_block(kind, lp, x)
        return x, (out if outs else None)

    def walked(layers, x):
        return stack.walk(x, parts, trees(layers), each)

    def looped(layers, x):
        got = []
        for kind, lp in zip(kinds, layers):
            x, out = each(kind, lp, x)
            got.append(out)
        return x, (jnp.stack(got) if outs else None)

    def loss(fn):
        def scalar(layers, x):
            x, out = fn(layers, x)
            return jnp.sum(x) + (jnp.sum(out * out) if outs else 0.0)
        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1)))

    x, out = jax.jit(walked)(layers, x0)
    want_x, want_out = looped(layers, x0)
    np.testing.assert_allclose(x, want_x, rtol=1e-6)
    if outs:
        assert out.shape == (len(kinds), 2)
        np.testing.assert_allclose(out, want_out, rtol=1e-6)
    else:
        assert out is None
    (got, grads), (want, want_grads) = (
        loss(walked)(layers, x0), loss(looped)(layers, x0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), grads, want_grads)
    # and layer_params finds each layer's leaves where the walk read them
    for layer, lp in enumerate(layers):
        mine = stack.layer_params(parts, trees(layers), layer)
        np.testing.assert_array_equal(mine["w"], lp["w"])


def test_walk_scans_a_stacked_part_once_and_inlines_the_rest():
    parts, layers, trees = _toy("FFSSFSSFSSF", 1)
    jaxpr = jax.make_jaxpr(lambda layers, x: stack.walk(
        x, parts, trees(layers), _toy_block))(layers, jnp.ones((2, 3)))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 3
    body = [e.primitive.name for e in scans[0].params["jaxpr"].jaxpr.eqns]
    assert body.count("tanh") == 3      # a period's three positions
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns].count("tanh") == 2


# ---------------------------------------------------------------------------
# The recompute decision
# ---------------------------------------------------------------------------

def _named(w, x):
    a = checkpoint_name(jnp.sin(x * w), "first")
    b = checkpoint_name(jnp.exp(a), "second")
    return jnp.tanh(b) * w


def _recomputed(fn):
    """The primitives of the computation the backward pass runs again, or
    None where it runs none."""
    jaxpr = jax.make_jaxpr(jax.grad(lambda w, x: jnp.sum(fn(w, x))))(
        2.0, jnp.ones(3))
    again = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in (
        "remat2", "checkpoint")]
    if not again:
        return None
    assert len(again) == 1
    return [e.primitive.name for e in again[0].params["jaxpr"].eqns]


def test_recompute_keeps_the_named_residual_and_no_other():
    assert stack.recompute(_named, False, ("first",)) is _named
    assert _recomputed(_named) is None
    whole = _recomputed(stack.recompute(_named, True))
    assert "sin" in whole and "exp" in whole
    met = []
    first = _recomputed(stack.recompute(_named, True, ("first",), met.append))
    assert "sin" not in first and "exp" in first
    assert met and set(met) == {"first"}
    both = _recomputed(stack.recompute(
        _named, True, ("first", "second"), met.append))
    assert "sin" not in both and set(met) == {"first", "second"}
    del met[:]
    # a name nothing carries keeps nothing
    other = _recomputed(stack.recompute(_named, True, ("third",), met.append))
    assert "sin" in other and "exp" in other and not met


# ---------------------------------------------------------------------------
# The copies are gone
# ---------------------------------------------------------------------------

def _sources():
    return {os.path.basename(path): open(path).read()
            for path in glob.glob(os.path.join(MODELS, "*.py"))}


def test_one_file_under_models_calls_jax_checkpoint():
    calls = {name for name, text in _sources().items()
             if re.search(r"(?<![\w.])(jax\.)?(checkpoint|remat)\(", text)}
    assert calls == {"stack.py"}


@pytest.mark.parametrize("names,keepers", [
    (r"attention\.KEPT|attn_ops\.KEPT", {
        "dots3.py", "qwen3_next.py", "xing4.py", "kimi_linear.py",
        "smallthinker.py", "minicpm_sala.py", "llama.py",
        "granite_hybrid.py", "keye_vl.py", "laguna.py", "phi4flash.py"}),
    (r"kda\.KEPT", {"kimi_linear.py"}),
    (r"lightning\.KEPT", {"minicpm_sala.py"}),
    (r"ssd\.KEPT", {"granite_hybrid.py"}),
    (r"selective_scan\.KEPT", {"phi4flash.py"}),
])
def test_families_keep_a_forward_kernels_residuals_at_their_call_site(
        names, keepers):
    """The keep is each family's own choice where it calls `recompute`
    (its cell's planned peak has the room), not a rule of `stack.py` or
    of the kernels: the flash forward's pair in eleven files (Llama's
    `_maybe_remat`, which `moe.py`'s layer goes through, keeps q, k, v
    beside it), the delta rule's in kimi's alone (qwen3next's step has
    not the room), the lightning rule's in minicpm_sala's, the
    state-space scan's in granite_hybrid's (where its configuration says
    so), the selective scan's in phi4flash's; ViT names nothing."""
    sources = _sources()
    assert {name for name, text in sources.items()
            if re.search(names, text)} == keepers
    assert {name for name, text in sources.items()
            if re.search(r"\bKEPT\b", text)} == {
        "dots3.py", "qwen3_next.py", "xing4.py", "kimi_linear.py",
        "smallthinker.py", "minicpm_sala.py", "llama.py",
        "granite_hybrid.py", "keye_vl.py", "laguna.py", "phi4flash.py"}
    assert "KEPT" not in sources["stack.py"]


def test_no_family_walks_its_layers_or_shifts_its_targets_itself():
    sources = _sources()
    for name in ("kimi_linear.py", "smallthinker.py", "dots3.py",
                 "qwen3_next.py", "minicpm_sala.py", "granite_hybrid.py",
                 "keye_vl.py", "laguna.py", "phi4flash.py"):
        assert "lax.scan(" not in sources[name], name
    assert {name for name, text in sources.items()
            if "_shift_targets" in text} == {"llama.py"}
    # beside the one tail, the pp stages' head loss and the classifier's
    assert {name: text.count("cross_entropy_sums(")
            for name, text in sources.items()
            if "cross_entropy_sums(" in text} == {
        "stack.py": 1, "llama.py": 1, "vit.py": 1}
