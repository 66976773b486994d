"""The laguna family (``models/laguna.py``) at a tiny size on the CPU
against the plain form of its equations (``benchmarks/families/
laguna.py``: explicit scores and mask, a loop over the experts): two
periods ``f S S S F S S S`` whose kinds differ in their query heads (4
and 6 on 2 key heads), a window of 16 under 48 positions, 8 experts
top-2 (4 held) beside a shared one, a leading dense layer. The layout
read from the config's lists; the window's edge; the two rotary rules
against closed forms; the gate; the share of the experts tied to the
uncut layer. (Sizes, gauges, meshes and the trainer:
``test_laguna_mesh.py``; what the two share: ``laguna_family.py``.)"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import laguna as family
from benchmarks.families.xing4 import yarn_inv_freq
from dlrover_tpu.models import laguna, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import (
    apply_rope,
    rms_norm,
    rope_frequencies,
    yarn_frequencies,
    yarn_mscale,
)
from tests.laguna_family import (  # noqa: F401  (fixtures by import)
    _assert_grads_agree, _built, _plain_loss, _weighty, built, changed,
    config, load_config, mesh)

FULL, WINDOW = family.FULL, family.WINDOW


@pytest.mark.parametrize("remat", ["off", "all"])
def test_loss_and_gradients_match_the_plain_form(config, mesh, remat):
    """The dense layer, one scanned period S S S F and the tail's scan
    of three, with every block recomputed and without."""
    fam, params, tokens = _built(changed(config, "assumed/remat", remat), mesh)
    assert fam.cfg.pattern_string == "fSSSFSSS" and fam.cfg.window == 16
    assert fam.cfg.remat == (remat == "all")
    assert [p.repeats for p in fam.cfg.layout] == [None, 1, 3]
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    _assert_grads_agree(grads, want_grads)
    # every layer's every leaf weighs
    for group in ("layers", "tail"):
        for slab in grads[group].values():
            for name, leaf in slab.items():
                rows = np.abs(np.asarray(leaf)).reshape(len(leaf), -1)
                assert rows.max(-1).min() > 0.0, name
    for name, leaf in grads["dense"]["layer0"].items():
        assert float(jnp.max(jnp.abs(leaf))) > 0.0, name


@pytest.mark.parametrize("path,value", [
    ("sliding_window", 8),                                  # another band
    ("sliding_window", 64),                                 # none that bites
    ("layer_types", [FULL] * 8),                            # a full mask
    ("rope_parameters/full_attention/attention_factor", 1.0),
    ("rope_parameters/full_attention/partial_rotary_factor", 1.0),
    ("rope_parameters/full_attention/factor", 16),
    ("rope_parameters/full_attention/rope_theta", 100.0),
    ("rope_parameters/sliding_attention/rope_theta", 50.0),
    ("rope_parameters/sliding_attention/partial_rotary_factor", 0.5),
    ("moe_routed_scaling_factor", 1.0),
    ("num_experts_per_tok", 3),
    ("rms_norm_eps", 0.1),
])
def test_each_config_term_moves_the_plain_form_and_the_program(
        built, config, mesh, path, value):
    """The plain form under a changed term is another loss, and the
    program built from the changed configuration follows it."""
    fam, params, tokens = built
    other = changed(config, path, value)
    base = _plain_loss(params, tokens, config)
    want = _plain_loss(params, tokens, other)
    assert abs(want - base) > 1e-4, (path, base, want)
    got = float(jax.jit(family.build(other, mesh).loss_fn)(params, tokens))
    assert abs(got - want) < 2e-5


@pytest.mark.parametrize("types,heads,dense,repeats", [
    # the full layers at the greater count, a period of two, no tail
    ([FULL, WINDOW] * 3, [6, 4] * 3, 2, [None, None, 2]),
    # no dense layer: a period of three, twice
    ([FULL, WINDOW, WINDOW] * 2, [4, 6, 6] * 2, 0, [2]),
    # no period that repeats: F F S S once and one layer more
    ([WINDOW, FULL, FULL, WINDOW, WINDOW, FULL], [6, 4, 4, 6, 6, 4], 1,
     [None, 1, 1]),
])
def test_the_layout_is_read_from_the_three_lists(
        config, mesh, types, heads, dense, repeats):
    other = dict(config, num_hidden_layers=6, layer_types=types,
                 num_attention_heads_per_layer=heads,
                 mlp_layer_types=["dense"] * dense + ["sparse"] * (6 - dense))
    fam, params, tokens = _built(other, mesh)
    letters = [{FULL: "F", WINDOW: "S"}[t] for t in types]
    assert fam.cfg.kinds == tuple(zip(letters, heads))
    assert [p.repeats for p in fam.cfg.layout] == repeats
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, other)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    _assert_grads_agree(grads, want_grads)


def test_pattern_period_and_what_a_config_may_not_say():
    cfg = laguna.LagunaConfig()
    assert cfg.pattern_string == "f" + "SSS" + "FSSS" * 9
    assert cfg.kinds[0] == ("F", 48) and cfg.kinds[1] == ("S", 64)
    assert [(len(p.kinds), p.repeats) for p in cfg.layout] == [
        (1, None), (4, 9), (1, 3)]
    assert (cfg.period, cfg.n_periods, len(cfg.tail_kinds)) == (4, 9, 3)
    assert cfg.heads_of("F") == 48 and cfg.heads_of("S") == 64
    tiny = laguna.LagunaConfig.tiny
    with pytest.raises(ValueError, match="layer_kinds"):
        tiny(layer_kinds=("F", "X") * 4)
    with pytest.raises(ValueError, match="head counts"):
        tiny(heads_per_layer=(4, 6))
    with pytest.raises(ValueError, match="do not group"):
        tiny(heads_per_layer=(4, 5, 6, 6) * 2)
    with pytest.raises(ValueError, match="rotary factor"):
        tiny(rotary_factor=0.3)


@pytest.mark.parametrize("key,value,match", [
    ("gating", False, "gating"), ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("moe_apply_router_weight_on_input", True, "router_weight_on_input"),
    ("mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 6, "leading"),
    ("layer_types", [FULL] * 7, "8 layers each"),
    ("rope_parameters/full_attention/rope_type", "default", "by yarn"),
])
def test_from_hf_refuses_what_the_program_does_not_compute(
        config, key, value, match):
    with pytest.raises(ValueError, match=match):
        laguna.LagunaConfig.from_hf(changed(config, key, value))


def test_params_are_dealt_out_to_the_layouts_parts(built):
    fam, params, _ = built
    assert sorted(params["dense"]) == ["layer0"]
    assert sorted(params["layers"]) == ["pos0", "pos1", "pos2", "pos3"]
    assert sorted(params["tail"]) == ["run0"]
    # the two kinds' slabs differ in shape, a position's own
    assert params["layers"]["pos0"]["wq"].shape == (1, 64, 6 * 16)
    assert params["layers"]["pos3"]["wq"].shape == (1, 64, 4 * 16)
    assert params["layers"]["pos3"]["w_g"].shape == (1, 64, 4)
    assert params["tail"]["run0"]["wo"].shape == (3, 6 * 16, 64)
    assert "router" not in params["dense"]["layer0"]
    # the reference reads them in the program's layer order
    for layer, lp in enumerate(family.layers_of(params)):
        own = laguna.layer_params(fam.cfg, params, layer)
        assert sorted(own) == sorted(lp)
        for name in lp:
            np.testing.assert_array_equal(lp[name], own[name])


def test_out_proj_std_is_the_closing_projections_own(config, mesh):
    fam = family.build(config, mesh)
    assert fam.cfg.out_proj_std == 1e-4 and fam.cfg.init_std == 0.02
    params = fam.init_params(jax.random.key(0))
    run = params["tail"]["run0"]
    for name in ("wo", "w_down", "ws_down"):
        assert float(jnp.std(run[name])) == pytest.approx(1e-4, rel=0.05)
    for name in ("wq", "wk", "w_g", "router", "w_gate", "ws_up"):
        assert float(jnp.std(run[name])) == pytest.approx(0.02, rel=0.08)
    dense = params["dense"]["layer0"]
    assert float(jnp.std(dense["w_down"])) == pytest.approx(1e-4, rel=0.05)
    assert float(jnp.std(dense["w_up"])) == pytest.approx(0.02, rel=0.05)


def _one_layer(built, layer):
    fam, params, tokens = built
    lp = laguna.layer_params(fam.cfg, params, layer)
    x = params["embed"][tokens]
    return fam.cfg, fam.cfg.kinds[layer], lp, x


def test_a_window_layers_query_sees_sixteen_keys_its_own_among_them(built):
    """``0 <= i - j < 16``: the key 15 back is the last a query sees.
    Another token at position ``j`` moves the attention output at
    ``j .. j + 15`` and at no other position, where a full layer's moves
    every later one."""
    cfg, kind, lp, x = _one_layer(built, 1)
    assert kind == ("S", 6) and cfg.window == 16
    j = 7
    moved = x.at[:, j].add(1.0)

    @jax.jit
    def outputs(lp, full_lp):
        def attn(lp, kind, x):
            y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            return laguna.attention(cfg, None, kind, lp, y)
        return (attn(lp, kind, moved) - attn(lp, kind, x),
                attn(full_lp, ("F", 4), moved) - attn(full_lp, ("F", 4), x))

    window, full = outputs(lp, _one_layer(built, 0)[2])
    change = np.abs(np.asarray(window)).max(-1)             # (b, s)
    assert (change[:, j:j + 16] > 1e-6).all()
    assert (change[:, :j] == 0).all() and (change[:, j + 16:] == 0).all()
    change = np.abs(np.asarray(full)).max(-1)
    assert (change[:, j:] > 1e-6).all() and (change[:, :j] == 0).all()


def test_the_full_layers_rotary_is_yarns_on_half_a_head_with_the_factor():
    """Channels 0-63 of a head turned by yarn's blended frequencies, cos
    and sin times the stated factor; channels 64-127 as they are."""
    cfg = laguna.LagunaConfig()
    inv_freq, magnitude = cfg.rotary("F")
    assert inv_freq.shape == (32,) and magnitude == 1.4158883083359672
    # the config states as a number what yarn's rule gives for its factor
    assert magnitude == pytest.approx(yarn_mscale(64.0, 1.0), rel=1e-12)
    assert magnitude == pytest.approx(0.1 * math.log(64.0) + 1.0, rel=1e-12)
    want = yarn_inv_freq(64, 5e5, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    np.testing.assert_allclose(
        inv_freq, yarn_frequencies(64, 5e5, 64.0, 4096, 64.0, 1.0))
    plain = np.asarray(rope_frequencies(64, 5e5))
    ratio = plain / np.asarray(inv_freq)
    # the fast pairs keep their frequency, the slow ones take a 64th,
    # some lie between: the blend is live
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(64.0)
    assert ((ratio > 1.001) & (ratio < 63.9)).sum() >= 3
    x = jax.random.normal(jax.random.key(0), (1, 40, 3, 128))
    positions = jnp.arange(5000, 5040, dtype=jnp.int32)[None]
    got = np.asarray(apply_rope(x, positions, inv_freq, magnitude))
    angles = np.asarray(positions[0], np.float64)[:, None] * want[None]
    cos = (np.cos(angles) * magnitude)[None, :, None, :]
    sin = (np.sin(angles) * magnitude)[None, :, None, :]
    x = np.asarray(x, np.float64)
    x1, x2 = x[..., :32], x[..., 32:64]
    np.testing.assert_allclose(got[..., :32], x1 * cos - x2 * sin, atol=2e-3)
    np.testing.assert_allclose(got[..., 32:64], x2 * cos + x1 * sin, atol=2e-3)
    np.testing.assert_array_equal(
        got[..., 64:], x[..., 64:].astype(np.float32))
    # a turned pair is as long as the factor makes it, no longer
    np.testing.assert_allclose(
        np.hypot(got[..., :32], got[..., 32:64]),
        magnitude * np.hypot(x1, x2), rtol=1e-4)


def test_the_window_layers_rotary_is_plain_on_the_whole_head():
    cfg = laguna.LagunaConfig()
    inv_freq, magnitude = cfg.rotary("S")
    assert inv_freq.shape == (64,) and magnitude == 1.0
    np.testing.assert_allclose(
        inv_freq, 1e4 ** (-np.arange(0, 128, 2) / 128.0), rtol=1e-6)
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 128))
    positions = jnp.arange(300, 308, dtype=jnp.int32)[None]
    got = np.asarray(apply_rope(x, positions, inv_freq, magnitude))
    angles = np.arange(300, 308)[:, None] * np.asarray(inv_freq, np.float64)
    cos, sin = (f(angles)[None, :, None, :] for f in (np.cos, np.sin))
    x = np.asarray(x, np.float64)
    np.testing.assert_allclose(
        got, np.concatenate([x[..., :64] * cos - x[..., 64:] * sin,
                             x[..., 64:] * cos + x[..., :64] * sin], -1),
        atol=1e-4)
    assert np.abs(got[..., 64:] - x[..., 64:]).max() > 0.1  # all 128 turn


def test_the_gate_is_a_sigmoid_a_head_of_the_layers_normed_input(
        built, config):
    """With ``W_g`` zero every head's output is halved; with the program's
    ``W_g`` the sublayer is the plain form's, which without its gate is
    another function."""
    cfg, kind, lp, x = _one_layer(built, 4)
    assert kind == ("F", 4)
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps)

    @jax.jit
    def outputs(lp, y):
        ungated = family._ref_attention_core(
            *family._ref_qkv(y, lp, config, FULL), None).reshape(
                *y.shape[:2], -1) @ lp["wo"]
        return (laguna.attention(cfg, None, kind, lp, y),
                laguna.attention(
                    cfg, None, kind, dict(lp, w_g=jnp.zeros_like(lp["w_g"])),
                    y),
                family._ref_attention(y, lp, config, FULL), ungated,
                laguna.head_gate(lp, y, cfg.dtype))

    got, halved, (want, want_gate), ungated, gate = outputs(lp, y)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(halved, 0.5 * ungated, atol=2e-5)
    np.testing.assert_allclose(gate, want_gate, atol=1e-6)
    assert gate.shape == (2, 48, 4)
    assert float(jnp.max(jnp.abs(got - ungated))) > 1e-2
    spread = np.asarray(gate)
    assert spread.min() < 0.3 and spread.max() > 0.7      # the gate weighs


@pytest.mark.parametrize("layer,kind", [(4, ("F", 4)), (1, ("S", 6))])
def test_a_recomputed_block_keeps_the_flash_forwards_pair(
        built, layer, kind):
    """A full and a window block under the family's own recompute keep
    the flash forward's output and ``lse`` and none of q, k, v (gauge
    ``attn.out_kept``); loss and gradients are the block's own."""
    from jax._src.ad_checkpoint import saved_residuals

    fam, params, tokens = built
    cfg = dataclasses.replace(fam.cfg, remat=True)
    assert cfg.kinds[layer] == kind
    lp = laguna.layer_params(cfg, params, layer)
    x = params["embed"][tokens]
    b, s, _ = x.shape
    h, kvh, hd = kind[1], cfg.n_kv_heads, cfg.head_dim
    trace.gauge("attn.out_kept", 0)
    fn = laguna._block_fn(cfg, None, kind)
    saved = [tuple(aval.shape) for aval, _ in saved_residuals(fn, lp, x)]
    assert (b, s, h, hd) in saved and (b, h, s) in saved
    assert (b, s, kvh, hd) not in saved and saved.count((b, s, h, hd)) == 1

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda lp, x: jnp.sum(fn(lp, x) ** 2), argnums=(0, 1)))(lp, x)

    got = grads(fn)
    assert trace.gauges()["attn.out_kept"] == 1
    want = grads(functools.partial(laguna.block, cfg, None, kind))
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_eight_shares_and_the_shared_expert_once_add_up(config, mesh):
    """Eight chips share a layer's 16 experts, two each. The routed parts
    the eight shares compute, with the shared expert (which every chip
    computes alike) counted once, are the uncut layer of the plain
    form."""
    whole_cfg = dict(config, num_experts=16, published_num_experts=16,
                     num_experts_per_tok=4)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = laguna.layer_params(whole.cfg, params, 1)
    u = jax.random.normal(jax.random.key(3), (2, 24, whole.cfg.dim))

    @jax.jit
    def uncut(lp):
        return (family._ref_routed(u, lp, whole_cfg)[0]
                + family._swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"]))

    want = uncut(lp)
    # the program's whole layer is that too
    np.testing.assert_allclose(
        jax.jit(lambda lp: moe.moe_mlp(whole.cfg.as_moe(), lp, u)[0])(lp),
        want, atol=5e-5)
    routed = {n: w for n, w in lp.items() if not n.startswith("ws_")}
    total = jax.jit(lambda lp: moe._shared_expert(lp, u))(lp)
    assert float(jnp.max(jnp.abs(total))) > 1e-2            # it weighs
    for first in range(0, 16, 2):
        share = dict(routed)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out = jax.jit(lambda share: moe.moe_mlp(share_cfg, share, u)[0])(
            share)
        total = total + out
        # and one share alone is the plain form's share
        ref_share = jax.jit(lambda share: family._ref_routed(
            u, share, whole_cfg, first)[0])(share)
        np.testing.assert_allclose(out, ref_share, atol=2e-5)
        assert float(jnp.max(jnp.abs(out))) > 1e-2     # each share weighs
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_live_rows_count_the_pairs_that_chose_a_held_expert(built):
    """``live_rows`` against the count layer by layer: the router on the
    feed-forward's normed input, the residual carried through the whole
    block; the dense layer has no row to count."""
    fam, params, tokens = built
    cfg = fam.cfg
    got = np.asarray(jax.jit(
        lambda p, t: laguna.live_rows(p, t, cfg))(params, tokens))

    @jax.jit
    def counted(params, tokens):
        x = params["embed"][tokens].astype(cfg.dtype)
        want = []
        for l, kind in enumerate(cfg.kinds):
            lp = laguna.layer_params(cfg, params, l)
            x, u = laguna.attention_half(cfg, None, kind, lp, x)
            if "router" in lp:
                top_e = moe.route(cfg.as_moe(), lp["router"],
                                  u.reshape(-1, cfg.dim))[2]
                want.append(jnp.sum(top_e < cfg.experts_held))
            x = laguna.feed_forward_half(cfg, None, lp, x, u)
        return want

    want = [int(n) for n in counted(params, tokens)]
    assert got.tolist() == want
    assert got.dtype == np.int32 and got.shape == (cfg.n_layers - 1,)
    # not the uniform expectation the gauge moe.rows_held gives
    pairs = tokens.size * cfg.experts_per_token
    assert 0 < got.min() and got.max() < pairs and len(set(want)) > 1
