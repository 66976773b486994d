"""The ``granite_hybrid`` family on the CPU at a tiny size: the program
against the plain reference (``benchmarks/families/granite_hybrid.py``) on
seeded weights, **the shares add up** (heads, experts, vocabulary), the
tied table's gradient, Granite's multipliers, and what
``validate_for_mesh`` refuses."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import Mesh

from benchmarks.families import granite_hybrid as family
from benchmarks.harness import granite_hybrid_flops
from dlrover_tpu.models import granite_hybrid, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import rms_norm, ssd
from dlrover_tpu.parallel import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _config("tiny-cpu-granite-hybrid")


@pytest.fixture(scope="module")
def fam(config):
    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    return family.build(config, mesh)


@pytest.fixture(scope="module")
def params(fam):
    return fam.init_params(jax.random.key(3))


def _tokens(cfg, seq, key=4, batch=2):
    return jax.random.randint(jax.random.key(key), (batch, seq), 0,
                              cfg.vocab_size, dtype=jnp.int32)


def _close(got, want, tol=2e-5):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol * max(scale, 1e-30))


# ---------------------------------------------------------------------------
# The program against the reference
# ---------------------------------------------------------------------------

def test_loss_and_gradients_are_the_references(fam, params, config):
    """40 tokens: two chunks of 16 and a padded one; every leaf, the
    convolution's bias, ``A_log``, ``dt_bias`` and ``D`` among them."""
    tokens = _tokens(fam.cfg, 40)
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: family.plain_loss(p, t, config)))(params, tokens)
    assert abs(float(loss) - float(want)) < 2e-6
    assert "lm_head" not in params
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, path
        np.testing.assert_allclose(got, ref, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_first_loss_has_the_tied_term(fam, config):
    """The formula against the plain reference at init on many tokens (at
    a logits scaling that makes the tied term large at this size), and the
    cell's number."""
    loud = {**config, "logits_scaling": 0.5}
    want = granite_hybrid_flops.expected_first_loss(loud)
    untied = np.log(256) + 64 * (0.02 / 0.5) ** 2 / 2
    assert 0.03 < want - untied < 0.06
    plain_loss = jax.jit(functools.partial(family.plain_loss, config=loud))
    losses = [float(plain_loss(
        fam.init_params(jax.random.key(seed)), _tokens(fam.cfg, 64, seed, 8)))
        for seed in range(4)]
    assert abs(np.mean(losses) - want) < 0.015
    assert abs(np.mean(losses) - untied) > 0.025
    cell = _config("granite-4.0-h-small-ep8-1chip")
    assert granite_hybrid_flops.expected_first_loss(cell) == pytest.approx(
        9.4529, abs=2e-4)
    assert granite_hybrid_flops.expected_first_loss(cell) - (
        np.log(12544) + 0.0032) == pytest.approx(0.0127, abs=3e-4)


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(fam, params):
    cfg, tokens = fam.cfg, _tokens(fam.cfg, 32)

    def loss(lookup, head):
        x = granite_hybrid.forward_layers({**params, "embed": lookup},
                                          tokens, cfg)
        return granite_hybrid.head_loss({**params, "embed": head}, x,
                                        tokens, cfg)

    table = params["embed"]
    by_lookup, by_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    whole = jax.jit(jax.grad(lambda p: fam.loss_fn(p, tokens)))(
        params)["embed"]
    assert float(jnp.abs(by_lookup).max()) > 0
    assert float(jnp.abs(by_head).max()) > 0
    _close(whole, by_lookup + by_head, 1e-5)
    # a row no token looked up has the head's part alone
    unseen = np.setdiff1d(np.arange(cfg.vocab_size), np.asarray(tokens))
    assert len(unseen) and not np.asarray(by_lookup)[unseen].any()
    assert np.asarray(whole)[unseen].any()


def test_the_loss_is_the_head_over_the_last_residual(fam, params):
    """``loss_fn`` is ``head_loss`` of ``forward_layers`` (the reference
    hook reads both from one program); a target below zero is no target."""
    cfg, tokens = fam.cfg, _tokens(fam.cfg, 32)
    x = jax.jit(functools.partial(
        granite_hybrid.forward_layers, cfg=cfg))(params, tokens)
    head_loss = jax.jit(functools.partial(granite_hybrid.head_loss, cfg=cfg))
    whole = head_loss(params, x, tokens)
    assert float(whole) == pytest.approx(
        float(jax.jit(fam.loss_fn)(params, tokens)), rel=1e-6)
    padded = head_loss(params, x, tokens.at[:, -8:].set(-1))
    short = head_loss(params, x[:, :-8], tokens[:, :-8])
    assert float(padded) == pytest.approx(float(short), rel=1e-6)
    assert abs(float(padded) - float(whole)) > 1e-4


# ---------------------------------------------------------------------------
# The shares add up
# ---------------------------------------------------------------------------

UNCUT = dict(mamba_heads=8, n_heads=4, n_kv_heads=2, n_experts=8)


def _mamba_share(lp, cfg, first, held):
    """The held heads' columns of W_in's z, x and dt parts, rows of W_out,
    channels of the convolution and the norm; B and C whole."""
    p, n, h = cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_heads
    di = h * p
    mine = np.arange(first * p, (first + held) * p)
    bc = np.arange(2 * di, 2 * di + 2 * n)
    cols = np.concatenate([mine, di + mine, bc,
                           2 * di + 2 * n + np.arange(first, first + held)])
    conv = np.concatenate([mine, di + np.arange(2 * n)])
    heads = slice(first, first + held)
    return {**lp, "w_in": lp["w_in"][:, cols], "conv_w": lp["conv_w"][conv],
            "conv_b": lp["conv_b"][conv], "a_log": lp["a_log"][heads],
            "dt_bias": lp["dt_bias"][heads], "d_skip": lp["d_skip"][heads],
            "m_norm": lp["m_norm"][mine], "w_out": lp["w_out"][mine]}


@pytest.fixture(scope="module")
def uncut():
    cfg = granite_hybrid.GraniteHybridConfig.tiny(**UNCUT)
    params = granite_hybrid.init_params(cfg, jax.random.key(5))
    # every leaf its own draw, the biases and the per-head leaves too
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(6), len(leaves))
    params = jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])
    y = jax.random.normal(jax.random.key(7), (2, 32, cfg.dim))
    return cfg, params, y


def test_a_head_share_is_the_uncut_mixers_slice_up_to_the_norm(uncut):
    """Each of four shares of two Mamba heads: the scan's output is the
    uncut scan's slice; the shares' sums of squares add to the whole's
    (what a tensor-parallel run of the norm would exchange, one float a
    token), and a share's own statistic is its own sum over its own
    count, which is **not** the whole's."""
    cfg, params, y = uncut
    lp = granite_hybrid.layer_params(cfg, params, 0)

    @functools.partial(jax.jit, static_argnums=0)
    def scanned(cfg, lp):
        operands, z = granite_hybrid.mamba_operands(cfg, lp, y)
        scan = ssd.ssd(*operands, chunk=16).reshape(2, 32, -1)
        return (scan, *granite_hybrid.gated(scan, z))

    whole, g, stat = scanned(cfg, lp)
    total, p = 0.0, cfg.mamba_head_dim
    assert dataclasses.replace(cfg, mamba_heads_held=2).inner == 2 * p
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, mamba_heads_held=2,
                                    first_mamba_head=first)
        mine = _mamba_share(lp, cfg, first, 2)
        scan, g_mine, stat_mine = scanned(share, mine)
        _close(scan, whole[..., first * p:(first + 2) * p], 1e-5)
        _close(stat_mine * 2 * p, jnp.sum(
            g[..., first * p:(first + 2) * p] ** 2, -1, keepdims=True), 1e-5)
        total = total + stat_mine * 2 * p
        assert float(jnp.max(jnp.abs(stat_mine / stat - 1))) > 0.05
        # and the share's mixer is its own norm over its own channels
        want = (g_mine * jax.lax.rsqrt(stat_mine + cfg.norm_eps)
                * mine["m_norm"]) @ mine["w_out"]
        _close(jax.jit(functools.partial(
            granite_hybrid.mamba_mixer, share))(mine, y), want, 1e-5)
    _close(total, stat * cfg.inner, 1e-5)


def test_the_attention_shares_add_up_to_the_uncut_layer(uncut):
    """Two shares of a key head and its two query heads: attention has no
    statistic across heads, so the shares' outputs sum to the uncut
    layer's."""
    cfg, params, y = uncut
    lp = granite_hybrid.layer_params(cfg, params, 2)
    assert cfg.kinds[2] == "A"
    mixer = jax.jit(granite_hybrid.attention_mixer, static_argnums=0)
    whole = mixer(cfg, lp, y)
    hd, total = cfg.head_dim, 0.0
    for first in (0, 2):
        share = dataclasses.replace(cfg, heads_held=2, first_head=first)
        q = slice(first * hd, (first + 2) * hd)
        kv = slice(first // 2 * hd, (first // 2 + 1) * hd)
        mine = {**lp, "w_q": lp["w_q"][:, q], "w_o": lp["w_o"][q],
                "w_k": lp["w_k"][:, kv], "w_v": lp["w_v"][:, kv]}
        total = total + mixer(share, mine, y)
    _close(total, whole, 1e-5)


def test_the_expert_shares_and_the_shared_expert_once_are_the_layer(uncut):
    """Four shares of two experts: their routed parts, with the shared
    expert counted once, are the uncut expert layer."""
    cfg, params, y = uncut
    lp = granite_hybrid.layer_params(cfg, params, 1)
    layer = jax.jit(moe.moe_mlp, static_argnums=0)
    whole = layer(cfg.as_moe(), lp, y)[0]
    routed = {k: v for k, v in lp.items() if not k.startswith("ws_")}
    total = jax.jit(moe._shared_expert)(lp, y)
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, experts_held=2, first_expert=first)
        mine = {**routed, **{k: routed[k][first:first + 2]
                             for k in ("w_gate", "w_up", "w_down")}}
        total = total + layer(share.as_moe(), mine, y)[0]
    _close(total, whole, 1e-5)


def test_the_vocabulary_slice_is_a_smaller_vocabulary(fam, params):
    """A table of the first half of the rows, ids drawn from it: the loss
    is the cross-entropy over the slice's logits alone."""
    cfg = dataclasses.replace(fam.cfg, vocab_size=128)
    sliced = {**params, "embed": params["embed"][:128]}
    tokens = _tokens(cfg, 32)

    @jax.jit
    def plain(sliced, tokens):
        x = granite_hybrid.head_input(
            cfg, sliced, granite_hybrid.forward_layers(sliced, tokens, cfg))
        logp = jax.nn.log_softmax(x[:, :-1] @ sliced["embed"].T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    want = plain(sliced, tokens)
    assert float(jax.jit(functools.partial(granite_hybrid.loss_fn, cfg=cfg))(
        sliced, tokens)) == pytest.approx(float(want), abs=2e-6)


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def test_the_pallas_form_of_the_mamba_mixer_is_the_xla_form(fam, params):
    cfg = fam.cfg
    lp = granite_hybrid.layer_params(cfg, params, 0)
    y = jax.random.normal(jax.random.key(8), (2, 40, cfg.dim))

    def both(interpret):
        return jax.jit(jax.value_and_grad(lambda lp, y: jnp.sum(
            granite_hybrid.mamba_mixer(cfg, lp, y, interpret=interpret) ** 2),
            argnums=(0, 1)))(lp, y)

    (want, want_grads), (got, got_grads) = both(False), both(True)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        _close(a, b, 1e-4)


def test_the_gate_comes_before_the_norm_over_the_whole_width():
    y = jax.random.normal(jax.random.key(9), (2, 3, 8))
    z = jax.random.normal(jax.random.key(10), (2, 3, 8))
    w = jnp.arange(1.0, 9.0)
    g = y * jax.nn.silu(z)
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5) * w
    _close(granite_hybrid.gated_norm(y, z, w, 1e-5), want, 1e-6)
    after = rms_norm(y, w, 1e-5) * jax.nn.silu(z)
    assert float(jnp.max(jnp.abs(after - want))) > 0.1


def test_the_convolution_has_a_bias_under_the_silu():
    x = jax.random.normal(jax.random.key(11), (1, 6, 3))
    w = jax.random.normal(jax.random.key(12), (3, 4))
    b = jnp.asarray([0.5, -1.0, 2.0])
    got = granite_hybrid.conv_bias_silu(x, w, b)
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(padded[:, i:i + 6] * w[:, i] for i in range(4)) + b)
    _close(got, want, 1e-6)


def test_remat_keeps_the_flash_pair_and_the_scan(fam, params):
    """The attention block keeps the flash pair, a Mamba block the scan's
    output and its chunks' states (``ssd.KEPT``); the gradient is the
    unrematerialised one."""
    cfg = dataclasses.replace(fam.cfg, remat=True)
    tokens = _tokens(cfg, 32)
    trace.gauge("ssm.state_kept", 0)
    want = jax.jit(jax.grad(lambda p: fam.loss_fn(p, tokens)))(params)
    got = jax.jit(jax.grad(
        lambda p: granite_hybrid.loss_fn(p, tokens, cfg)))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-5)
    assert trace.gauges()["ssm.state_kept"] == 1
    assert trace.gauges()["attn.out_kept"] == 1
    lp = granite_hybrid.layer_params(cfg, params, 0)
    x = jax.random.normal(jax.random.key(13), (2, 32, cfg.dim))
    kept = [tuple(a.shape) for a, _ in saved_residuals(
        granite_hybrid._block_fn(cfg, None, "M"), lp, x)]
    assert (2, 32 // cfg.mamba_chunk, cfg.held_mamba_heads,
            cfg.mamba_head_dim, cfg.mamba_state) in kept


def test_the_layout_is_one_period_of_ten_four_times():
    cfg = granite_hybrid.GraniteHybridConfig()
    part, = cfg.layout
    assert (len(part.kinds), part.repeats) == (10, 4)
    assert cfg.pattern_string[:10] == "MMMMMAMMMM"
    assert cfg.kinds.count("A") == 4 and cfg.inner == 8192
    assert (cfg.held_heads, cfg.held_kv_heads, cfg.group) == (32, 8, 4)
    cut = dataclasses.replace(cfg, layer_types=cfg.layer_types[:10],
                              mamba_heads_held=32, heads_held=8)
    assert (cut.layout[0].repeats, cut.inner, cut.held_kv_heads) == (
        1, 2048, 2)


def test_a_log_is_the_published_heads(fam):
    cfg = dataclasses.replace(fam.cfg, first_mamba_head=4)
    lp = granite_hybrid.layer_params(
        cfg, granite_hybrid.init_params(cfg, jax.random.key(0)), 0)
    _close(jnp.exp(lp["a_log"]), jnp.asarray([5.0, 6.0]), 1e-6)
    assert not np.asarray(lp["conv_b"]).any()
    assert np.asarray(lp["dt_bias"] == 1).all()
    assert np.asarray(lp["d_skip"] == 1).all()


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 6.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.25), ("logits_scaling", 4.0)])
def test_each_multiplier_is_in_the_forward(fam, params, field, value):
    tokens = _tokens(fam.cfg, 32)
    # branches that add something: the closing projections at sigma
    cfg = fam.cfg
    loud = granite_hybrid.init_params(
        dataclasses.replace(cfg, out_proj_std=None), jax.random.key(3))
    def read(cfg):
        return tuple(jax.jit(functools.partial(fn, cfg=cfg))(loud, tokens)
                     for fn in (granite_hybrid.forward_layers,
                                granite_hybrid.loss_fn))

    (x, loss), (x_moved, loss_moved) = read(cfg), read(
        dataclasses.replace(cfg, **{field: value}))
    if field == "logits_scaling":
        np.testing.assert_array_equal(x, x_moved)
        assert abs(float(loss_moved - loss)) > 1e-5
    else:
        assert float(jnp.max(jnp.abs(x_moved - x))) > 1e-4


def test_the_gauges_say_what_was_built(fam, params):
    jax.jit(fam.loss_fn)(params, _tokens(fam.cfg, 32))
    gauges = trace.gauges()
    for name, want in (("ssm.heads_held", 2), ("ssm.heads", 8),
                       ("ssm.state", 16), ("ssm.norm_channels", 32),
                       ("ssm.chunk", 16), ("ssm.kernel", 0),
                       ("attn.scale", 0.0625), ("attn.heads_held", 2),
                       ("layers.ssm", 3), ("layers.attention", 1),
                       ("layers.tied_head", 1), ("moe.experts_held", 2),
                       ("moe.top_k", 2), ("moe.shared_experts", 1)):
        assert gauges[name] == want, name
    assert trace.text("layers.pattern") == "MMAM"


def test_live_rows_counts_the_pairs_that_chose_a_held_expert(fam, params):
    tokens = _tokens(fam.cfg, 64)
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (4,) and rows.dtype == np.int32
    # 2 of 8 experts held, top 2 of 128 tokens: 64 under uniform routing
    assert (rows > 32).all() and (rows < 96).all()


@pytest.mark.parametrize("axis,word", [
    ("sp", "state"), ("tp", "not a mesh axis"), ("pp", "one scan")])
def test_validate_for_mesh_refuses_what_it_says(axis, word):
    cfg = granite_hybrid.GraniteHybridConfig.tiny()
    shape = {"dp": 1, "pp": 1, "fsdp": 1, "ep": 1, "sp": 1, "tp": 1,
             axis: 2}
    mesh = Mesh(np.array(jax.devices()[:1] * 2).reshape(
        [shape[a] for a in shape]), tuple(shape))
    with pytest.raises(ValueError, match=word):
        granite_hybrid.validate_for_mesh(cfg, mesh, batch=2)


def test_validate_for_mesh_takes_data_and_expert_parallelism():
    cfg = granite_hybrid.GraniteHybridConfig.tiny()
    shape = {"dp": 1, "pp": 1, "fsdp": 1, "ep": 2, "sp": 1, "tp": 1}
    mesh = Mesh(np.array(jax.devices()[:1] * 2).reshape(
        list(shape.values())), tuple(shape))
    granite_hybrid.validate_for_mesh(cfg, mesh, batch=4)
    with pytest.raises(ValueError, match="does not divide"):
        granite_hybrid.validate_for_mesh(cfg, mesh, batch=3)
    with pytest.raises(ValueError, match="experts held"):
        granite_hybrid.validate_for_mesh(
            dataclasses.replace(cfg, experts_held=3), mesh, batch=4)


@pytest.mark.parametrize("kw,word", [
    (dict(layer_types=("mamba", "lightning")), "layer_types"),
    (dict(n_heads=3), "group"),
    (dict(heads_held=1), "whole groups"),
    (dict(mamba_heads_held=3), "no share"),
    (dict(mamba_heads_held=2, first_mamba_head=3), "no share"),
    (dict(heads_held=2, first_head=4), "no share"),
])
def test_a_configuration_that_names_no_share_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        granite_hybrid.GraniteHybridConfig.tiny(**kw)


def test_the_family_refuses_a_config_of_another_model(config):
    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    for key, value in (("tie_word_embeddings", False), ("mamba_n_groups", 8),
                       ("position_embedding_type", "rope"),
                       ("mamba_conv_bias", False),
                       ("model_type", "granitemoe")):
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, mesh)
    with pytest.raises(ValueError, match="mamba_expand"):
        family.build({**config, "mamba_expand": 4}, mesh)


def test_the_cells_configuration_is_the_published_one_but_for_its_cut():
    cell = _config("granite-4.0-h-small-ep8-1chip")
    cfg = granite_hybrid.GraniteHybridConfig(**family._sizes(cell))
    published = granite_hybrid.GraniteHybridConfig()
    cut = {"vocab_size", "layer_types", "experts_held", "mamba_heads_held",
           "heads_held"}
    for field in dataclasses.fields(cfg):
        if field.name not in cut:
            assert getattr(cfg, field.name) == getattr(
                published, field.name), field.name
    assert cfg.layer_types == published.layer_types[:10]
    assert (cfg.vocab_size, cfg.as_moe().n_held, cfg.held_mamba_heads,
            cfg.held_heads, cfg.held_kv_heads) == (12544, 9, 32, 8, 2)
    assert granite_hybrid.param_count(cfg) == 1340223584
    for key in cell["reduced"]:
        assert "published_" + key in cell, key
