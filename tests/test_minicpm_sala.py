"""The ``minicpm_sala`` family on the CPU at a tiny size: the program
against the plain reference (``benchmarks/families/minicpm_sala.py``) on
seeded weights, its layout, MiniCPM's scalings, the two branches of the
sparse layer, and what ``validate_for_mesh`` refuses."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks.families import minicpm_sala as family
from dlrover_tpu.models import minicpm_sala, stack
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention, rms_norm
from dlrover_tpu.parallel import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tiny-cpu-minicpm-sala.json")) as f:
        return json.load(f)


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


# ---------------------------------------------------------------------------
# The program against the reference
# ---------------------------------------------------------------------------

def test_loss_and_gradients_are_the_references(fam, params, config):
    """Twice ``dense_len`` (every query chooses): the loss and every
    leaf's gradient. (The dense branch:
    ``test_within_dense_len_the_sparse_layer_is_causal_attention``.)"""
    tokens = _tokens(fam.cfg, 64)
    assert fam.cfg.sparse_at(64)
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, t: family.plain_loss(p, t, config)))(params, tokens)
    assert abs(float(loss) - float(want)) < 2e-6
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(ref).max())
        assert scale > 0, path
        np.testing.assert_allclose(got, ref, atol=2e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


# (every limit of the family's hook at 64 positions, and wrong programs
# failing it: benchmarks/tests/test_minicpm_sala_reference.py, by hand;
# the rehearsal cell tiny-cpu-minicpm-sala-steady runs the hook too)


def test_the_first_loss_is_the_one_the_scalings_give(fam, params, config):
    from benchmarks.harness import minicpm_sala_flops

    want = minicpm_sala_flops.expected_first_loss(config)
    m = config["hidden_size"] / config["dim_model_base"]
    assert want == pytest.approx(
        np.log(256) + 64 * (0.02 / m) ** 2 / 2)
    assert fam.expected_first_loss == want
    loss = float(jax.jit(fam.loss_fn)(params, _tokens(fam.cfg, 32)))
    assert abs(loss - want) < 0.1
    # the published sizes: ln 18362 + 4096 x (0.02 / 16)^2 / 2
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-9b-d4-1chip.json")) as f:
        full = json.load(f)
    assert minicpm_sala_flops.expected_first_loss(full) == pytest.approx(
        np.log(18362) + 0.0032)


def test_the_sliced_vocabulary_is_the_references_slice(config):
    """A model that holds ids 0 .. V/4 - 1: the program on the sliced
    table and head is the reference on the whole model's rows and columns
    of that slice."""
    whole = minicpm_sala.MiniCPMSalaConfig.tiny(
        la_slopes=tuple(map(tuple, config["assumed"]["lightning_slopes"])))
    held = whole.vocab_size // 4
    sliced = minicpm_sala.MiniCPMSalaConfig(
        **{**whole.__dict__, "vocab_size": held})
    params = minicpm_sala.init_params(whole, jax.random.key(5))
    mine = {**params, "embed": params["embed"][:held],
            "lm_head": params["lm_head"][:, :held]}
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(
        jnp.shape, minicpm_sala.init_params(sliced, jax.random.key(5)))
    tokens = _tokens(sliced, 64)
    got = jax.jit(lambda p, t: minicpm_sala.loss_fn(p, t, sliced))(
        mine, tokens)
    want = jax.jit(lambda p, t: family.plain_loss(
        p, t, {**config, "vocab_size": held}))(mine, tokens)
    assert abs(float(got) - float(want)) < 2e-6


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def _layer_input(cfg, params, layer, seq=64):
    x = jax.random.normal(jax.random.key(6), (2, seq, cfg.dim)) * 0.3
    lp = minicpm_sala.layer_params(cfg, params, layer)
    return lp, rms_norm(x, lp["attn_norm"], cfg.norm_eps)


def test_the_pallas_forms_of_both_layers_are_the_xla_forms(fam, params):
    cfg = fam.cfg
    for layer, fn in ((0, minicpm_sala.sparse_layer),
                      (1, minicpm_sala.lightning_layer)):
        lp, y = _layer_input(cfg, params, layer)
        got, want = (jax.jit(jax.value_and_grad(
            lambda y: jnp.sum(jnp.sin(fn(cfg, lp, y, interpret=interpret)))))(
                y) for interpret in (True, False))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-6)


def test_within_dense_len_the_sparse_layer_is_causal_attention(fam, params):
    cfg = fam.cfg
    lp, y = _layer_input(cfg, params, 0, seq=32)
    assert not cfg.sparse_at(32)
    got = jax.jit(functools.partial(minicpm_sala.sparse_layer, cfg))(lp, y)

    @jax.jit
    def causal(lp, y):
        q, k, v, gate = minicpm_sala.sparse_operands(cfg, lp, y)
        out = attention.mha_reference(q, k, v, causal=True)
        return (out * jax.nn.sigmoid(gate)).reshape(2, 32, -1) @ lp["w_o"]

    np.testing.assert_allclose(got, causal(lp, y), atol=1e-6)


def test_the_choice_takes_no_gradient(fam, params):
    """The block scores depend on q and k; the layer's gradient is that of
    attention under a fixed choice."""
    cfg = fam.cfg
    lp, y = _layer_input(cfg, params, 0)
    q, k, _, _ = jax.jit(functools.partial(
        minicpm_sala.sparse_operands, cfg))(lp, y)
    choose = functools.partial(minicpm_sala.choose_blocks, cfg)
    chosen = jax.jit(choose)(q, k)
    assert chosen.dtype == jnp.int8 and chosen.shape == (2, 2, 64, 8)
    assert (np.asarray(chosen).sum(-1) == np.minimum(
        np.arange(64) // 8 + 1, 4)).all()
    grads = jax.jit(jax.grad(lambda q, k: jnp.sum(
        choose(q, k).astype(jnp.float32)), (0, 1)))(q, k)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads)


def test_remat_keeps_the_named_residuals_and_the_gradient(fam, params):
    cfg = fam.cfg
    remat = minicpm_sala.MiniCPMSalaConfig(**{**cfg.__dict__, "remat": True})
    tokens = _tokens(cfg, 64)
    trace.gauge("attn.out_kept", 0)
    trace.gauge("la.state_kept", 0)
    want = jax.jit(jax.grad(
        lambda p, t: minicpm_sala.loss_fn(p, t, cfg)))(params, tokens)
    got = jax.jit(jax.grad(
        lambda p, t: minicpm_sala.loss_fn(p, t, remat)))(params, tokens)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-7)
    gauges = trace.gauges()
    assert gauges["attn.out_kept"] == 1 and gauges["la.state_kept"] == 1


# ---------------------------------------------------------------------------
# Layout, scalings, gauges
# ---------------------------------------------------------------------------

def test_the_layers_are_runs_of_like_mixers():
    cfg = minicpm_sala.MiniCPMSalaConfig()
    assert cfg.n_layers == 32 and cfg.kinds.count("S") == 8
    assert cfg.pattern_string == "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"
    assert [(p.kinds[0], p.repeats) for p in cfg.layout] == [
        ("S", 1), ("L", 8), ("S", 1), ("L", 6), ("S", 2), ("L", 4),
        ("S", 1), ("L", 6), ("S", 3)]
    # the published slopes: 24 rows of 32, the family's formula
    assert len(cfg.slopes) == 24 and cfg.slopes[0][0] == pytest.approx(
        2 ** -0.25 * (1 - 1 / 31 + 1e-5))
    cut = minicpm_sala.MiniCPMSalaConfig(
        mixer_types=cfg.mixer_types[:4], vocab_size=18362)
    assert cut.layout == (stack.Part(("S",), 1), stack.Part(("L",), 3))
    assert cut.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert minicpm_sala.param_count(cut) == 1259853184


def test_the_configuration_states_the_formulas_slopes():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-9b-d4-1chip.json")) as f:
        full = json.load(f)
    stated = full["assumed"]["lightning_slopes"]
    assert np.shape(stated) == (3, 32)
    for row, layer in zip(stated, (1, 2, 3)):
        np.testing.assert_allclose(
            row, minicpm_sala.lightning_slopes(32, layer, 32), rtol=1e-12)
    assert max(map(max, stated)) == pytest.approx(0.8138, abs=1e-4)
    assert min(map(min, stated)) == pytest.approx(0.0035, abs=1e-4)
    # no width differs from the published config
    cfg = family.build(full, build_mesh(
        MeshConfig().resolve(1), devices=jax.devices()[:1])).cfg
    published = minicpm_sala.MiniCPMSalaConfig()
    for name in ("dim", "ffn_dim", "n_heads", "n_kv_heads", "head_dim",
                 "la_heads", "la_head_dim", "blk_kernel", "blk_stride",
                 "blk_size", "blk_topk", "blk_init", "blk_window",
                 "dense_len", "rope_theta", "scale_emb", "scale_depth",
                 "dim_model_base", "norm_eps", "published_layers"):
        assert getattr(cfg, name) == getattr(published, name), name
    assert cfg.mixer_types == published.mixer_types[:4]


def test_a_lightning_layer_reads_its_own_slopes(fam, params):
    cfg = fam.cfg
    assert cfg.pattern_string == "SLLS"
    rows = [minicpm_sala.layer_params(cfg, params, i).get("slopes")
            for i in range(4)]
    assert rows[0] is None and rows[3] is None
    np.testing.assert_allclose(rows[1], cfg.slopes[0], rtol=1e-6)
    np.testing.assert_allclose(rows[2], cfg.slopes[1], rtol=1e-6)
    assert "slopes" not in str(jax.tree.structure(params))


@pytest.mark.parametrize("field,value", [
    ("scale_emb", 1.0), ("scale_depth", 2.8), ("dim_model_base", 64),
    ("published_layers", 16)])
def test_each_scaling_is_in_the_forward(fam, params, field, value):
    cfg = fam.cfg
    other = minicpm_sala.MiniCPMSalaConfig(**{**cfg.__dict__, field: value})
    tokens = _tokens(cfg, 64)
    forward = jax.jit(minicpm_sala.forward_layers, static_argnums=2)
    loss = jax.jit(minicpm_sala.loss_fn, static_argnums=2)
    a = forward(params, tokens, cfg)
    b = forward(params, tokens, other)
    if field == "dim_model_base":       # the head's divisor only
        np.testing.assert_array_equal(a, b)
        assert other.head_divisor == 1.0 and cfg.head_divisor == 4.0
        assert float(loss(params, tokens, cfg)) != float(
            loss(params, tokens, other))
    else:
        assert float(jnp.abs(a - b).max()) > 1e-4


def test_the_embedding_is_scaled_and_the_branches_by_the_published_depth(
        fam, params):
    cfg = fam.cfg
    tokens = _tokens(cfg, 64)
    none = minicpm_sala.MiniCPMSalaConfig(**{
        **cfg.__dict__, "scale_depth": 0.0})
    np.testing.assert_allclose(
        jax.jit(minicpm_sala.forward_layers, static_argnums=2)(
            params, tokens, none),
        12.0 * params["embed"][tokens], rtol=1e-6)
    assert cfg.residual_scale == pytest.approx(1.4 / 2.0)


def test_the_gauges_say_what_was_built(fam, params):
    cfg = fam.cfg
    jax.eval_shape(lambda p, t: minicpm_sala.loss_fn(p, t, cfg), params,
                   _tokens(cfg, 64))
    g = trace.gauges()
    assert (g["attn.blk_size"], g["attn.blk_topk"], g["attn.blk_forced"],
            g["attn.blk_dense_len"], g["attn.blk_sparse"]) == (8, 4, 3, 32, 1)
    assert g["attn.blk_pairs_share"] == pytest.approx(1440 / 2080)
    assert (g["la.heads"], g["la.chunk"], g["la.kernel"]) == (4, 16, 0)
    assert g["la.slope_max"] == pytest.approx(max(map(max, cfg.slopes)))
    assert g["la.slope_min"] == pytest.approx(min(map(min, cfg.slopes)))
    assert g["mup.residual_scale"] == pytest.approx(0.7)
    assert trace.text("layers.pattern") == "SLLS"
    for scope in ("la_proj", "la_norm_rope", "la_chunk", "la_out",
                  "blk_pool", "blk_score", "blk_pick", "sattn_gate",
                  "attn_proj", "dense_mlp"):
        assert scope in trace.scopes(), scope
    jax.eval_shape(lambda p, t: minicpm_sala.loss_fn(p, t, cfg), params,
                   _tokens(cfg, 32))
    g = trace.gauges()
    assert g["attn.blk_sparse"] == 0 and g["attn.blk_pairs_share"] == 1.0


def test_live_rows_counts_the_tiles_the_choice_touches(fam, params):
    cfg = fam.cfg
    rows = np.asarray(fam.live_rows(params, _tokens(cfg, 64, batch=1)))
    # two sparse layers; 64 positions are one tile of the forward's walk
    assert rows.tolist() == [1, 1]


# ---------------------------------------------------------------------------
# What it refuses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("axis,word", [
    ("sp", "recurrent state"), ("tp", "none is split"), ("pp", "runs")])
def test_validate_for_mesh_refuses_what_it_cannot_do(axis, word):
    cfg = minicpm_sala.MiniCPMSalaConfig.tiny()
    shape = {"dp": 1, "pp": 1, "fsdp": 1, "ep": 1, "sp": 1, "tp": 1,
             axis: 2}
    devices = np.array(jax.devices()[:1] * 2).reshape(
        [shape[a] for a in shape])
    mesh = Mesh(devices, tuple(shape))
    with pytest.raises(ValueError, match=word):
        minicpm_sala.validate_for_mesh(cfg, mesh, batch=2)


def test_validate_for_mesh_takes_data_parallelism_and_holds_the_batch():
    cfg = minicpm_sala.MiniCPMSalaConfig.tiny()
    shape = {"dp": 2, "pp": 1, "fsdp": 1, "ep": 1, "sp": 1, "tp": 1}
    mesh = Mesh(np.array(jax.devices()[:1] * 2).reshape(
        list(shape.values())), tuple(shape))
    minicpm_sala.validate_for_mesh(cfg, mesh, batch=4)
    with pytest.raises(ValueError, match="does not divide"):
        minicpm_sala.validate_for_mesh(cfg, mesh, batch=3)


@pytest.mark.parametrize("kw,word", [
    (dict(mixer_types=("minicpm4", "mamba")), "mixer_types"),
    (dict(n_heads=3), "group"),
    (dict(la_slopes=((0.1, 0.2, 0.3, 0.4),)), "la_slopes"),
    (dict(la_slopes=((0.1, 0.2, 0.3, -0.4),) * 2), "la_slopes"),
])
def test_a_configuration_that_names_no_model_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        minicpm_sala.MiniCPMSalaConfig.tiny(**kw)


def test_the_family_refuses_a_config_of_another_model(config):
    mesh = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    for key, value in (("attn_use_rope", True), ("qk_norm", False),
                       ("model_type", "minicpm")):
        with pytest.raises(ValueError, match=key):
            family.build({**config, key: value}, mesh)
