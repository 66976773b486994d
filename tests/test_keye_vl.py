"""The keye_vl family (``models/keye_vl.py``) at a tiny size on the CPU
against the plain form of its equations (``benchmarks/families/keye_vl.py``:
its own three-row rotary, explicit scores, a stable sort, a loop over the
experts), on a sequence longer than the selection with two image spans;
the two parts of the loss and where each one's gradient goes; the shares
of experts and ids tied to the uncut layer; what a block keeps; the
meshes the family refuses."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import keye_vl as family
from dlrover_tpu.models import keye_vl, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import dsa
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64


def _load(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("tiny-cpu-keye-vl.json")


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one, routers and indexers that spread their
    scores, projections that make attention and the experts weigh, so that
    every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 64))
    lp = dict(params["layers"])
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "idx_k_norm",
                 "idx_k_bias"):
        lp[name] = lp[name] + 0.3 * jax.random.normal(
            next(keys), lp[name].shape)
    for name, by in (("router", 40.0), ("wq", 6.0), ("wo", 30.0),
                     ("w_down", 100.0), ("idx_wq", 10.0), ("idx_ww", 60.0)):
        lp[name] = lp[name] * by
    return dict(params, layers=lp, lm_head=params["lm_head"] * 10.0)


@pytest.fixture(scope="module")
def built(config, mesh):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, SEQ), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def _terms(fam, config):
    return lambda p, t: keye_vl.loss_terms(
        p, t, fam.cfg, None, family.positions_for(config, *t.shape))


def _assert_grads_agree(grads, want_grads, tol=3e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        assert err <= tol * scale + 1e-7, (
            jax.tree_util.keystr(path), err, scale)


def test_the_positions_follow_the_published_rule(config):
    """Two segments of 16 text tokens and an image of 4 x 4: text counts up
    on all three rows, an image at ``p`` has rows ``p``, ``p`` + its
    patch's row, ``p`` + its patch's column, what follows starts at ``p +
    4``; at the cell's sizes the largest position is 12415 and a quarter
    of the tokens (but an image's first patch) turn by three positions."""
    rows = family.positions_for(config, 2, SEQ)
    assert rows.shape == (3, 2, SEQ) and rows.dtype == np.int32
    np.testing.assert_array_equal(rows[:, 0], rows[:, 1])
    one = rows[:, 0]
    np.testing.assert_array_equal(one[:, :16], np.tile(np.arange(16), (3, 1)))
    np.testing.assert_array_equal(one[0, 16:32], np.full(16, 16))
    np.testing.assert_array_equal(one[1, 16:32], 16 + np.arange(16) // 4)
    np.testing.assert_array_equal(one[2, 16:32], 16 + np.arange(16) % 4)
    np.testing.assert_array_equal(one[:, 32:48],
                                  np.tile(20 + np.arange(16), (3, 1)))
    listed = _load("keye-vl-2.0-30b-a3b-ep8-1chip.json")
    big = family.positions_for(listed, 1, 16384)
    assert big.max() == 12415
    assert np.mean(np.any(big != big[:1], axis=0)) == 4092 / 16384
    with pytest.raises(ValueError, match="segments"):
        family.positions_for(config, 1, 30)


def _both_parts(fn, params):
    """``[(value, grads) of CE, (value, grads) of L_I]`` in one program."""
    return jax.jit(lambda p: [jax.value_and_grad(
        lambda p: fn(p)[part])(p) for part in (0, 1)])(params)


@pytest.fixture(scope="module")
def parts(built, config):
    fam, params, tokens = built
    got = _both_parts(lambda p: _terms(fam, config)(p, tokens), params)
    assert trace.gauges()["attn.mrope_rows_differ"] == 30 / 64
    return got, _both_parts(
        lambda p: family.plain_loss(p, tokens, config), params)


@pytest.mark.parametrize("part", [0, 1], ids=["CE", "L_I"])
def test_each_part_of_the_loss_and_its_gradient_match_the_plain_form(
        built, parts, part):
    """Top-16 under 64 positions, two image spans."""
    fam = built[0]
    assert fam.cfg.index_topk == 16 < SEQ and fam.cfg.group == 2
    (got, grads), (want, want_grads) = parts[0][part], parts[1][part]
    assert abs(float(got) - float(want)) < 2e-5 * max(1.0, abs(float(want)))
    assert float(want) > 1e-3
    _assert_grads_agree(grads, want_grads, tol=1e-3)


def test_the_image_spans_move_the_loss_and_absent_positions_are_a_texts(
        built, parts):
    fam, params, tokens = built
    text = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), (3, 2, SEQ))
    absent, given = jax.jit(lambda p, t, pos: (
        keye_vl.loss_fn(p, t, fam.cfg),
        keye_vl.loss_fn(p, t, fam.cfg, None, pos)))(params, tokens, text)
    np.testing.assert_array_equal(absent, given)
    with_images = float(parts[0][0][0]) + float(parts[0][1][0])
    assert abs(float(absent) - with_images) > 1e-4
    jax.eval_shape(lambda p, t: keye_vl.loss_fn(p, t, fam.cfg), params, tokens)
    assert trace.gauges()["attn.mrope_rows_differ"] == 0


def _is_indexer(path) -> bool:
    return any(name in jax.tree_util.keystr(path)
               for name in keye_vl.INDEXER)


@pytest.mark.parametrize("part,own", [(0, False), (1, True)],
                         ids=["CE", "L_I"])
def test_the_two_parts_move_disjoint_parameters(parts, part, own):
    """``L_I``'s gradient reaches the indexer's parameters alone and CE's
    none of them, exactly (the stop-gradients, not a tolerance)."""
    grads = parts[0][part][1]
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        moved = bool(np.asarray(leaf).any())
        assert moved == (_is_indexer(path) == own), (
            part, jax.tree_util.keystr(path))


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"mrope_section": [4, 2, 2]}),
    ("sa_config", {"indexer_head_dim": 8, "indexer_num_heads": 2,
                   "indexer_num_kv_heads": 1, "topk": 12}),
    ("norm_topk_prob", False),
])
def test_each_config_term_moves_the_plain_form_and_the_program(
        built, parts, config, mesh, key, value):
    _, params, tokens = built
    changed = dict(config, **{key: value})
    fam = family.build(changed, mesh)
    got, want = (np.asarray(x) for x in jax.jit(lambda p: (
        _terms(fam, changed)(p, tokens),
        family.plain_loss(p, tokens, changed)))(params))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-7)
    base = [float(value) for value, _ in parts[1]]
    assert max(abs(a - b) for a, b in zip(want, base)) > 1e-4


def test_the_published_config_is_the_default():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(json.loads(line) for line in open(catalog)
                 if '"name": "Keye-VL-2.0-30B-A3B"' in line)
    assert keye_vl.KeyeVLConfig.from_hf(
        entry["config"], param_dtype=jnp.float32) == keye_vl.KeyeVLConfig()
    listed = _load("keye-vl-2.0-30b-a3b-ep8-1chip.json")
    assert listed["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert listed["published_" + key] == value, key
        else:
            assert listed[key] == value, key


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("tie_word_embeddings", True),
    ("attention_bias", True),
    ("sa_config", {"indexer_head_dim": 8, "indexer_num_heads": 2,
                   "indexer_num_kv_heads": 2, "topk": 16})])
def test_what_the_program_does_not_compute_is_refused(config, key, value):
    with pytest.raises(ValueError, match="models/keye_vl.py computes"):
        keye_vl.KeyeVLConfig.from_hf(dict(config, **{key: value}))


def test_param_count_of_the_listed_cut(mesh):
    """ISSUE 54's arithmetic: attention 18.874 M, the indexer 2.261 M, the
    router 0.262 M, an expert 4.719 M with 16 held, tables 2 x 18992 x
    2048: a layer 96.90 M, 465.4 M in all."""
    cfg = family.build(_load("keye-vl-2.0-30b-a3b-ep8-1chip.json"), mesh).cfg
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    layer = attention + indexer + 2048 * 128 + 16 * 3 * 2048 * 768 + 2 * 2048
    assert round(layer / 1e6, 2) == 96.90
    total = 4 * layer + 2 * 18992 * 2048 + 2048
    assert keye_vl.param_count(cfg) == total == 465_391_104
    assert (cfg.n_experts, cfg.as_moe().n_held, cfg.group) == (128, 16, 8)
    assert cfg.sections(128) == (16, 24, 24) and cfg.sections(64) == (8, 12, 12)


# -- the shares ---------------------------------------------------------------

def test_the_expert_shares_routed_parts_add_up_to_the_uncut_layer(
        config, mesh):
    """Two chips share a layer's 8 experts, four each (the tiny
    configuration's deployment): what each share's expert layer gives sums
    to the uncut plain form's layer."""
    whole_cfg = dict(config, num_experts=8, num_local_experts=8)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = keye_vl.layer_params(whole.cfg, params, 1)
    u = jax.random.normal(jax.random.key(3), (1, 24, whole.cfg.dim))
    want, _ = jax.jit(functools.partial(
        family._ref_expert_layer, config=whole_cfg))(u, lp)
    total = 0.0
    for first in range(0, 8, 4):
        share = dict(lp, **{name: lp[name][first:first + 4]
                            for name in ("w_gate", "w_up", "w_down")})
        cfg = dataclasses.replace(
            whole.cfg, experts_held=4, first_expert=first).as_moe()
        out, _ = jax.jit(functools.partial(moe.moe_mlp, cfg))(share, u)
        assert float(jnp.max(jnp.abs(out))) > 1e-3
        total = total + out
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)


def test_the_vocabulary_slice_is_a_smaller_vocabulary(built, config, mesh):
    """Ids 0-127 of 256: the sliced model is the whole model's tables cut
    to their first rows and columns, and its loss is the plain form's on
    that smaller vocabulary."""
    fam, params, _ = built
    cut = dict(config, vocab_size=128)
    small = family.build(cut, mesh)
    sliced = dict(params, embed=params["embed"][:128],
                  lm_head=params["lm_head"][:, :128])
    assert jax.tree.map(jnp.shape, sliced) == jax.tree.map(
        jnp.shape, jax.eval_shape(small.init_params, jax.random.key(0)))
    tokens = jax.random.randint(jax.random.key(6), (2, SEQ), 0, 128)
    got = float(jax.jit(small.loss_fn)(sliced, tokens))
    want = sum(float(x) for x in jax.jit(
        lambda p: family.plain_loss(p, tokens, cut))(sliced))
    assert abs(got - want) < 2e-5 * want
    # the residual stream does not know the slice: the same hidden states
    hidden = jax.jit(lambda p: keye_vl.forward_layers(p, tokens, fam.cfg)[0])
    np.testing.assert_array_equal(hidden(sliced), hidden(params))


def test_live_rows_count_the_pairs_that_chose_a_held_expert(built, config):
    fam, params, tokens = built
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (2,) and rows.dtype == np.int32
    positions = family.positions_for(config, *tokens.shape)

    @jax.jit
    def plain(params):
        x, held = params["embed"][tokens], []
        for lp in family.layers_of(params):
            out = family._ref_block(x, lp, config, positions)
            x = out["after"]
            held.append(jnp.sum(out["top_e"] < 4))
        return jnp.stack(held)

    np.testing.assert_array_equal(rows, plain(params))
    assert 0 < rows.min() and rows.max() < tokens.size * 2


# -- the kernels' path on the CPU, what a block keeps, the meshes ----------------

def test_a_layer_through_the_kernels_in_interpret_mode(built, config):
    """Group 2 through the ``_sel`` kernels, ``dsa_probs`` reading the key
    head where it lies, the index kernels: 128 positions, top-16."""
    fam, params, _ = built
    cfg = fam.cfg
    lp = keye_vl.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (1, 128, cfg.dim))
    tables = keye_vl.rotary_tables(cfg, family.positions_for(config, 1, 128))
    # the forward: tests/test_dsa.py and tests/test_attention_select.py
    # hold each kernel's backward
    got, want = (jax.jit(lambda lp, interpret=interpret: keye_vl.attention(
        cfg, None, tables, lp, y, interpret=interpret))(lp)
        for interpret in (True, False))
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(got[2], want[2])


def test_a_layers_gradients_through_the_kernels_in_interpret_mode(
        built, config):
    """The same layer differentiated: the ``_sel`` backward pair at group
    2 and L_I's path through the three index kernels, against the XLA
    forms, in every parameter of the attention and the indexer."""
    fam, params, _ = built
    cfg = fam.cfg
    lp = keye_vl.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (1, 128, cfg.dim))
    g = jax.random.normal(jax.random.key(3), y.shape)
    tables = keye_vl.rotary_tables(cfg, family.positions_for(config, 1, 128))

    def scalar(lp, interpret):
        out, l_i = keye_vl.attention(
            cfg, None, tables, lp, y, interpret=interpret)[:2]
        return jnp.sum(out * g) + l_i

    got, want = (jax.jit(jax.grad(
        lambda lp, interpret=interpret: scalar(lp, interpret)))(lp)
        for interpret in (True, False))
    moved = [name for name, d in want.items() if float(jnp.max(jnp.abs(d)))]
    assert set(moved) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm",
                          *keye_vl.INDEXER}
    _assert_grads_agree(got, want, tol=1e-4)


def test_the_select_kernel_changes_nothing_of_a_layer(
        built, config, monkeypatch):
    """The same layer through the same kernels, the selection by
    `dsa_select` and by the XLA passes: one mask, so the same outputs and
    the same gradients."""
    fam, params, _ = built
    cfg = fam.cfg
    lp = keye_vl.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (1, 128, cfg.dim))
    g = jax.random.normal(jax.random.key(3), y.shape)
    tables = keye_vl.rotary_tables(cfg, family.positions_for(config, 1, 128))

    def run():
        def scalar(lp):
            out, l_i, mask, _ = keye_vl.attention(
                cfg, None, tables, lp, y, interpret=True)
            return jnp.sum(out * g) + l_i, (out, l_i, mask)

        return jax.jit(jax.value_and_grad(scalar, has_aux=True))(lp)

    (_, got), got_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 1
    assert trace.gauges()["attn.select_kernel"] == 1
    assert trace.gauges()["attn.index_bwd_kernels"] == 1
    assert trace.gauges()["attn.probs_heads_a_trip"] == dsa._probs_trip(
        cfg.n_heads)
    monkeypatch.setattr(dsa, "_select_rows", lambda s: None)
    (_, want), want_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 0
    assert trace.gauges()["attn.select_kernel"] == 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    for name in want_grads:
        np.testing.assert_array_equal(got_grads[name], want_grads[name])


@pytest.mark.parametrize("remat", [False, True], ids=["off", "on"])
def test_remat_changes_no_gradient_and_the_gauges_say_what_was_kept(
        built, parts, config, remat):
    fam, params, tokens = built
    positions = family.positions_for(config, *tokens.shape)
    assert not fam.cfg.remat
    (ce, ce_grads), (l_i, l_i_grads) = parts[0]
    cfg = dataclasses.replace(fam.cfg, remat=remat)
    got, grads = jax.jit(jax.value_and_grad(lambda p: keye_vl.loss_fn(
        p, tokens, cfg, None, positions)))(params)
    # a recomputed block keeps all of `keye_vl.KEPT`, each behind a gauge
    assert [trace.gauges()[name] for name in (
        "attn.mask_kept", "attn.out_kept", "attn.loss_grad_kept")] == [
            float(remat)] * 3
    np.testing.assert_allclose(got, ce + l_i, rtol=1e-6)
    _assert_grads_agree(
        grads, jax.tree.map(jnp.add, ce_grads, l_i_grads), tol=1e-5)
    # a forward alone keeps nothing
    jax.eval_shape(lambda p: keye_vl.loss_fn(p, tokens, cfg), params)
    assert trace.gauges()["attn.mask_kept"] == 0


def test_sections_that_do_not_deal_out_a_head_are_refused():
    with pytest.raises(ValueError, match="mrope_section"):
        keye_vl.KeyeVLConfig.tiny(mrope_section=(2, 3, 3))


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    gauges = trace.gauges()
    for name, want in (
            ("attn.select_topk", 16), ("attn.group", 2),
            ("attn.index_heads", 2), ("attn.index_dim", 8),
            ("attn.causal_pairs", SEQ * (SEQ + 1) // 2),
            ("attn.select_pairs", 16 * 17 // 2 + (SEQ - 16) * 16),
            ("moe.experts_held", 4), ("moe.experts", 8)):
        assert gauges[name] == want, name
    assert trace.text("layers.pattern") == "KK"


@pytest.mark.parametrize("axis,why", [
    ("tp", "sum over all the heads"), ("sp", "take no selection"),
    ("pp", "nor for the position rows")])
def test_an_axis_the_family_has_no_form_for_is_refused(axis, why):
    cfg = keye_vl.KeyeVLConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1, pp=1)
    sizes[axis] = 2
    mesh = build_mesh(MeshConfig(**sizes).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match=why):
        keye_vl.validate_for_mesh(cfg, mesh, batch=2)


def test_experts_held_and_the_batch_must_divide_over_the_mesh():
    cfg = keye_vl.KeyeVLConfig.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    mesh = build_mesh(mc, jax.devices()[:2])
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        keye_vl.validate_for_mesh(cfg, mesh, batch=2)
    with pytest.raises(ValueError, match="does not divide"):
        keye_vl.validate_for_mesh(cfg, mesh, batch=3)


def test_three_steps_through_the_trainer_with_a_falling_loss(config):
    mc = MeshConfig(dp=-1, fsdp=2).resolve(4)
    mesh = build_mesh(mc, devices=jax.devices()[:4])
    fam = family.build(config, mesh)
    tc = TrainConfig(global_batch_size=4, micro_batch_size=1,
                     learning_rate=3e-3, warmup_steps=1)
    trainer = ElasticTrainer(fam.loss_fn, fam.param_specs, mesh, mc, tc)
    state = trainer.init_state(fam.init_params(jax.random.key(0)))
    accum, per = trainer.step_batch_shape
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (accum, per, SEQ), 0, 256),
        trainer.batch_sharding)
    losses = []
    for _ in range(3):
        state, loss = trainer.step(state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[2] < losses[0] - 0.05 and losses[1] <= losses[0], losses
    assert abs(losses[0] - fam.expected_first_loss) < 0.25
