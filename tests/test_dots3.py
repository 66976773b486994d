"""The dots3 family (``models/dots3.py``) at a tiny size on the CPU against
the plain form of its equations (``benchmarks/families/dots3.py``:
explicit scores, ``lax.top_k``, a loop over the experts), with the
selection and the window both shorter than the sequence; the two parts
of the loss and where each one's gradient goes; the layout read from
``layer_types``; the shares of heads and experts tied to the uncut
layer; the meshes."""

import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import dots3 as family
from dlrover_tpu.models import dots3, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention, dsa
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F, S = "full_attention", "sliding_attention"


def _load(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("tiny-cpu-dots3.json")


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one, routers and indexers that spread their
    scores, projections that make attention and the experts weigh, so that
    every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 256))

    def block(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm",
                     "idx_k_norm", "idx_k_bias"):
            if name in lp:
                lp[name] = lp[name] + 0.3 * jax.random.normal(
                    next(keys), lp[name].shape)
        for name, by in (("router", 40.0), ("w_qb", 6.0), ("w_o", 30.0),
                         ("w_g", 30.0), ("w_down", 100.0), ("ws_down", 30.0),
                         ("idx_wq", 10.0), ("idx_ww", 60.0)):
            if name in lp:
                lp[name] = lp[name] * by
        return lp

    return dict(
        params, lm_head=params["lm_head"] * 10.0,
        **{group: {k: block(v) for k, v in params[group].items()}
           for group in ("dense", "layers", "tail")})


def _built(config, mesh, seq=48):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


@pytest.fixture(scope="module")
def built(config, mesh):
    return _built(config, mesh)


def _terms(fam):
    return lambda p, t: dots3.loss_terms(p, t, fam.cfg, None)


def _assert_grads_agree(grads, want_grads, tol=3e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        assert err <= tol * scale + 1e-7, (
            jax.tree_util.keystr(path), err, scale)


@pytest.mark.parametrize("part", [0, 1], ids=["CE", "L_I"])
def test_each_part_of_the_loss_and_its_gradient_match_the_plain_form(
        built, config, part):
    """fFSSS, top-16 and a window of 9 under 48 positions."""
    fam, params, tokens = built
    assert fam.cfg.pattern_string == "fFSSS"
    assert fam.cfg.index_topk == 16 and fam.cfg.window == 9
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: _terms(fam)(p, tokens)[part]))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)[part]))(params)
    assert abs(float(got) - float(want)) < 2e-5 * max(1.0, abs(float(want)))
    assert float(want) > 1e-3
    _assert_grads_agree(grads, want_grads, tol=1e-3)


def test_the_loss_is_the_sum_of_its_parts(built):
    fam, params, tokens = built
    ce, l_i = jax.jit(_terms(fam))(params, tokens)
    total = jax.jit(fam.loss_fn)(params, tokens)
    np.testing.assert_allclose(total, ce + l_i, rtol=1e-6)


def _is_indexer(path) -> bool:
    return any(name in jax.tree_util.keystr(path) for name in dots3.INDEXER)


def test_the_two_parts_move_disjoint_parameters(built):
    """``L_I``'s gradient reaches the indexer's parameters alone and CE's
    none of them, exactly (the stop-gradients, not a tolerance)."""
    fam, params, tokens = built
    for part, own in ((0, False), (1, True)):
        grads = jax.jit(jax.grad(
            lambda p: _terms(fam)(p, tokens)[part]))(params)
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
            moved = bool(np.asarray(leaf).any())
            name = jax.tree_util.keystr(path)
            if _is_indexer(path) != own:
                assert not moved, (part, name)
            elif "router_bias" not in name:
                assert moved, (part, name)


@pytest.mark.parametrize("key,value", [
    ("sliding_window_size", 5), ("sliding_window_size", 64),
    ("index_topk", 8), ("index_topk", 64),
    ("rope_theta", 100.0), ("swa_rope_theta", 50.0),
    ("apply_mla_qkv_lora_rescale", False), ("norm_topk_prob", False),
    ("routed_scaling_factor", 2.0), ("rms_norm_eps", 0.1),
    ("layer_types", [F, S, S, F, S]), ("first_k_dense_replace", 0),
])
def test_each_config_term_moves_the_plain_form_and_the_program(
        built, config, mesh, key, value):
    fam, params, tokens = built
    changed = dict(config, **{key: value})
    if key in ("layer_types", "first_k_dense_replace"):
        # another layout is another tree
        fam2, params, tokens = _built(changed, mesh)
        base = None
    else:
        fam2 = family.build(changed, mesh)
        base = [float(x) for x in family.plain_loss(params, tokens, config)]
    want = [float(x) for x in family.plain_loss(params, tokens, changed)]
    if base is not None:
        assert max(abs(w - b) for w, b in zip(want, base)) > 1e-4, (key, want)
    got = [float(x) for x in jax.jit(_terms(fam2))(params, tokens)]
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=2e-5)


@pytest.mark.parametrize("types,dense,pattern,period,periods,tail", [
    ([F, F, S, S, S], 1, "fFSSS", 4, 1, ""),
    ([F, F, S, S, S, F, S, S, S, F], 1, "fFSSSFSSSF", 4, 2, "F"),
    ([F, S, F, S, F, S], 2, "fsFSFS", 2, 2, ""),
    ([S, S, S], 0, "SSS", 1, 3, ""),
    ([F, S, S, F], 0, "FSSF", 3, 1, "F"),
])
def test_the_layout_is_read_from_layer_types(
        config, mesh, types, dense, pattern, period, periods, tail):
    changed = dict(config, layer_types=types, num_hidden_layers=len(types),
                   first_k_dense_replace=dense)
    fam, params, tokens = _built(changed, mesh, seq=32)
    cfg = fam.cfg
    assert (cfg.pattern_string, cfg.period, cfg.n_periods,
            "".join(cfg.tail_kinds)) == (pattern, period, periods, tail)
    assert sorted(params["layers"]) == [
        dots3.pos_name(i) for i in range(period)]
    got = jax.jit(_terms(fam))(params, tokens)
    want = family.plain_loss(params, tokens, changed)
    np.testing.assert_allclose(
        [float(x) for x in got], [float(x) for x in want], rtol=3e-5,
        atol=2e-5)
    # layer_params finds every layer where the reference's walk does
    for i, lp in enumerate(family.layers_of(params, changed)):
        mine = dots3.layer_params(cfg, params, i)
        assert sorted(mine) == sorted(lp)
        np.testing.assert_array_equal(mine["w_qa"], lp["w_qa"])


def test_the_published_config_is_the_default():
    entry = next(
        json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"dots3-note-prev"' in line) if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if entry is None:
        pytest.skip("no catalog here")
    cfg = dots3.Dots3Config.from_hf(entry["config"])
    assert cfg == dots3.Dots3Config()
    assert (cfg.period, cfg.n_periods, cfg.tail_kinds) == (4, 11, ("F",))
    assert cfg.pattern_string == "f" + "FSSS" * 11 + "F"
    full, window = cfg.latent("F"), cfg.latent("S")
    assert (full.n_heads, full.qk_head_dim, full.kv_lora_rank) == (
        128, 192, 512)
    assert (window.n_heads, window.qk_head_dim, window.kv_lora_rank) == (
        64, 256, 1024)
    np.testing.assert_allclose(full.latent_rescale, (5 ** 0.5, 10 ** 0.5))
    np.testing.assert_allclose(window.latent_rescale, (5 ** 0.5, 5 ** 0.5))


@pytest.mark.parametrize("key,value", [
    ("attention_gate_type", "elementwise"), ("topk_method", "greedy"),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True)])
def test_what_the_program_does_not_compute_is_refused(config, key, value):
    with pytest.raises(ValueError, match=key):
        dots3.Dots3Config.from_hf(dict(config, **{key: value}))


def test_param_count_of_the_listed_cut():
    listed = _load("dots3-note-prev-ep32-1chip.json")
    fam_cfg = dots3.Dots3Config.from_hf(
        dict(listed, num_attention_heads=128, swa_num_attention_heads=64,
             n_routed_experts=256),
        heads_held=32, swa_heads_held=16, experts_held=8)
    assert dots3.param_count(fam_cfg) == 1_452_459_520
    # the count of ISSUE 40, by hand: layer 0 (F, dense), F, S, S, S
    full = (5120 * 1024 + 1024 + 1024 * 32 * 192 + 5120 * 576 + 512
            + 512 * 32 * 256 + 32 * 128 * 5120 + 5120 * 32
            + 1024 * 64 * 128 + 5120 * 128 + 256 + 5120 * 64)
    window = (5120 * 1024 + 1024 + 1024 * 16 * 256 + 5120 * 1088 + 1024
              + 1024 * 16 * 320 + 16 * 128 * 5120 + 5120 * 16)
    experts = (5120 * 256 + 256 + (8 + 1) * 3 * 5120 * 1536)
    norms = 2 * 5120
    want = (full + norms + 3 * 5120 * 13824
            + full + norms + experts + 3 * (window + norms + experts)
            + 2 * 19008 * 5120 + 5120)
    assert want == 1_452_459_520


# -- the shares ---------------------------------------------------------------

def _head_share(lp, shape_all, first, held):
    """The leaves of heads ``first .. first + held - 1``: columns of
    ``w_qb``, ``w_kvb``, ``w_g``, rows of ``w_o``; the rest as it is."""
    h = shape_all.n_heads
    dn, dr, dv = (shape_all.qk_nope_dim, shape_all.qk_rope_dim,
                  shape_all.v_head_dim)
    take = slice(first, first + held)

    def columns(w, width):
        return w.reshape(w.shape[0], h, width)[:, take].reshape(
            w.shape[0], held * width)

    return dict(
        lp, w_qb=columns(lp["w_qb"], dn + dr),
        w_kvb=columns(lp["w_kvb"], dn + dv), w_g=lp["w_g"][:, take],
        w_o=lp["w_o"].reshape(h, dv, -1)[take].reshape(held * dv, -1))


@pytest.mark.parametrize("layer,kind", [(1, "F"), (2, "S")])
def test_the_head_shares_add_up_to_the_uncut_layers_attention(
        config, mesh, layer, kind):
    """Four chips split a layer's heads; ``W_qa``, ``W_kva``, the norms
    and the indexer are what each computes alike, so every share selects
    the same keys. The shares' outputs sum to the uncut reference's
    attention, and their ``p`` to the uncut ``p``."""
    whole_cfg = dict(config, num_attention_heads=4,
                     swa_num_attention_heads=4,
                     published_swa_num_attention_heads=4)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = dots3.layer_params(whole.cfg, params, layer)
    y = jax.random.normal(jax.random.key(2), (2, 40, whole.cfg.dim))
    want = family._ref_attention(y, lp, whole_cfg, kind)
    positions = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))

    total = jnp.zeros_like(want["attn"])
    for first in range(4):
        held = {"heads_held": 1, "first_head": first} if kind == "F" else {
            "swa_heads_held": 1, "swa_first_head": first}
        cfg = dataclasses.replace(whole.cfg, **held)
        share = _head_share(lp, whole.cfg.latent(kind), first, 1)
        out, _ = dots3.attention(cfg, None, kind, positions, share, y)
        assert float(jnp.max(jnp.abs(out))) > 1e-3     # each share weighs
        total = total + out
    np.testing.assert_allclose(total, want["attn"], atol=3e-5, rtol=3e-5)


def test_the_expert_shares_add_up_with_the_shared_expert_once(config, mesh):
    """Four chips share a layer's 8 experts, two each; the shared expert
    is counted once. The parts sum to the uncut plain form's layer."""
    whole_cfg = dict(config, n_routed_experts=8)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = dots3.layer_params(whole.cfg, params, 1)
    u = jax.random.normal(jax.random.key(3), (2, 24, whole.cfg.dim))
    want, _ = family._ref_expert_layer(u, lp, whole_cfg)

    shared = {k: lp[k] for k in ("ws_gate", "ws_up", "ws_down")}
    total = family._swiglu(u, *shared.values())
    for first in range(0, 8, 2):
        share = {k: v for k, v in lp.items() if k not in shared}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out, _ = moe.moe_mlp(cfg, share, u)
        assert float(jnp.max(jnp.abs(out))) > 1e-3
        total = total + out
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=5e-5)


def test_the_shares_p_add_up(config, mesh):
    """What an absent head would add to ``p`` is left out: two shares'
    head-summed probabilities sum to the uncut layer's."""
    q, k = (jax.random.normal(jax.random.key(i), (1, 32, 4, 24))
            for i in (0, 1))
    mask = dsa.selection_mask(
        jax.random.normal(jax.random.key(2), (1, 32, 32)), 8)
    from dlrover_tpu.ops.attention import mha_reference_with_lse
    _, lse = mha_reference_with_lse(q, k, q, select=mask)
    whole = dsa.head_summed_probs(q, k, lse, mask, 24 ** -0.5)
    halves = sum(dsa.head_summed_probs(
        q[:, :, h], k[:, :, h], lse[:, h], mask, 24 ** -0.5)
        for h in (slice(0, 2), slice(2, 4)))
    np.testing.assert_allclose(halves, whole, rtol=1e-5, atol=1e-7)


# -- the kernels' path on the CPU, the counters, the meshes -------------------

def test_a_full_layer_through_the_kernels_in_interpret_mode(built):
    fam, params, tokens = built
    cfg = fam.cfg
    lp = dots3.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))

    def run(interpret):
        return jax.value_and_grad(lambda lp: sum(
            jnp.sum(x) for x in dots3.attention(
                cfg, None, "F", positions, lp, y, interpret=interpret)))(lp)

    (got, got_grads), (want, want_grads) = run(True), run(False)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _assert_grads_agree(got_grads, want_grads, tol=1e-3)


def test_the_select_kernel_changes_nothing_of_a_full_layer(
        built, monkeypatch):
    """The same layer through the same kernels, the selection by
    `dsa_select` and by the XLA passes: one mask, so the same outputs and
    the same gradients."""
    fam, params, tokens = built
    cfg = fam.cfg
    lp = dots3.layer_params(cfg, params, 1)
    y = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))

    def run():
        return jax.value_and_grad(lambda lp: sum(
            jnp.sum(x) for x in dots3.attention(
                cfg, None, "F", positions, lp, y, interpret=True)))(lp)

    got, got_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 1
    monkeypatch.setattr(dsa, "_select_rows", lambda s: None)
    want, want_grads = run()
    assert trace.gauges()["dsa.select_kernel"] == 0
    np.testing.assert_array_equal(got, want)
    for name in want_grads:
        np.testing.assert_array_equal(got_grads[name], want_grads[name])


def test_live_rows_count_the_pairs_that_chose_a_held_expert(built, config):
    fam, params, tokens = built
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (4,) and rows.dtype == np.int32
    # the plain form's routers on the plain form's own chain
    x = params["embed"][tokens]
    want = []
    for lp, kind in zip(family.layers_of(params, config),
                        family.kinds_of(config)):
        out = family._ref_block(x, lp, config, kind)
        x = out["after"]
        if "top_e" in out:
            want.append(int(jnp.sum(out["top_e"] < 4)))
    np.testing.assert_array_equal(rows, want)
    assert 0 < rows.min() and rows.max() < tokens.size * 2


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    # a fresh function: traced again whatever the earlier tests traced
    jax.eval_shape(lambda p, t: fam.loss_fn(p, t), params, tokens)
    g = trace.gauges()
    assert (g["dsa.topk"], g["dsa.index_heads"], g["dsa.kernel"]) == (
        16, 4, 0)
    assert (g["attn.heads_held"], g["attn.heads"]) == (2, 4)
    assert (g["attn.swa_heads_held"], g["attn.swa_heads"]) == (1, 2)
    assert (g["attn.window"], g["attn.window_layers"],
            g["attn.full_layers"]) == (9, 3, 2)
    assert (g["layers.period"], g["layers.dense"]) == (4, 1)
    assert trace.text("layers.pattern") == "fFSSS"
    assert {"dsa_index", "dsa_select", "dsa_loss", "attn_gate",
            "mla_proj"} <= set(trace.scopes())


def _kernel_calls(jaxpr, found=None):
    """The Pallas calls of a jaxpr and of every jaxpr inside it, by the
    kernel's ``name=``."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


@pytest.mark.parametrize("kind,layer,want", [
    ("F", 1, {"dsa_index_fwd": 1, "dsa_select": 1, "dsa_probs": 1,
              "dsa_index_bwd_dq": 1, "dsa_index_bwd_dk": 1,
              "attention_fwd_sel": 1,
              "attention_bwd_dq_sel": 1, "attention_bwd_dkv_sel": 1}),
    ("S", 2, {"attention_fwd_swa": 1, "attention_bwd_dq_swa": 1,
              "attention_bwd_dkv_swa": 1})])
def test_a_recomputed_full_block_runs_the_loss_and_the_scores_once(
        built, monkeypatch, kind, layer, want):
    """The kernels a block's gradient calls under remat, checkpoint's
    partial evaluation done (it has dropped from the recomputed forward
    what the backward does not read). Until PR 43 a full block called
    ``dsa_index_fwd`` 2 and ``dsa_probs`` 2 times: the KL's autodiff read
    ``log_softmax(scores)`` and ``probs`` again. Until PR 46 the flash
    forward was called twice in either kind of block: its output and
    ``lse`` are the backward's, and the block keeps them now. The
    threshold's kernel (PR 55) runs once: the block keeps its mask."""
    fam, params, _ = built
    cfg = dataclasses.replace(fam.cfg, remat=True)
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    positions = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))
    x = jax.random.normal(jax.random.key(2), (2, 128, cfg.dim))
    fn = dots3._block_fn(cfg, None, kind, positions)
    trace.gauge("dsa.loss_grad_kept", 0)
    trace.gauge("attn.out_kept", 0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda lp, x: sum(jnp.sum(out) for out in fn(lp, x)),
        argnums=(0, 1)))(dots3.layer_params(cfg, params, layer), x)
    assert dict(_kernel_calls(jaxpr.jaxpr)) == want
    assert trace.gauges()["dsa.loss_grad_kept"] == (kind == "F")
    assert trace.gauges()["attn.out_kept"] == 1


def test_remat_changes_no_gradient_and_the_gauge_says_what_was_kept(built):
    """Every leaf's gradient, the indexer's three included, with the full
    blocks keeping the mask and ``d L_I / d scores`` against the same
    model with nothing recomputed."""
    fam, params, tokens = built

    def value_and_grads(remat):
        cfg = dataclasses.replace(fam.cfg, remat=remat)
        out = jax.jit(jax.value_and_grad(
            lambda p: dots3.loss_fn(p, tokens, cfg, None)))(params)
        return out, trace.gauges()["dsa.loss_grad_kept"]

    (want, want_grads), kept = value_and_grads(False)
    assert kept == 0
    (got, grads), kept = value_and_grads(True)
    assert kept == 1
    np.testing.assert_allclose(got, want, rtol=1e-6)
    _assert_grads_agree(grads, want_grads, tol=1e-5)
    moved = [jax.tree_util.keystr(path) for path, leaf
             in jax.tree_util.tree_flatten_with_path(grads)[0]
             if _is_indexer(path) and np.asarray(leaf).any()]
    assert len(moved) == 2 * len(dots3.INDEXER), moved
    # a forward alone keeps nothing
    jax.eval_shape(lambda p: dots3.loss_fn(
        p, tokens, dataclasses.replace(fam.cfg, remat=True), None), params)
    assert trace.gauges()["dsa.loss_grad_kept"] == 0


@pytest.mark.parametrize("axis,why", [
    ("tp", "no head-sharded form"), ("sp", "neither a selection nor"),
    ("pp", "blocks differ in shape")])
def test_an_axis_the_family_has_no_form_for_is_refused(axis, why):
    cfg = dots3.Dots3Config.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1, pp=1)
    sizes[axis] = 2
    mesh = build_mesh(MeshConfig(**sizes).resolve(2), jax.devices()[:2])
    with pytest.raises(ValueError, match=why):
        dots3.validate_for_mesh(cfg, mesh, batch=2)


def test_experts_held_must_divide_over_ep():
    cfg = dots3.Dots3Config.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        dots3.validate_for_mesh(cfg, build_mesh(mc, jax.devices()[:2]),
                                batch=2)


@pytest.mark.parametrize("held", [
    dict(heads_held=5), dict(heads_held=2, first_head=3),
    dict(swa_heads_held=0)])
def test_held_heads_lie_inside_the_layers_heads(held):
    with pytest.raises(ValueError, match="held of"):
        dots3.Dots3Config.tiny(**held)


def test_the_ep_and_fsdp_paths_on_cpu_devices(config):
    one = build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])
    fam1, params, _ = _built(config, one)
    tokens = jax.random.randint(jax.random.key(4), (4, 32), 0, 256)
    want, want_grads = jax.jit(jax.value_and_grad(fam1.loss_fn))(
        params, tokens)
    for sizes in (dict(ep=2), dict(ep=2, fsdp=2)):
        n = 2 * sizes.get("fsdp", 1)
        mc = MeshConfig(dp=1, **sizes).resolve(n)
        mesh = build_mesh(mc, devices=jax.devices()[:n])
        fam = family.build(config, mesh)
        placed = jax.device_put(
            params, named_shardings(mesh, fam.param_specs))
        loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(placed, tokens)
        assert abs(float(loss) - float(want)) < 2e-5, sizes
        _assert_grads_agree(grads, want_grads, tol=1e-3)


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
        jax.random.randint(jax.random.key(1), (accum, per, 32), 0, 256),
        trainer.batch_sharding)
    losses = []
    for _ in range(3):
        state, loss = trainer.step(state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[2] < losses[0] - 0.05 and losses[1] <= losses[0], losses
    assert abs(losses[0] - fam.expected_first_loss) < 0.25
