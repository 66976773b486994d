"""The qwen3_next family (``models/qwen3_next.py``) at a tiny size on the
CPU against the plain form of its equations (``benchmarks/families/
qwen3_next.py``: the delta rule token by token, explicit scores, a loop
over the experts), term by term; the quarter rotary; the period; the
share of the experts tied to the uncut layer."""

import copy
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import qwen3_next as family
from dlrover_tpu.models import moe, qwen3_next
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import apply_rope, rope_frequencies
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-qwen3-next.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from their init (the stored offsets away from
    zero), a router that spreads its scores, decays, steps and gates away
    from their init, so that every term weighs."""
    keys = iter(jax.random.split(jax.random.key(5), 256))

    def noisy(leaf, scale):
        return leaf + scale * jax.random.normal(next(keys), leaf.shape)

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "o_norm"):
            if name in lp:
                lp[name] = noisy(lp[name], 0.3)
        if "a_log" in lp:
            lp["w_qkvz"] = lp["w_qkvz"] * 20.0
            lp["w_ba"] = lp["w_ba"] * 30.0
            lp["dt_bias"] = noisy(lp["dt_bias"], 0.5)
        else:
            lp["w_q"] = lp["w_q"] * 20.0
            lp["w_k"] = lp["w_k"] * 20.0
        lp["router"] = lp["router"] * 40.0
        lp["w_s"] = lp["w_s"] * 40.0
        lp["w_down"] = lp["w_down"] * 30.0
        lp["ws_down"] = lp["ws_down"] * 30.0
        lp["w_o"] = lp["w_o"] * 10.0
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0,
                final_norm=noisy(params["final_norm"], 0.3),
                layers={k: slab(v) for k, v in params["layers"].items()})


@pytest.fixture(scope="module")
def built(config, mesh):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 48), 0, fam.cfg.vocab_size)
    return fam, params, tokens


@pytest.fixture(params=["xla", "kernels"])
def gdn_form(request, monkeypatch):
    """The Gated DeltaNet layer's two forms: off the TPU it takes XLA's
    ops; with ``interpret`` the chip's path on the CPU: the per-head
    delta rule's two kernels and the Pallas passes around them (the
    convolution with its norms, the head norm with its SiLU gate)."""
    if request.param == "kernels":
        monkeypatch.setattr(qwen3_next, "gdn_attention", functools.partial(
            qwen3_next.gdn_attention, interpret=True))
    return request.param


def test_loss_and_gradients_match_the_plain_form(built, config, gdn_form):
    fam, params, tokens = built
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err <= 6e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err,
                                            scale)
    assert trace.gauges()["kda.io_fused"] == (gdn_form == "kernels")
    assert trace.gauges()["attn.gdn_kernel"] == (gdn_form == "kernels")
    # and every parameter of both mixers and of the expert layer weighs
    for pos, names in ((0, ("a_log", "dt_bias", "conv", "w_qkvz", "w_ba",
                            "o_norm", "w_s", "router", "attn_norm")),
                       (3, ("w_q", "w_k", "w_v", "q_norm", "k_norm", "w_s"))):
        slab = grads["layers"][qwen3_next.pos_name(pos)]
        for name in names:
            assert float(jnp.min(jnp.max(jnp.abs(slab[name]).reshape(
                slab[name].shape[0], -1), axis=-1))) > 0.0, (pos, name)


TERMS = ["shared", "shared_gate", "renormalize", "aux", "conv", "decay_rate",
         "dt_bias", "step", "head_norm", "out_gate", "q_norm", "attn_gate",
         "rotary", "norm_offset"]


@pytest.mark.parametrize("term", TERMS)
def test_each_term_moves_the_plain_form(built, config, term):
    """Each named term, changed in the plain form, moves its loss by far
    more than float32 rounding: a program that dropped it would be seen
    by ``test_loss_and_gradients_match_the_plain_form``."""
    _, params, tokens = built
    base = float(family.plain_loss(params, tokens, config))
    changed, p = copy.deepcopy(config), params

    def edit(name, fn):
        return dict(params, layers={
            k: ({**v, name: fn(v[name])} if name in v else v)
            for k, v in params["layers"].items()})

    kw = 2 * 16          # the tiny configuration's q (and k) width
    if term == "shared":
        p = edit("ws_down", jnp.zeros_like)
    elif term == "shared_gate":
        p = edit("w_s", jnp.zeros_like)         # the gate 1/2 everywhere
    elif term == "renormalize":
        changed["norm_topk_prob"] = False
    elif term == "aux":
        changed["assumed"]["router_aux_loss_coef"] = 0.0
    elif term == "conv":
        # only the token's own tap: no convolution
        p = edit("conv", lambda w: w.at[..., :-1].set(0.0))
    elif term == "decay_rate":
        p = edit("a_log", lambda a: a + 1.0)
    elif term == "dt_bias":
        p = edit("dt_bias", jnp.zeros_like)
    elif term == "step":
        p = edit("w_ba", lambda w: w.at[..., :4].set(0.0))   # beta = 1/2
    elif term == "head_norm":
        p = edit("o_norm", jnp.ones_like)
    elif term == "out_gate":
        p = edit("w_qkvz", lambda w: w.at[..., 2 * kw + 64:].set(0.0))
    elif term == "q_norm":
        p = edit("q_norm", jnp.zeros_like)
    elif term == "attn_gate":
        p = edit("w_q", lambda w: w.reshape(*w.shape[:-1], 4, 64).at[
            ..., 32:].set(0.0).reshape(w.shape))
    elif term == "rotary":
        changed["partial_rotary_factor"] = 0.5
    else:
        p = edit("mlp_norm", jnp.zeros_like)
    moved = float(family.plain_loss(p, tokens, changed))
    assert abs(moved - base) > 1e-4, (term, base, moved)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("partial_rotary_factor", 0.5),
    ("rope_theta", 10000), ("rms_norm_eps", 0.1),
])
def test_program_follows_each_config_term(config, mesh, key, value):
    changed = dict(config, **{key: value})
    fam = family.build(changed, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0, 256)
    got = float(jax.jit(fam.loss_fn)(params, tokens))
    assert abs(got - float(family.plain_loss(params, tokens, changed))) < 2e-5
    assert abs(got - float(family.plain_loss(params, tokens, config))) > 1e-5


# ---------------------------------------------------------------------------
# The mixers and the expert layer, each against the plain form
# ---------------------------------------------------------------------------

def _layer(built, i):
    fam, params, tokens = built
    lp = qwen3_next.layer_params(fam.cfg, params, i)
    x = jax.random.normal(jax.random.key(7), (2, 48, fam.cfg.dim))
    return fam.cfg, lp, qwen3_next.norm(x, lp["attn_norm"], fam.cfg.norm_eps)


def test_gdn_layer_matches_the_token_by_token_form(built, config, gdn_form):
    cfg, lp, y = _layer(built, 0)
    got = jax.jit(lambda lp, y: qwen3_next.gdn_attention(cfg, lp, y))(lp, y)
    want = family._ref_gdn(y, lp, config)
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # two value heads read each key head's q and k
    q, k, v, g, beta, z = qwen3_next.gdn_inputs(cfg, lp, y)
    assert q.shape == k.shape == (2, 48, 2, 16)
    assert v.shape == z.shape == (2, 48, 4, 16)
    assert g.shape == beta.shape == (2, 48, 4)
    assert float(jnp.max(g)) < 0.0


def test_gated_attention_matches_explicit_scores(built, config, mesh):
    cfg, lp, y = _layer(built, 3)
    got = jax.jit(
        lambda lp, y: qwen3_next.gated_attention(cfg, mesh, lp, y))(lp, y)
    want = family._ref_gattn(y, lp, config)
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_expert_layer_with_the_gated_shared_expert(built, config):
    cfg, lp, y = _layer(built, 0)
    got, aux = moe.moe_mlp(cfg.as_moe(), lp, y)
    want, top_e, want_aux = family._ref_expert_layer(y, lp, config)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
    assert top_e.shape == (96, 2)
    assert trace.gauges()["moe.shared_gate"] == 1
    # a layer without w_s: the shared expert ungated, as it was
    bare = {k: v for k, v in lp.items() if k != "w_s"}
    ungated, _ = moe.moe_mlp(cfg.as_moe(), bare, y)
    assert trace.gauges()["moe.shared_gate"] == 0
    assert float(jnp.max(jnp.abs(ungated - got))) > 1e-3


@pytest.mark.parametrize("rotary_dim", [8, 16, 32])
def test_quarter_rotary_turns_the_first_channels_only(rotary_dim):
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 32))
    positions = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    got = apply_rope(x, positions, rope_frequencies(rotary_dim, 1e7))
    want = family._partial_rotary(x, 1e7, rotary_dim)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(got[..., rotary_dim:], x[..., rotary_dim:])
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :rotary_dim]
                                 - x[:, 1:, :, :rotary_dim]))) > 1e-2
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)


# ---------------------------------------------------------------------------
# The period and the layout
# ---------------------------------------------------------------------------

def test_pattern_of_the_published_model_and_the_cut():
    published = qwen3_next.Qwen3NextConfig()
    assert published.pattern_string == "GGGF" * 12
    assert published.period == 4 and published.rotary_dim == 64
    part, = published.layout
    assert part.kinds == ("G", "G", "G", "F") and part.repeats == 12
    cut = qwen3_next.Qwen3NextConfig(n_layers=8)
    assert cut.pattern_string == "GGGFGGGF" and cut.layout[0].repeats == 2


def test_a_gggf_model_is_its_blocks_by_hand(built):
    fam, params, tokens = built
    cfg = fam.cfg
    x = params["embed"][tokens].astype(cfg.dtype)
    aux = []
    for i, kind in enumerate(cfg.kinds):
        x, a = qwen3_next.block(
            cfg, None, kind, qwen3_next.layer_params(cfg, params, i), x)
        aux.append(a)
    got, got_aux = qwen3_next.forward_layers(params, tokens, cfg)
    np.testing.assert_allclose(got, x, rtol=1e-3, atol=2e-3)
    assert float(got_aux) == pytest.approx(float(jnp.mean(jnp.stack(aux))))


@pytest.mark.parametrize("interval,depth,pattern", [
    (2, 4, "GFGF"), (4, 4, "GGGF"), (3, 6, "GGFGGF")])
def test_other_periods_run_and_match_the_plain_form(
        config, mesh, interval, depth, pattern):
    changed = dict(config, full_attention_interval=interval,
                   num_hidden_layers=depth)
    fam = family.build(changed, mesh)
    assert fam.cfg.pattern_string == pattern
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(jax.random.key(4), (2, 32), 0, 256)
    got = float(jax.jit(fam.loss_fn)(params, tokens))
    assert abs(got - float(family.plain_loss(params, tokens, changed))) < 2e-5


def test_live_rows_count_the_held_experts_pairs(built, config):
    fam, params, tokens = built
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (8,) and rows.dtype == np.int32
    # the plain form's routers, layer by layer
    x = params["embed"][tokens]
    for i, lp in enumerate(family.layers_of(params)):
        x, _, _, top_e, _ = family._ref_block(x, lp, config)
        assert rows[i] == int(jnp.sum(top_e < 2)), i


# ---------------------------------------------------------------------------
# The share tied to the model
# ---------------------------------------------------------------------------

def test_the_four_shares_add_up(config, mesh):
    """Four chips share a layer's 8 experts, two each. The routed parts
    the four shares compute, plus the gated shared expert once, are the
    uncut layer of the plain form."""
    whole_cfg = dict(config, num_experts=8, published_num_experts=8,
                     num_experts_per_tok=3)
    whole = family.build(whole_cfg, mesh)
    params = _weighty(whole.init_params(jax.random.key(1)))
    lp = qwen3_next.layer_params(whole.cfg, params, 0)
    y = jax.random.normal(jax.random.key(2), (2, 24, whole.cfg.dim))
    want, _, _ = family._ref_expert_layer(y, lp, whole_cfg)

    shared = moe._shared_expert(lp, y)
    total = shared
    for first in range(0, 8, 2):
        share = {k: v for k, v in lp.items()
                 if not k.startswith("ws_") and k != "w_s"}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out, _ = moe.moe_mlp(share_cfg, share, y)
        total = total + out
        # and one share alone is the plain form's share
        ref_share, _, _ = family._ref_expert_layer(
            y, {**lp, **{n: share[n] for n in ("w_gate", "w_up", "w_down")}},
            dict(whole_cfg, num_experts=2, first_expert=first))
        np.testing.assert_allclose(out + shared, ref_share, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2   # experts weigh


# ---------------------------------------------------------------------------
# Sizes, gauges, meshes, the trainer
# ---------------------------------------------------------------------------

def test_param_count_of_the_published_model_and_the_cut():
    # ISSUE 45's arithmetic: a Gated DeltaNet mixer 33.72 M, a gated
    # attention mixer 27.26 M, router + shared expert + gate 4.20 M, an
    # expert 3.146 M; the cut 1.1735 B, the whole model 79.67 B
    gdn = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    gattn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    rest = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048   # + the two norms
    assert gdn == 33_718_464 and gattn == 27_263_488
    expert = 3 * 2048 * 512
    whole = (36 * gdn + 12 * gattn + 48 * (rest + 512 * expert)
             + 2 * 151936 * 2048 + 2048)
    assert qwen3_next.param_count(qwen3_next.Qwen3NextConfig()) == whole
    assert whole == pytest.approx(79.67e9, rel=1e-3)
    cut = dict(vocab_size=18992, n_layers=8)
    n32 = qwen3_next.param_count(
        qwen3_next.Qwen3NextConfig(experts_held=32, **cut))
    n16 = qwen3_next.param_count(
        qwen3_next.Qwen3NextConfig(experts_held=16, **cut))
    assert n32 == 1_173_540_992
    assert n32 == (6 * gdn + 2 * gattn + 8 * (rest + 32 * expert)
                   + 2 * 18992 * 2048 + 2048)
    assert n32 - n16 == 8 * 16 * expert


def test_init_follows_the_configuration(config, mesh):
    fam = family.build(dict(config, assumed=dict(
        config["assumed"], out_proj_std=1e-4)), mesh)
    params = fam.init_params(jax.random.key(0))
    g, f = params["layers"]["pos0"], params["layers"]["pos3"]
    for slab in (g, f):
        for name in ("w_o", "w_down", "ws_down"):
            assert float(jnp.std(slab[name])) == pytest.approx(1e-4, rel=0.2)
        assert float(jnp.std(slab["router"])) == pytest.approx(0.02, rel=0.2)
        for name in ("attn_norm", "mlp_norm"):
            assert float(jnp.max(jnp.abs(slab[name]))) == 0.0
    assert float(jnp.max(jnp.abs(params["final_norm"]))) == 0.0
    assert float(jnp.min(g["dt_bias"])) == float(jnp.max(g["o_norm"])) == 1.0
    a = jnp.exp(g["a_log"])
    assert 0.0 < float(jnp.min(a)) and float(jnp.max(a)) <= 16.0
    assert float(jnp.max(jnp.abs(f["q_norm"]))) == 0.0


def test_gauges_say_what_the_build_is(built):
    fam, params, tokens = built
    jax.eval_shape(fam.loss_fn, params, tokens)
    g = trace.gauges()
    assert g["attn.gdn_layers"] == 6 and g["attn.full_layers"] == 2
    assert g["attn.gdn_key_heads"] == 2 and g["attn.gdn_value_heads"] == 4
    assert g["attn.gdn_chunk"] == 16 and g["attn.gdn_kernel"] == 0
    assert g["attn.rotary_dim"] == 8 and g["attn.group"] == 2
    assert g["layers.period"] == 4
    assert g["moe.experts"] == 8 and g["moe.experts_held"] == 2
    assert g["moe.rows_held"] == 2 * 48 * 2 * 2 / 8
    assert g["moe.shared_experts"] == 1 and g["moe.shared_gate"] == 1
    assert trace.text("layers.pattern") == "GGGFGGGF"


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_mesh_axes_it_cannot_run_are_refused(axis):
    cfg = qwen3_next.Qwen3NextConfig.tiny()
    sizes = dict(dp=1, fsdp=1, ep=1, sp=1, tp=1)
    sizes[axis] = 2
    mc = MeshConfig(**sizes).resolve(2)
    with pytest.raises(ValueError, match="recurrent state"):
        qwen3_next.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


def test_experts_held_must_divide_over_ep():
    cfg = qwen3_next.Qwen3NextConfig.tiny(experts_held=3)
    mc = MeshConfig(dp=1, fsdp=1, ep=2, sp=1, tp=1).resolve(2)
    with pytest.raises(ValueError, match="not divisible by mesh ep"):
        qwen3_next.validate_for_mesh(
            cfg, build_mesh(mc, jax.devices()[:2]), 2)


def test_value_heads_must_group_over_key_heads():
    with pytest.raises(ValueError, match="do not group"):
        qwen3_next.Qwen3NextConfig.tiny(gdn_value_heads=3)


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
    # the first update is warm-up's (lr 0): the loss falls from the second
    assert losses[2] < losses[0] - 0.05 and losses[1] <= losses[0], losses
    assert abs(losses[0] - fam.expected_first_loss) < 0.25
