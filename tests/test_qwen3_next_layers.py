"""The qwen3_next family layer by layer (see ``test_qwen3_next.py``): the
program follows each term of the configuration; the mixers and the
expert layer against the plain form; the quarter rotary; the period;
the share of the experts tied to the uncut layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import qwen3_next as family
from dlrover_tpu.models import moe, qwen3_next
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import apply_rope, rope_frequencies
from tests.qwen3_next_family import (  # noqa: F401  (fixtures by import)
    _plain_loss, _weighty, built, config, gdn_form, mesh)


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
    assert abs(got - _plain_loss(params, tokens, changed)) < 2e-5
    assert abs(got - _plain_loss(params, tokens, config)) > 1e-5


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
    want = jax.jit(functools.partial(family._ref_gdn, config=config))(y, lp)
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # two value heads read each key head's q and k
    q, k, v, g, beta, z = jax.jit(functools.partial(
        qwen3_next.gdn_inputs, cfg))(lp, y)
    assert q.shape == k.shape == (2, 48, 2, 16)
    assert v.shape == z.shape == (2, 48, 4, 16)
    assert g.shape == beta.shape == (2, 48, 4)
    assert float(jnp.max(g)) < 0.0


def test_gated_attention_matches_explicit_scores(built, config, mesh):
    cfg, lp, y = _layer(built, 3)
    got = jax.jit(
        lambda lp, y: qwen3_next.gated_attention(cfg, mesh, lp, y))(lp, y)
    want = jax.jit(functools.partial(
        family._ref_gattn, config=config))(y, lp)
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_expert_layer_with_the_gated_shared_expert(built, config):
    cfg, lp, y = _layer(built, 0)
    layer = jax.jit(functools.partial(moe.moe_mlp, cfg.as_moe()))
    got, aux = layer(lp, y)
    want, top_e, want_aux = jax.jit(functools.partial(
        family._ref_expert_layer, config=config))(y, lp)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
    assert top_e.shape == (96, 2)
    assert trace.gauges()["moe.shared_gate"] == 1
    # a layer without w_s: the shared expert ungated, as it was
    bare = {k: v for k, v in lp.items() if k != "w_s"}
    ungated, _ = layer(bare, y)
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

    @jax.jit
    def by_hand(params, tokens):
        x = params["embed"][tokens].astype(cfg.dtype)
        aux = []
        for i, kind in enumerate(cfg.kinds):
            x, a = qwen3_next.block(
                cfg, None, kind, qwen3_next.layer_params(cfg, params, i), x)
            aux.append(a)
        return x, aux

    x, aux = by_hand(params, tokens)
    got, got_aux = jax.jit(functools.partial(
        qwen3_next.forward_layers, cfg=cfg))(params, tokens)
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
    assert abs(got - _plain_loss(params, tokens, changed)) < 2e-5


def test_live_rows_count_the_held_experts_pairs(built, config):
    fam, params, tokens = built
    rows = np.asarray(fam.live_rows(params, tokens))
    assert rows.shape == (8,) and rows.dtype == np.int32
    # the plain form's routers, layer by layer

    @jax.jit
    def counted(params, tokens):
        x = params["embed"][tokens]
        want = []
        for lp in family.layers_of(params):
            x, _, _, top_e, _ = family._ref_block(x, lp, config)
            want.append(jnp.sum(top_e < 2))
        return want

    for i, want in enumerate(counted(params, tokens)):
        assert rows[i] == int(want), i


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

    def ref_layer(lp, ref_cfg):
        return jax.jit(functools.partial(
            family._ref_expert_layer, config=ref_cfg))(y, lp)[0]

    want = ref_layer(lp, whole_cfg)
    shared = jax.jit(moe._shared_expert)(lp, y)
    total = shared
    for first in range(0, 8, 2):
        share = {k: v for k, v in lp.items()
                 if not k.startswith("ws_") and k != "w_s"}
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = lp[name][first:first + 2]
        share_cfg = dataclasses.replace(
            whole.cfg, experts_held=2, first_expert=first).as_moe()
        out, _ = jax.jit(functools.partial(moe.moe_mlp, share_cfg))(share, y)
        total = total + out
        # and one share alone is the plain form's share
        ref_share = ref_layer(
            {**lp, **{n: share[n] for n in ("w_gate", "w_up", "w_down")}},
            dict(whole_cfg, num_experts=2, first_expert=first))
        np.testing.assert_allclose(out + shared, ref_share, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-2   # experts weigh
