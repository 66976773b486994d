"""The dots3 family's configuration (see ``test_dots3.py``): each term
moves the plain form and the program alike; the layout is read from
``layer_types``; the published config is the default; what the program
does not compute is refused; the listed cut's parameter count."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks.families import dots3 as family
from dlrover_tpu.models import dots3
from tests.dots3_family import (  # noqa: F401  (fixtures by import)
    F, S, _built, _load, _plain_terms, _terms, built, config, mesh)


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
        base = _plain_terms(params, tokens, config)
    want = _plain_terms(params, tokens, changed)
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
    want = _plain_terms(params, tokens, changed)
    np.testing.assert_allclose(
        [float(x) for x in got], want, rtol=3e-5, atol=2e-5)
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
