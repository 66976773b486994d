"""The qwen3_next family (``models/qwen3_next.py``) at a tiny size on the
CPU against the plain form of its equations (``benchmarks/families/
qwen3_next.py``: the delta rule token by token, explicit scores, a loop
over the experts): the loss and every gradient in both forms of the
Gated DeltaNet layer, and each term of the plain form. (The mixers, the
quarter rotary, the period and the share of the experts tied to the
uncut layer: ``test_qwen3_next_layers.py``; sizes, meshes and the
trainer: ``test_qwen3_next_mesh.py``; what the three share:
``qwen3_next_family.py``.)"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import qwen3_next as family
from dlrover_tpu.models import qwen3_next
from dlrover_tpu.observability import trace
from tests.qwen3_next_family import (  # noqa: F401  (fixtures by import)
    _plain_loss, built, config, gdn_form, mesh)


def test_loss_and_gradients_match_the_plain_form(built, config, gdn_form):
    fam, params, tokens = built
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.plain_loss(p, tokens, config)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
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
            leaf = np.asarray(slab[name])
            assert np.abs(leaf).reshape(len(leaf), -1).max(-1).min() > 0.0, (
                pos, name)


TERMS = ["shared", "shared_gate", "renormalize", "aux", "conv", "decay_rate",
         "dt_bias", "step", "head_norm", "out_gate", "q_norm", "attn_gate",
         "rotary", "norm_offset"]


@pytest.mark.parametrize("term", TERMS)
def test_each_term_moves_the_plain_form(built, config, term):
    """Each named term, changed in the plain form, moves its loss by far
    more than float32 rounding: a program that dropped it would be seen
    by ``test_loss_and_gradients_match_the_plain_form``."""
    _, params, tokens = built
    base = _plain_loss(params, tokens, config)
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
    moved = _plain_loss(p, tokens, changed)
    assert abs(moved - base) > 1e-4, (term, base, moved)
