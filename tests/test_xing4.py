"""The xing4 family (``models/xing4.py``) at a tiny size on the CPU against
the plain form of its equations (``benchmarks/families/xing4.py``: no
kernel, no sort, explicit scores, Sinkhorn as written): the loss and
every gradient, and each term of the plain form. (The
configuration's terms in the program, the share of the experts, the
stream coefficients and the trainer: ``test_xing4_layers.py``; what the
two share: ``xing4_family.py``.)"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import xing4 as family
from tests.xing4_family import (  # noqa: F401  (fixtures by import)
    built, config, mesh)


def test_loss_and_gradients_match_the_plain_form(built, config):
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
        assert err <= 2e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err,
                                            scale)
    # top-k's indices give the choice bias no gradient, in either form
    assert float(jnp.max(jnp.abs(grads["layers"]["router_bias"]))) == 0.0
    # and every stream coefficient weighs
    for name in ("hc_attn_alpha", "hc_mlp_alpha", "hc_attn_bias",
                 "hc_mlp_phi"):
        assert np.abs(np.asarray(
            grads["layers"][name])).max(-1).min() > 0.0, name


TERMS = ["bias", "scaling", "shared", "mscale", "yarn", "clamp", "mtp",
         "norm_topk_prob", "hc_eps"]


@pytest.mark.parametrize("term", TERMS)
def test_each_term_moves_the_plain_form(built, config, term):
    """Each named term, left out of the plain form, moves its loss by far
    more than float32 rounding: a program that dropped it would be seen."""
    _, params, tokens = built
    base = float(family.plain_loss(params, tokens, config))
    changed, p = copy.deepcopy(config), params
    if term == "bias":
        zero = lambda lp: dict(lp, router_bias=jnp.zeros_like(
            lp["router_bias"]))
        p = dict(params, layers=zero(params["layers"]))
    elif term == "scaling":
        changed["routed_scaling_factor"] = 1
    elif term == "shared":
        changed["n_shared_experts"] = 0
    elif term == "mscale":
        changed["rope_scaling"]["mscale_all_dim"] = 0
        changed["rope_scaling"]["mscale"] = 0
    elif term == "yarn":
        # plain frequencies at the same temperature
        changed["rope_scaling"]["beta_fast"] = 1e-9
        changed["rope_scaling"]["beta_slow"] = 1e-9
    elif term == "clamp":
        changed["mhc_h_res_clamp_max"] = 0.5
    elif term == "mtp":
        changed["assumed"]["mtp_loss_weight"] = 0.0
    elif term == "norm_topk_prob":
        changed["norm_topk_prob"] = False
    else:
        changed["hc_eps"] = 0.3
    moved = float(family.plain_loss(p, tokens, changed))
    assert abs(moved - base) > 1e-4, (term, base, moved)
