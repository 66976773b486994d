"""The kimi_linear family (``models/kimi_linear.py``) at a tiny size on the
CPU against the plain form of its equations (``benchmarks/families/
kimi_linear.py``: the delta rule token by token, explicit scores, a loop
over the experts): the loss and every gradient in both forms of the KDA
layer, and each term of the plain form. (The layer pattern, the two
attention kinds and the share of the experts tied to the uncut layer:
``test_kimi_linear_layers.py``; what the two share:
``kimi_linear_family.py``.)"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_linear as family
from dlrover_tpu.models import kimi_linear
from dlrover_tpu.observability import trace
from tests.kimi_linear_family import (  # noqa: F401  (fixtures by import)
    _plain_loss, built, config, kda_form, mesh)


def test_loss_and_gradients_match_the_plain_form(built, config, kda_form):
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
        assert err <= 3e-4 * scale + 1e-7, (jax.tree_util.keystr(path), err,
                                            scale)
    # top-k's indices give the choice bias no gradient, in either form
    run = grads["runs"][kimi_linear.run_name(1)]
    assert float(jnp.max(jnp.abs(run["router_bias"]))) == 0.0
    gauges = trace.gauges()
    assert gauges["kda.io_fused"] == gauges["kda.kernel"] == (
        kda_form == "kernels")
    # and every KDA parameter weighs
    for name in ("a_log", "dt_bias", "conv_q", "conv_k", "conv_v", "w_f1",
                 "w_g1", "b_g2", "w_b", "o_norm"):
        leaf = np.asarray(run[name])
        assert np.abs(leaf).reshape(len(leaf), -1).max(-1).min() > 0.0, name


TERMS = ["bias", "scaling", "shared", "renormalize", "conv", "decay_rate",
         "dt_bias", "gate_bias", "step", "head_norm", "latent_norm"]


@pytest.mark.parametrize("term", TERMS)
def test_each_term_moves_the_plain_form(built, config, term):
    """Each named term, changed in the plain form, moves its loss by far
    more than float32 rounding: a program that dropped it would be seen."""
    _, params, tokens = built
    base = _plain_loss(params, tokens, config)
    changed, p = copy.deepcopy(config), params

    def edit(name, fn):
        return dict(params, runs={
            k: ({**v, name: fn(v[name])} if name in v else v)
            for k, v in params["runs"].items()})

    if term == "bias":
        p = edit("router_bias", jnp.zeros_like)
    elif term == "scaling":
        changed["routed_scaling_factor"] = 1
    elif term == "shared":
        changed["num_shared_experts"] = 0
    elif term == "renormalize":
        changed["moe_renormalize"] = False
    elif term == "conv":
        # only the token's own tap: no convolution
        p = edit("conv_k", lambda w: w.at[..., :-1].set(0.0))
    elif term == "decay_rate":
        p = edit("a_log", lambda a: a + 1.0)
    elif term == "dt_bias":
        p = edit("dt_bias", jnp.zeros_like)
    elif term == "gate_bias":
        p = edit("b_g2", jnp.zeros_like)
    elif term == "step":
        p = edit("w_b", jnp.zeros_like)         # beta = 1/2 everywhere
    elif term == "head_norm":
        p = edit("o_norm", jnp.ones_like)
    else:
        p = edit("kv_a_norm", jnp.ones_like)
    moved = _plain_loss(p, tokens, changed)
    assert abs(moved - base) > 1e-4, (term, base, moved)
