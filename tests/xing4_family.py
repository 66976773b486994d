"""What the two files of the xing4 family's tests share (``test_xing4.py``:
the loss and its gradients against the plain form, term by term;
``test_xing4_layers.py``: the configuration's terms, the share, the
stream coefficients, the sizes and the trainer): the tiny configuration
and the weighty parameters built from it. A file takes the fixtures by
importing them; ``built`` is an ``init`` and costs each file two or three
seconds."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.families import xing4 as family
from dlrover_tpu.parallel import MeshConfig, build_mesh


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-xing4.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one, a choice bias that changes choices, a
    router that spreads its scores and stream coefficients large enough
    that every one of them weighs."""
    keys = iter(jax.random.split(jax.random.key(5), 64))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
        for sub in ("hc_attn", "hc_mlp"):
            lp[f"{sub}_alpha"] = jnp.full_like(lp[f"{sub}_alpha"], 0.7)
            lp[f"{sub}_phi"] = lp[f"{sub}_phi"] * 20.0
            lp[f"{sub}_bias"] = lp[f"{sub}_bias"] + 0.5 * jax.random.normal(
                next(keys), lp[f"{sub}_bias"].shape)
        if "router" in lp:
            lp["router"] = lp["router"] * 40.0
            lp["router_bias"] = 0.4 * jax.random.normal(
                next(keys), lp["router_bias"].shape)
        lp["w_down"] = lp["w_down"] * 30.0
        lp["w_o"] = lp["w_o"] * 10.0
        lp["w_qb"] = lp["w_qb"] * 20.0
        return lp

    params = dict(params, dense=slab(params["dense"]),
                  layers=slab(params["layers"]),
                  lm_head=params["lm_head"] * 10.0)
    if "mtp" not in params:
        return params
    params["mtp"] = dict(params["mtp"], block=slab(params["mtp"]["block"]))
    for name in ("enorm", "hnorm", "norm"):
        params["mtp"][name] = params["mtp"][name] + 0.3 * jax.random.normal(
            next(keys), params["mtp"][name].shape)
    return params


@pytest.fixture(scope="module")
def built(config, mesh):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 48), 0, fam.cfg.vocab_size)
    return fam, params, tokens
