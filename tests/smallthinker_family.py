"""What the two files of the smallthinker family's tests share
(``test_smallthinker.py``: the program against the plain form, the layout,
the router, the shares; ``test_smallthinker_mesh.py``: sizes, gauges,
meshes and the trainer): the tiny configuration, the weighty parameters
built from it, the plain form's loss under ``jit`` and the gradients'
comparison. A file takes the fixtures by importing them; ``built`` is an
``init`` and costs each file two or three seconds."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks.families import smallthinker as family
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.plain_forms import jitted_plain_loss


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-smallthinker.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one (and the two norms of a layer apart), a
    router that spreads its logits, projections that make attention and
    the experts weigh, so that every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 64))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
        lp["router"] = lp["router"] * 40.0
        lp["wq"] = lp["wq"] * 20.0
        lp["wo"] = lp["wo"] * 40.0
        lp["w_down"] = lp["w_down"] * 120.0
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0,
                layers={k: slab(v) for k, v in params["layers"].items()})


def _built(config, mesh, seq=48):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


@pytest.fixture(scope="module")
def built(config, mesh):
    return _built(config, mesh)


def _plain_loss(params, tokens, config):
    return float(jitted_plain_loss(family, config)(params, tokens))


def _assert_grads_agree(grads, want_grads, tol=3e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)   # off their meshes
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        assert err <= tol * scale + 1e-7, (
            jax.tree_util.keystr(path), err, scale)
