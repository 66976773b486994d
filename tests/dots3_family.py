"""What the files of the dots3 family's tests share (``test_dots3.py``: the
two parts of the loss against the plain form, the shares, the kernels in
interpret mode and what a block keeps; ``test_dots3_layout.py``: the
configuration's terms and the layout; ``test_dots3_mesh.py``: the meshes
and the trainer): the tiny configuration, the weighty parameters built
from it, and the comparisons. A file takes the fixtures by importing them;
``built`` is an ``init`` and costs each file two or three seconds."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks.families import dots3 as family
from dlrover_tpu.models import dots3
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.plain_forms import jitted_plain_loss


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


def _plain_terms(params, tokens, config):
    return [float(x) for x in jitted_plain_loss(family, config)(
        params, tokens)]


def _assert_grads_agree(grads, want_grads, tol=3e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        got, ref = np.asarray(got), np.asarray(ref)
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        assert err <= tol * scale + 1e-7, (
            jax.tree_util.keystr(path), err, scale)


def _is_indexer(path) -> bool:
    return any(name in jax.tree_util.keystr(path) for name in dots3.INDEXER)
