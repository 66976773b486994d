"""What the two files of the laguna family's tests share
(``test_laguna.py``: the program against the plain form, the window's
edge, the rotary rules, the gate, the shares; ``test_laguna_mesh.py``:
sizes, gauges, meshes and the trainer): the tiny configuration, the
weighty parameters built from it, the plain form's loss under ``jit``
and the gradients' comparison. A file takes the fixtures by importing
them; ``built`` is an ``init`` and costs each file two or three seconds."""

import copy
import json
import os

import jax
import pytest

from benchmarks.families import laguna as family
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.plain_forms import jitted_plain_loss
from tests.smallthinker_family import _assert_grads_agree  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load_config("tiny-cpu-laguna.json")


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def changed(config: dict, path: str, value) -> dict:
    """``config`` with the key at ``path`` (``a/b/c`` into its nested
    groups) set to ``value``; the original is left as it was."""
    out = copy.deepcopy(config)
    *groups, key = path.split("/")
    into = out
    for group in groups:
        into = into[group]
    into[key] = value
    return out


def _weighty(params):
    """Norm weights away from one (and the two norms of a layer apart), a
    router that spreads its scores, projections that make attention, the
    gate and every feed-forward weigh (the configuration's out-proj sigma
    is 1e-4), so that every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 64))

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm"):
            lp[name] = lp[name] + 0.3 * jax.random.normal(
                next(keys), lp[name].shape)
        for name, by in (("router", 40.0), ("wq", 20.0), ("w_g", 30.0),
                         ("wo", 8e3), ("w_down", 2.4e4), ("ws_down", 8e3)):
            if name in lp:
                lp[name] = lp[name] * by
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0, **{
        group: {k: slab(v) for k, v in params[group].items()}
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


def _plain_loss(params, tokens, config):
    return float(jitted_plain_loss(family, config)(params, tokens))
