"""What the two files of the phi4flash family's tests share
(``test_phi4flash.py``: the program against the plain reference, the
shared tensors, the window's edge, no position term, the LayerNorm;
``test_phi4flash_mesh.py``: sizes, FLOPs, the first loss, gauges, meshes
and the trainer): the tiny configuration and the weighty parameters built
from it. A file takes the fixtures by importing them."""

import json
import os

import jax
import pytest

from benchmarks.families import phi4flash as family
from dlrover_tpu.parallel import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BRANCH_ENDS = ("w_out", "w_o", "w_2", "w_down")


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load_config("tiny-cpu-phi4flash.json")


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one and biases away from zero (the two
    norms of a layer apart), branch-closing projections that make every
    mixer and feed-forward weigh (the configuration's sigma for them is
    1e-5), so that every term shows."""
    keys = iter(jax.random.split(jax.random.key(5), 256))

    def tree(lp):
        lp = dict(lp)
        for name in lp:
            if "norm" in name:
                lp[name] = lp[name] + 0.3 * jax.random.normal(
                    next(keys), lp[name].shape)
            elif name in BRANCH_ENDS:
                lp[name] = lp[name] * 2e3
        return lp

    out = {k: tree(v) if k in ("memory", "keys") else
           {p: tree(lp) for p, lp in v.items()} if k in ("first", "second")
           else v for k, v in params.items()}
    return dict(tree({k: out[k] for k in ("final_norm", "final_norm_b")}),
                **{k: v for k, v in out.items() if "final" not in k})


@pytest.fixture(scope="module")
def built(config, mesh):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 64), 0, fam.cfg.vocab_size)
    return fam, params, tokens
