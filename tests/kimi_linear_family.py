"""What the two files of the kimi_linear family's tests share
(``test_kimi_linear.py``: the loss and its gradients against the plain
form, term by term; ``test_kimi_linear_layers.py``: the configuration's
terms, the layer pattern, the two attention kinds, the share, the sizes
and the trainer): the tiny configuration, the weighty parameters built
from it, the plain form's loss under ``jit`` and the KDA layer's two
forms. A file takes the fixtures by importing them; ``built`` is an
``init`` and costs each file two or three seconds."""

import functools
import json
import os

import jax
import pytest

from benchmarks.families import kimi_linear as family
from dlrover_tpu.models import kimi_linear
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.plain_forms import jitted_plain_loss


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-kimi-linear.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from one, a choice bias that changes choices, a
    router that spreads its scores, decays and gates away from their
    init, so that every term weighs."""
    keys = iter(jax.random.split(jax.random.key(5), 256))

    def noisy(leaf, scale):
        return leaf + scale * jax.random.normal(next(keys), leaf.shape)

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "kv_a_norm", "o_norm"):
            if name in lp:
                lp[name] = noisy(lp[name], 0.3)
        if "a_log" in lp:
            lp["w_f2"] = lp["w_f2"] * 30.0
            lp["w_g2"] = lp["w_g2"] * 30.0
            lp["w_b"] = lp["w_b"] * 30.0
            lp["b_g2"] = noisy(lp["b_g2"], 0.5)
            lp["dt_bias"] = noisy(lp["dt_bias"], 1.0)
        else:
            lp["w_q"] = lp["w_q"] * 20.0
        if "router" in lp:
            lp["router"] = lp["router"] * 40.0
            lp["router_bias"] = noisy(lp["router_bias"], 0.4)
        lp["w_down"] = lp["w_down"] * 30.0
        lp["w_o"] = lp["w_o"] * 10.0
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0,
                runs={k: slab(v) for k, v in params["runs"].items()})


def _plain_loss(params, tokens, config):
    return float(jitted_plain_loss(family, config)(params, tokens))


@pytest.fixture(scope="module")
def built(config, mesh):
    fam = family.build(config, mesh)
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 48), 0, fam.cfg.vocab_size)
    return fam, params, tokens


@pytest.fixture(params=["xla", "kernels"])
def kda_form(request, monkeypatch):
    """The KDA layer's two forms: off the TPU it takes XLA's ops; with
    ``interpret`` its Pallas forms (the passes around the delta rule and
    the delta rule's kernels), which hold the layer's wiring of them."""
    if request.param == "kernels":
        monkeypatch.setattr(kimi_linear, "kda_attention", functools.partial(
            kimi_linear.kda_attention, interpret=True))
    return request.param
