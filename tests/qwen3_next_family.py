"""What the three files of the qwen3_next family's tests share
(``test_qwen3_next.py``: the loss and its gradients against the plain
form, term by term; ``test_qwen3_next_layers.py``: the configuration's
terms, the mixers and the expert layer, the period, the share;
``test_qwen3_next_mesh.py``: the sizes, the meshes and the trainer): the
tiny configuration, the weighty parameters built from it, the plain
form's loss under ``jit`` and the Gated DeltaNet layer's two forms. A
file takes the fixtures by importing them; ``built`` is an ``init`` and
costs each file two or three seconds."""

import functools
import json
import os

import jax
import pytest

from benchmarks.families import qwen3_next as family
from dlrover_tpu.models import qwen3_next
from dlrover_tpu.parallel import MeshConfig, build_mesh
from tests.plain_forms import jitted_plain_loss


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmarks", "configs", "tiny-cpu-qwen3-next.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig().resolve(1), devices=jax.devices()[:1])


def _weighty(params):
    """Norm weights away from their init (the stored offsets away from
    zero), a router that spreads its scores, decays, steps and gates away
    from their init, so that every term weighs."""
    keys = iter(jax.random.split(jax.random.key(5), 256))

    def noisy(leaf, scale):
        return leaf + scale * jax.random.normal(next(keys), leaf.shape)

    def slab(lp):
        lp = dict(lp)
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "o_norm"):
            if name in lp:
                lp[name] = noisy(lp[name], 0.3)
        if "a_log" in lp:
            lp["w_qkvz"] = lp["w_qkvz"] * 20.0
            lp["w_ba"] = lp["w_ba"] * 30.0
            lp["dt_bias"] = noisy(lp["dt_bias"], 0.5)
        else:
            lp["w_q"] = lp["w_q"] * 20.0
            lp["w_k"] = lp["w_k"] * 20.0
        lp["router"] = lp["router"] * 40.0
        lp["w_s"] = lp["w_s"] * 40.0
        lp["w_down"] = lp["w_down"] * 30.0
        lp["ws_down"] = lp["ws_down"] * 30.0
        lp["w_o"] = lp["w_o"] * 10.0
        return lp

    return dict(params, lm_head=params["lm_head"] * 10.0,
                final_norm=noisy(params["final_norm"], 0.3),
                layers={k: slab(v) for k, v in params["layers"].items()})


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
def gdn_form(request, monkeypatch):
    """The Gated DeltaNet layer's two forms: off the TPU it takes XLA's
    ops; with ``interpret`` the chip's path on the CPU: the per-head
    delta rule's two kernels and the Pallas passes around them (the
    convolution with its norms, the head norm with its SiLU gate)."""
    if request.param == "kernels":
        monkeypatch.setattr(qwen3_next, "gdn_attention", functools.partial(
            qwen3_next.gdn_attention, interpret=True))
    return request.param
