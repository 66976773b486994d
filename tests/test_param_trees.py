"""Every family's parameter tree is the tree its checkpoints were written
with: the paths, shapes, dtypes and partition specs of each tiny
configuration, and what ``init_params`` draws under one key, against a
record taken at the commit before ``models/stack.py`` (PR 44's parent;
``qwen3_next``'s and ``minicpm_sala``'s at the PRs that added them, 45
and 48) and kept in
``tests/golden/param_trees.json``. A saved state restores by path
and shape, so a family that moves onto shared layout code must leave every
line of its record as it is.

A new family, or a change that means to alter a tree, rewrites the record:
``JAX_PLATFORMS=cpu python -m tests.test_param_trees`` from the repo's root
(and says so in its PR)."""

import json
import os

import jax
import numpy as np
import pytest

from dlrover_tpu.models import (
    dots3, granite_hybrid, keye_vl, kimi_linear, llama, minicpm_sala, moe,
    qwen3_next,
    smallthinker, vit, xing4)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "param_trees.json")

F, S = dots3.FULL, dots3.WINDOW
FAMILIES = {
    "llama": (llama, llama.LlamaConfig.tiny()),
    "moe": (moe, moe.MoeConfig.tiny()),
    "vit": (vit, vit.ViTConfig.tiny()),
    "xing4": (xing4, xing4.Xing4Config.tiny()),
    "kimi_linear": (kimi_linear, kimi_linear.KimiLinearConfig.tiny()),
    "smallthinker": (smallthinker, smallthinker.SmallThinkerConfig.tiny()),
    "dots3": (dots3, dots3.Dots3Config.tiny()),
    # two dense layers, two periods of two and a tail: every part a layout has
    "dots3_head_and_tail": (dots3, dots3.Dots3Config.tiny(
        layer_kinds=(F, S, F, S, F, S, F), n_dense_layers=2)),
    "qwen3_next": (qwen3_next, qwen3_next.Qwen3NextConfig.tiny()),
    "minicpm_sala": (minicpm_sala, minicpm_sala.MiniCPMSalaConfig.tiny()),
    "granite_hybrid": (granite_hybrid,
                       granite_hybrid.GraniteHybridConfig.tiny()),
    "keye_vl": (keye_vl, keye_vl.KeyeVLConfig.tiny(experts_held=4)),
}


def record(module, cfg) -> dict:
    """``{path: [shape, dtype, spec, sum, sum of magnitudes]}`` of the
    family's tree under ``jax.random.key(44)``."""
    params = module.init_params(cfg, jax.random.key(44))
    specs = module.param_specs(cfg)
    spec_of = {
        jax.tree_util.keystr(path): str(spec) for path, spec
        in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))[0]}
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = jax.tree_util.keystr(path)
        values = np.asarray(leaf, np.float64)
        out[key] = [list(leaf.shape), str(leaf.dtype), spec_of[key],
                    float(values.sum()), float(np.abs(values).sum())]
    assert sorted(out) == sorted(spec_of)
    return out


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_parameter_tree_is_the_one_checkpoints_were_written_with(name):
    with open(GOLDEN) as f:
        want = json.load(f)[name]
    got = record(*FAMILIES[name])
    assert list(got) == list(want), "the tree's paths, in flatten order"
    for path, (shape, dtype, spec, total, magnitude) in want.items():
        assert got[path][:3] == [shape, dtype, spec], path
        # the draws are the parent's draws (another key moves a leaf's sum
        # by the root of its size, far more than rounding on another host)
        np.testing.assert_allclose(
            got[path][3:], [total, magnitude], rtol=0,
            atol=1e-6 * max(magnitude, 1.0), err_msg=path)
    module, cfg = FAMILIES[name]
    assert module.param_count(cfg) == sum(
        int(np.prod(shape)) for shape, *_ in want.values())


def write(records: dict):
    """One line a leaf."""
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    families = [
        '"%s": {\n%s\n}' % (name, ",\n".join(
            f"{json.dumps(path)}: {json.dumps(row)}"
            for path, row in leaves.items()))
        for name, leaves in records.items()]
    with open(GOLDEN, "w") as f:
        f.write("{\n" + ",\n".join(families) + "\n}\n")


if __name__ == "__main__":
    write({name: record(*FAMILIES[name]) for name in sorted(FAMILIES)})
