"""``harness/flops.py`` against the arithmetic it was copied from
(``bench.py:_model_flops_per_step``), for the benchmark's configurations."""

import importlib.util
import os

import pytest

from conftest import ROOT, load_json, one_device_mesh

from benchmarks.families import llama as family
from benchmarks.harness import flops


def _bench_py():
    spec = importlib.util.spec_from_file_location(
        "bench_py", os.path.join(ROOT, "bench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("config_name,batch,seq", [
    ("mistral-7b-v0.3-d5", 2, 4096),
    ("mistral-7b-v0.3-d20-fsdp4", 4, 4096),
])
def test_flops_agree_with_bench_py(config_name, batch, seq):
    config = load_json("configs", config_name + ".json")
    fam = family.build(config, one_device_mesh())
    ours = fam.flops_per_token(seq) * batch * seq
    theirs = _bench_py()._model_flops_per_step(fam.cfg, batch, seq)
    assert ours == pytest.approx(theirs, rel=1e-12)


def test_matmul_parameters_of_the_two_depths():
    sizes = dict(dim=4096, n_heads=32, n_kv_heads=8, head_dim=128,
                 ffn_dim=14336, vocab_size=32768)
    d5 = flops.dense_decoder_matmul_params(n_layers=5, **sizes)
    d20 = flops.dense_decoder_matmul_params(n_layers=20, **sizes)
    # one layer 218.1 M, the head 134.2 M (ISSUE 24)
    assert d5 == 5 * 218_103_808 + 134_217_728
    assert d20 - d5 == 15 * 218_103_808
