"""The step's gradient-reduction form has one source: ``TrainConfig``
and the mesh (with its slice count). ``_zero1_mode``, ``_hier_mode`` and
the ``WorldDescriptor`` spec are pure functions of them, and the
environment variables that once overrode them are read by nothing."""

import jax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

CFG = llama.LlamaConfig.tiny()

MESHES = {
    "dp4": dict(dp=4),
    "dp2xfsdp2": dict(dp=2, fsdp=2),
    "dp2xpp2": dict(dp=2, pp=2),
}


def _trainer(axes, n_slices=1, factory=True, **knobs):
    """No params, no init_state: the modes, the descriptor and the
    config hash read only the config, the mesh and the avatars."""
    mc = MeshConfig(**axes).resolve(4)
    mesh = build_mesh(mc, devices=jax.devices()[:4], n_slices=n_slices)
    tc = TrainConfig(global_batch_size=16, micro_batch_size=2,
                     warmup_steps=0, total_steps=100, **knobs)

    def loss_factory(m):
        return lambda p, t: llama.loss_fn(p, t, CFG, m)

    return ElasticTrainer(
        None if factory else loss_factory(mesh),
        llama.param_specs(CFG, pp=mc.pp), mesh, mc, tc,
        loss_factory=loss_factory if factory else None,
        n_slices=n_slices,
    )


# (mesh, n_slices, zero1, hier_collectives, overlap_collectives)
#   -> (_zero1_mode, _hier_mode, world_descriptor().spec)
FORMS = [
    ("dp4", 1, False, True, True, "off", "flat", "dp4"),
    ("dp4", 1, True, True, True, "scatter", "flat", "dp4+zero1"),
    # one slice: the hier / overlap fields decide nothing
    ("dp4", 1, True, False, False, "scatter", "flat", "dp4+zero1"),
    ("dp4", 2, False, True, True, "off", "overlap", "dp4+2slice+overlap"),
    ("dp4", 2, False, True, False, "off", "hier", "dp4+2slice"),
    # overlap never outlives hier
    ("dp4", 2, False, False, True, "off", "flat", "dp4"),
    ("dp4", 2, True, True, False, "scatter", "hier", "dp4+2slice+zero1"),
    ("dp4", 2, True, True, True, "scatter", "overlap",
     "dp4+2slice+overlap+zero1"),
    ("dp4", 2, True, False, True, "scatter", "flat", "dp4+zero1"),
    ("dp2xfsdp2", 1, False, True, True, "off", "flat", "dp2xfsdp2"),
    ("dp2xfsdp2", 1, True, True, True, "gspmd", "flat", "dp2xfsdp2+zero1"),
    # dp == n_slices: nothing left to reduce on ICI first
    ("dp2xfsdp2", 2, True, True, True, "gspmd", "flat", "dp2xfsdp2+zero1"),
    # pp: zero-1 does not compose with the pipeline schedules
    ("dp2xpp2", 1, True, True, True, "off", "flat", "dp2xpp2"),
    ("dp2xpp2", 2, False, True, True, "off", "flat", "dp2xpp2"),
]


@pytest.mark.parametrize(
    "mesh,n_slices,zero1,hier,overlap,zero1_mode,hier_mode,spec", FORMS,
    ids=[f"{f[0]}-{f[1]}slice-z{int(f[2])}h{int(f[3])}o{int(f[4])}"
         for f in FORMS],
)
def test_step_form_is_a_function_of_config_and_mesh(
    mesh, n_slices, zero1, hier, overlap, zero1_mode, hier_mode, spec
):
    tr = _trainer(MESHES[mesh], n_slices, zero1=zero1,
                  hier_collectives=hier, overlap_collectives=overlap)
    assert tr._zero1_mode(tr.mesh) == zero1_mode
    assert tr._hier_mode(tr.mesh) == hier_mode
    assert tr.world_descriptor().spec == spec


def test_step_form_without_a_loss_factory():
    """A plain ``loss_fn`` (every benchmark cell) has no single-device
    body to go manual with: zero-1 is GSPMD's, the reduction flat."""
    tr = _trainer(MESHES["dp4"], 2, factory=False, zero1=True)
    assert tr._zero1_mode(tr.mesh) == "gspmd"
    assert tr._hier_mode(tr.mesh) == "flat"
    assert tr.world_descriptor().spec == "dp4+zero1"


@pytest.mark.parametrize("name", [
    "DLROVER_TPU_ZERO1",
    "DLROVER_TPU_HIER_COLLECTIVES",
    "DLROVER_TPU_OVERLAP_COLLECTIVES",
    "DLROVER_TPU_OVERLAP_BUCKET_MB",
    "DLROVER_TPU_CHUNKED_CE",
])
def test_exported_legacy_switches_change_nothing(name, monkeypatch):
    monkeypatch.delenv(name, raising=False)
    tr = _trainer(MESHES["dp4"], 2)
    spec = tr.world_descriptor().spec
    config_hash = tr._config_hash(tr.mesh)
    assert spec == "dp4+2slice+overlap"
    for value in ("1", "0"):
        monkeypatch.setenv(name, value)
        exported = _trainer(MESHES["dp4"], 2)
        assert exported.world_descriptor().spec == spec
        assert exported._config_hash(exported.mesh) == config_hash
