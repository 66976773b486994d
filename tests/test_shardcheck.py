"""shardcheck (dlrover_tpu/lint/shardcheck.py): IR parsers are exact on
the forms this jaxlib actually prints; every SC rule fires on a seeded
regression and stays quiet on the healthy program; the golden contracts
round-trip (generate → pass, seed → fail); and the trainer's lower-time
hook vetoes a violating build in strict mode — including for a
neighbor world that is not live."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.lint import contract_model, shardcheck
from dlrover_tpu.lint.__main__ import main as lint_main
from dlrover_tpu.models import llama

# ---------------------------------------------------------------------------
# parser units (text only — no lowering)
# ---------------------------------------------------------------------------


def test_parse_replica_groups_explicit():
    assert shardcheck.parse_replica_groups("{{0,2},{1,3}}") == [
        (0, 2), (1, 3)
    ]


def test_parse_replica_groups_iota():
    assert shardcheck.parse_replica_groups("[4,2]<=[8]") == [
        (0, 1), (2, 3), (4, 5), (6, 7)
    ]


def test_parse_replica_groups_iota_transpose():
    # arange(8).reshape(2,2,2).transpose(2,1,0).reshape(4,2)
    assert shardcheck.parse_replica_groups("[4,2]<=[2,2,2]T(2,1,0)") == [
        (0, 4), (2, 6), (1, 5), (3, 7)
    ]


def test_shape_bytes():
    assert shardcheck.shape_bytes("f32[2,16,64]") == 2 * 16 * 64 * 4
    assert shardcheck.shape_bytes("bf16[8]") == 16
    assert shardcheck.shape_bytes("f32[]") == 4
    assert shardcheck.shape_bytes("token[]") == 0


def test_tensor_type_dims():
    assert shardcheck.tensor_type_dims("8x16x256xf32") == (
        (8, 16, 256), "f32"
    )
    assert shardcheck.tensor_type_dims("f32") == ((), "f32")
    assert shardcheck.tensor_type_dims("?x4xf32") == ((), "")


def test_parse_sharding_forms():
    assert shardcheck.parse_sharding("{replicated}").kind == "replicated"
    assert shardcheck.parse_sharding("{maximal device=0}").kind == "maximal"
    tiled = shardcheck.parse_sharding("{devices=[4,1,2]<=[8]}")
    assert tiled.kind == "tiled" and tiled.tile_count == 8
    assert tiled.replicate_ways == 1
    part = shardcheck.parse_sharding(
        "{devices=[2,2,2]<=[2,2,2]T(2,1,0) last_tile_dim_replicate}"
    )
    assert part.tile_count == 4 and part.replicate_ways == 2


def test_mesh_spec_canonicalization():
    assert shardcheck.mesh_spec_of({"sp": 2, "dp": 2}) == "dp2xsp2"
    assert shardcheck.parse_mesh_spec("sp2xdp2") == {"sp": 2, "dp": 2}
    assert shardcheck.mesh_spec_of(
        shardcheck.parse_mesh_spec("sp2xdp2")
    ) == "dp2xsp2"
    with pytest.raises(ValueError):
        shardcheck.parse_mesh_spec("zz4")
    with pytest.raises(ValueError):
        shardcheck.parse_mesh_spec("dp")


def test_axis_attribution():
    coords = shardcheck.MeshCoords({"dp": 2, "fsdp": 2, "tp": 2})
    # tp: innermost — consecutive ids
    assert coords.attribute_groups([(0, 1), (2, 3), (4, 5), (6, 7)]) == "tp"
    # fsdp: middle — stride 2
    assert coords.attribute_groups([(0, 2), (1, 3), (4, 6), (5, 7)]) == "fsdp"
    # dp: outermost — stride 4
    assert coords.attribute_groups([(0, 4), (1, 5), (2, 6), (3, 7)]) == "dp"
    # fused data reduce over dp+fsdp
    assert coords.attribute_groups([(0, 2, 4, 6), (1, 3, 5, 7)]) == "dp+fsdp"
    # everything varies: still named by axes, never collapsed — the
    # same logical collective must key the same cell on every mesh
    assert coords.attribute_groups([tuple(range(8))]) == "dp+fsdp+tp"
    # single-axis mesh: a full-world reduce is labeled by its one axis
    assert shardcheck.MeshCoords({"dp": 4}).attribute_groups(
        [(0, 1, 2, 3)]
    ) == "dp"
    assert coords.attribute_pairs([(0, 4), (4, 0), (1, 5), (5, 1)]) == "dp"


# ---------------------------------------------------------------------------
# the lowered contract program (one compile, shared)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def contract_setup():
    trainer, state, batch = contract_model.build_contract_trainer(
        {"dp": 2, "fsdp": 2}
    )
    program = trainer.step_ir()
    program.label = "hlo:dp2xfsdp2"
    return trainer, state, batch, program


def test_healthy_program_is_clean(contract_setup):
    _, _, _, program = contract_setup
    assert shardcheck.check_program(program) == []


def test_census_attributes_real_collectives(contract_setup):
    _, _, _, program = contract_setup
    census = shardcheck.collective_census(program.hlo, program.coords())
    assert census, "a dp2xfsdp2 step with no collectives cannot be right"
    axes_seen = {k.split("|")[1] for k in census}
    assert "fsdp" in axes_seen  # param gathers / grad reduce-scatters
    assert "unattributed" not in axes_seen
    assert all(c["count"] > 0 for c in census.values())


def test_contract_roundtrip_and_seeded_regressions(
    contract_setup, tmp_path
):
    """generate → pass; then three seeded regressions each fail: a
    collective the contract never saw, count growth, byte growth."""
    _, _, _, program = contract_setup
    cdir = str(tmp_path)
    shardcheck.write_contract(cdir, "dp2xfsdp2", program)
    contract = shardcheck.load_contract(cdir, "dp2xfsdp2")
    assert shardcheck.check_census_against_contract(program, contract) == []

    key = next(iter(contract["census"]))
    # count growth: contract remembers one fewer op
    seeded = json.loads(json.dumps(contract))
    seeded["census"][key]["count"] -= 1
    v = shardcheck.check_census_against_contract(program, seeded)
    assert any("count grew" in x.message for x in v)

    # byte growth beyond tolerance
    seeded = json.loads(json.dumps(contract))
    seeded["census"][key]["bytes"] = int(
        seeded["census"][key]["bytes"] / 2
    )
    v = shardcheck.check_census_against_contract(program, seeded)
    assert any("bytes grew" in x.message for x in v)

    # a whole cell the contract never saw
    seeded = json.loads(json.dumps(contract))
    del seeded["census"][key]
    v = shardcheck.check_census_against_contract(program, seeded)
    assert any("new collective" in x.message for x in v)

    # model/config change: contract is for a different program
    seeded = json.loads(json.dumps(contract))
    seeded["config_hash"] = "0000deadbeef"
    v = shardcheck.check_census_against_contract(program, seeded)
    assert any("config_hash" in x.message for x in v)


def test_census_improvements_reported(contract_setup, tmp_path):
    _, _, _, program = contract_setup
    cdir = str(tmp_path)
    contract = shardcheck.write_contract(cdir, "dp2xfsdp2", program)
    key = next(iter(contract["census"]))
    contract["census"][key]["count"] += 3  # the program now does less
    census = shardcheck.collective_census(program.hlo, program.coords())
    notes = shardcheck.census_improvements(census, contract)
    assert notes and key in notes[0]


def test_checked_in_contracts_pass_for_all_three_meshes():
    """The acceptance gate: ``python -m dlrover_tpu.lint --hlo`` exits
    0 against the checked-in contracts for dp=4, dp=2×fsdp=2 and
    sp=2×dp=2 (the spec alone decides the variant)."""
    assert lint_main(
        ["--hlo", "dp4", "--hlo", "dp2xfsdp2", "--hlo", "sp2xdp2"]
    ) == 0


def test_async_start_collective_records_sent_shard_bytes():
    """An all-gather records the per-device SENT shard (result bytes /
    participants) — the unit every other op and the analytic comm
    ledger already use — and an async ``all-gather-start`` (whose type
    is an (operand, result) tuple) must fingerprint identically to the
    sync lowering of the same transfer."""
    coords = shardcheck.MeshCoords({"dp": 4})
    async_hlo = (
        "  %ags = (f32[4,8]{1,0}, f32[16,8]{1,0}) all-gather-start("
        "f32[4,8]{1,0} %p), replica_groups={{0,1,2,3}}, dimensions={0},"
        " use_global_device_ids=true\n"
    )
    sync_hlo = (
        "  %ag = f32[16,8]{1,0} all-gather(f32[4,8]{1,0} %p), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, "
        "use_global_device_ids=true\n"
    )
    a = shardcheck.collective_census(async_hlo, coords)
    s = shardcheck.collective_census(sync_hlo, coords)
    assert a == s == {"all-gather|dp": {"count": 1, "bytes": 4 * 8 * 4}}


def test_cli_rejects_mixed_ast_and_ir_modes():
    assert lint_main(["--hlo", "dp4", "--fix-baseline"]) == 2
    assert lint_main(["--hlo", "dp4", "--rule", "JG003"]) == 2
    assert lint_main(["--hlo", "dp4", "dlrover_tpu/"]) == 2
    assert lint_main(["--fix-contracts"]) == 2


def test_census_attribution_by_logical_position_not_device_id():
    """Replica-group members in post-GSPMD HLO are logical
    device-assignment positions. On a mesh whose device order is
    permuted (every real TPU torus mesh), mapping members through
    hardware ids would invert dp/fsdp attribution — decode them as
    flat mesh positions directly."""
    d = jax.devices()[:4]
    permuted = np.array([d[0], d[2], d[1], d[3]]).reshape(2, 2)
    mesh = Mesh(permuted, ("dp", "fsdp"))

    f = jax.jit(
        lambda x: x * 1.0,
        in_shardings=NamedSharding(mesh, P("dp", "fsdp")),
        out_shardings=NamedSharding(mesh, P("dp", None)),
    )
    hlo = f.lower(
        jax.ShapeDtypeStruct((8, 8), np.float32)
    ).compile().as_text()
    census = shardcheck.collective_census(
        hlo, shardcheck.MeshCoords({"dp": 2, "fsdp": 2})
    )
    assert set(census) == {"all-gather|fsdp"}, census


# ---------------------------------------------------------------------------
# SC002 — replicated large tensor
# ---------------------------------------------------------------------------


def _lower_with_constraint(spec):
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

    def f(x):
        y = jnp.einsum("bi,bj->bij", x, x)
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, spec)
        )
        return y.sum()

    av = jax.ShapeDtypeStruct(
        (8, 64), np.float32, sharding=NamedSharding(mesh, P("dp"))
    )
    return jax.jit(f).lower(av).as_text()


def test_sc002_fires_on_replicated_constraint():
    program = shardcheck.StepProgram(
        label="t", stablehlo=_lower_with_constraint(P()),
        axis_sizes={"dp": 4},
    )
    v = shardcheck.check_replicated_large(program, threshold_bytes=1024)
    assert v and v[0].rule == "SC002"
    assert "fully replicated" in v[0].message


def test_sc002_quiet_on_sharded_constraint_and_below_threshold():
    sharded = shardcheck.StepProgram(
        label="t", stablehlo=_lower_with_constraint(P("dp")),
        axis_sizes={"dp": 4},
    )
    assert shardcheck.check_replicated_large(sharded, 1024) == []
    replicated = shardcheck.StepProgram(
        label="t", stablehlo=_lower_with_constraint(P()),
        axis_sizes={"dp": 4},
    )
    # 8*64*64 f32 = 128 KiB < a 1 MiB threshold
    assert shardcheck.check_replicated_large(replicated, 1 << 20) == []


# ---------------------------------------------------------------------------
# SC003 — dense-vocab materialization (the chunked-CE gate)
# ---------------------------------------------------------------------------


def test_sc003_fires_when_dense_ce_reenabled():
    """A loss written as ``_ce_sums(forward(...))`` brings the [B,T,V]
    f32 logits back — shardcheck sees them in the lowered program."""
    trainer, _, _ = contract_model.build_contract_trainer(
        {"dp": 2, "fsdp": 2}
    )
    cfg = llama.LlamaConfig.tiny(
        vocab_size=contract_model.VOCAB,
        ce_chunk_size=contract_model.CE_CHUNK,
    )

    def dense_loss(mesh):
        def loss(params, tokens):
            nll_sum, n_valid = llama._ce_sums(
                llama.forward(params, tokens, cfg, mesh), tokens
            )
            return nll_sum / jnp.maximum(n_valid, 1.0)

        return loss

    trainer.loss_factory = dense_loss
    program = trainer.step_ir()
    v = [x for x in shardcheck.check_program(program)
         if x.rule == "SC003"]
    assert v, "dense CE must materialize a seq×vocab dot_general"
    assert "vocab=256" in v[0].message


def test_sc003_quiet_on_chunked_ce(contract_setup):
    _, _, _, program = contract_setup
    assert shardcheck.check_dense_vocab(program) == []


def test_sc003_silent_without_hints(contract_setup):
    _, _, _, program = contract_setup
    blind = shardcheck.StepProgram(
        label="t", stablehlo=program.stablehlo,
        axis_sizes=program.axis_sizes,
    )
    assert shardcheck.check_dense_vocab(blind) == []


# ---------------------------------------------------------------------------
# SC004 — output-sharding drift
# ---------------------------------------------------------------------------


def test_sc004_clean_when_pinned(contract_setup):
    _, _, _, program = contract_setup
    assert shardcheck.check_output_sharding_drift(program) == []


def test_sc004_fires_on_unpinned_outputs(contract_setup):
    trainer, _, _, _ = contract_setup
    program = trainer.step_ir(pinned=False)
    v = shardcheck.check_output_sharding_drift(program)
    assert v and all(x.rule == "SC004" for x in v)
    assert any("no pinned output sharding" in x.message for x in v)


def test_sc004_fires_on_wrong_pin():
    """Deliberately pinning a donated leaf to a DIFFERENT sharding than
    its input = the signature changes every step."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    sh_in = NamedSharding(mesh, P("dp"))
    sh_out = NamedSharding(mesh, P())  # wrong on purpose

    f = jax.jit(
        lambda s: ({"w": s["w"] * 2.0}, s["w"].sum()),
        donate_argnums=(0,),
        out_shardings=({"w": sh_out}, NamedSharding(mesh, P())),
    )
    av = {"w": jax.ShapeDtypeStruct((8, 8), np.float32, sharding=sh_in)}
    with pytest.warns(UserWarning, match="donated buffers were not usable"):
        stablehlo = f.lower(av).as_text()
    program = shardcheck.StepProgram(
        label="t", stablehlo=stablehlo, axis_sizes={"dp": 4},
    )
    v = shardcheck.check_output_sharding_drift(program)
    assert v and "lost its donation alias" in v[0].message


# ---------------------------------------------------------------------------
# SC005 — host transfer inside the step
# ---------------------------------------------------------------------------


def test_sc005_fires_on_debug_callback():
    def f(x):
        jax.debug.print("mean {m}", m=x.mean())
        return x * 2

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((8,), np.float32)
    )
    program = shardcheck.StepProgram(
        label="t", hlo=lowered.compile().as_text(), axis_sizes={},
    )
    v = shardcheck.check_host_transfer(program)
    assert v and v[0].rule == "SC005"
    assert "callback" in v[0].message


def test_sc005_quiet_on_clean_program(contract_setup):
    _, _, _, program = contract_setup
    assert shardcheck.check_host_transfer(program) == []


# ---------------------------------------------------------------------------
# the trainer hook (DLROVER_TPU_SHARDCHECK)
# ---------------------------------------------------------------------------


def _bad_contract_for(tmp_path, spec, program):
    """A contract that makes SC001 fire: same config hash, but a
    census that has never seen one of the program's collectives."""
    data = shardcheck.write_contract(str(tmp_path), spec, program)
    assert data["census"], "seeding needs at least one collective"
    del data["census"][next(iter(data["census"]))]
    with open(shardcheck.contract_path(str(tmp_path), spec), "w") as f:
        json.dump(data, f)


def test_hook_strict_vetoes_the_build(tmp_path, monkeypatch):
    trainer, state, batch = contract_model.build_contract_trainer(
        {"dp": 4}
    )
    program = trainer.step_ir()
    _bad_contract_for(tmp_path, "dp4", program)
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK", "2")
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK_CONTRACTS", str(tmp_path))
    trainer.warm.clear()  # force a fresh lowering through the hook
    with pytest.raises(shardcheck.ShardcheckError):
        trainer.lower_step(trainer.mesh, trainer.mesh_config)
    # strict step() build propagates the veto instead of silently
    # falling back to plain jit (which would run the rejected program)
    with pytest.raises(shardcheck.ShardcheckError):
        trainer.step(state, batch)


def test_hook_checks_speculative_neighbor_world(tmp_path, monkeypatch):
    """The hook runs for EVERY lowering, so a regression on the
    post-resize mesh is caught before the resize happens: lowering a
    world that is NOT live still gets vetoed."""
    from dlrover_tpu.parallel import build_mesh
    from dlrover_tpu.parallel.mesh import MeshConfig

    trainer, _, _ = contract_model.build_contract_trainer({"dp": 4})
    neighbor_mc = MeshConfig(dp=2).resolve(2)
    neighbor = build_mesh(neighbor_mc, devices=jax.devices()[:2])
    program = trainer.step_ir(neighbor, neighbor_mc)
    _bad_contract_for(tmp_path, "dp2", program)
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK", "2")
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK_CONTRACTS", str(tmp_path))
    trainer.warm.clear()
    with pytest.raises(shardcheck.ShardcheckError):
        trainer.lower_step(neighbor, neighbor_mc, source="speculative")


def test_hook_warn_mode_does_not_raise(tmp_path, monkeypatch, caplog):
    trainer, _, _ = contract_model.build_contract_trainer({"dp": 4})
    program = trainer.step_ir()
    _bad_contract_for(tmp_path, "dp4", program)
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK", "1")
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK_CONTRACTS", str(tmp_path))
    trainer.warm.clear()
    compiled, info = trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert compiled is not None and info["cache"] == "miss"


def test_hook_skips_contract_for_different_program(tmp_path, monkeypatch):
    """At lower time a config-hash mismatch means "no contract for this
    program" (the checked-in tiny-model contracts must not veto a real
    model training on the same mesh); only the CLI, where the program
    is pinned, treats a mismatch as a violation."""
    trainer, _, _ = contract_model.build_contract_trainer({"dp": 4})
    program = trainer.step_ir()
    data = shardcheck.write_contract(str(tmp_path), "dp4", program)
    data["config_hash"] = "0000deadbeef"  # some other model's contract
    del data["census"][next(iter(data["census"]))]  # would fire SC001
    with open(shardcheck.contract_path(str(tmp_path), "dp4"), "w") as f:
        json.dump(data, f)
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK", "2")
    monkeypatch.setenv("DLROVER_TPU_SHARDCHECK_CONTRACTS", str(tmp_path))
    trainer.warm.clear()
    compiled, _ = trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert compiled is not None  # no veto


def test_hook_off_by_default(contract_setup, monkeypatch):
    monkeypatch.delenv("DLROVER_TPU_SHARDCHECK", raising=False)
    trainer, _, _, _ = contract_setup
    trainer.warm.clear()
    compiled, _ = trainer.lower_step(trainer.mesh, trainer.mesh_config)
    assert compiled is not None


# ---------------------------------------------------------------------------
# SC007 — custom-call census (the kernel contract)
# ---------------------------------------------------------------------------

_KERNEL_HLO = """\
HloModule jit_step

ENTRY %main.1 (p0: f32[256,512], p1: bf16[512,128]) -> f32[256,8] {
  %p0 = f32[256,512]{1,0} parameter(0)
  %p1 = bf16[512,128]{1,0} parameter(1)
  %cc.1 = f32[256,8]{1,0} custom-call(f32[256,512]{1,0} %p0, bf16[512,128]{1,0} %p1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fused_ce_fwd/pallas_call"}
  %cc.2 = f32[256,8]{1,0} custom-call(f32[256,512]{1,0} %p0, bf16[512,128]{1,0} %p1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/attention_fwd/pallas_call"}
  %sh.1 = f32[256,8]{1,0} custom-call(f32[256,8]{1,0} %cc.1), custom_call_target="Sharding"
  ROOT %cc.3 = f32[256,8]{1,0} custom-call(f32[256,8]{1,0} %cc.2), custom_call_target="OtherLib"
}
"""


def test_sc007_census_parses_canned_hlo():
    census = shardcheck.custom_call_census(_KERNEL_HLO)
    # the partitioner's Sharding plumbing is benign — never censused
    assert "Sharding" not in census
    tcc = census["tpu_custom_call"]
    assert tcc["count"] == 2
    # two calls, identical shape signature -> one unique site
    assert tcc["sites"] == [
        "(f32[256,512], bf16[512,128]) -> f32[256,8]"
    ]
    assert census["OtherLib"]["count"] == 1


def test_sc005_never_flags_device_kernels():
    """A Pallas/Mosaic tpu_custom_call is a DEVICE kernel — the exact
    opposite of a host transfer. SC005 must stay quiet on it (SC007
    owns the kernel inventory); a genuine host callback on the same
    program still fires."""
    program = shardcheck.StepProgram(
        label="t", hlo=_KERNEL_HLO, axis_sizes={},
    )
    assert shardcheck.check_host_transfer(program) == []

    with_cb = _KERNEL_HLO.replace(
        'custom_call_target="OtherLib"',
        'custom_call_target="xla_ffi_python_cpu_callback"',
    )
    program = shardcheck.StepProgram(label="t", hlo=with_cb,
                                     axis_sizes={})
    v = shardcheck.check_host_transfer(program)
    assert len(v) == 1 and v[0].rule == "SC005"
    # and SC007's census still inventories the device kernels next to it
    assert "tpu_custom_call" in shardcheck.custom_call_census(with_cb)


def test_sc007_contract_roundtrip_and_seeded_regressions(
    contract_setup, tmp_path
):
    """generate → pass; a contracted kernel the program lacks fires the
    silent-fallback violation; an un-contracted kernel and count drift
    fire too; pre-SC007 contracts (no custom_calls section) skip."""
    _, _, _, program = contract_setup
    contract = shardcheck.write_contract(str(tmp_path), "dp2xfsdp2",
                                         program)
    assert "custom_calls" in contract
    assert shardcheck.check_custom_calls_against_contract(
        program, contract
    ) == []

    # the headline regression: the contract remembers a kernel the
    # program no longer lowers (dispatcher silently fell back)
    seeded = json.loads(json.dumps(contract))
    seeded["custom_calls"]["tpu_custom_call"] = {
        "count": 2,
        "sites": ["(f32[256,512], bf16[512,128]) -> f32[256,8]"],
    }
    v = shardcheck.check_custom_calls_against_contract(program, seeded)
    assert any(
        x.rule == "SC007" and "vanished" in x.message for x in v
    )

    # a kernel the contract never saw
    census = shardcheck.custom_call_census(program.hlo)
    census["tpu_custom_call"] = {"count": 1, "sites": ["() -> f32[1]"]}
    v = shardcheck.check_custom_calls_against_contract(
        program, contract, census=census
    )
    assert any(
        x.rule == "SC007" and "new custom-call kernel" in x.message
        for x in v
    )

    # count/shape drift on an existing target
    seeded = json.loads(json.dumps(contract))
    seeded["custom_calls"]["k"] = {"count": 1, "sites": ["() -> f32[1]"]}
    census = shardcheck.custom_call_census(program.hlo)
    census["k"] = {"count": 3, "sites": ["() -> f32[2]"]}
    v = shardcheck.check_custom_calls_against_contract(
        program, seeded, census=census
    )
    assert any(x.rule == "SC007" and "drifted" in x.message for x in v)

    # pre-SC007 contract: rule unarmed (regenerate to arm)
    legacy = json.loads(json.dumps(contract))
    del legacy["custom_calls"]
    assert shardcheck.check_custom_calls_against_contract(
        program, legacy
    ) == []

    # another model's contract: SC001 owns the hash mismatch report
    other = json.loads(json.dumps(contract))
    other["config_hash"] = "0000deadbeef"
    other["custom_calls"]["ghost"] = {"count": 1, "sites": []}
    assert shardcheck.check_custom_calls_against_contract(
        program, other
    ) == []


def test_sc007_seeded_kernel_drop_fails_cli(tmp_path, monkeypatch):
    """ISSUE 17 acceptance: regenerate the dp4 contract into a scratch
    dir, seed a kernel entry the CPU-lowered program cannot have, and
    the shardcheck CLI exits non-zero on exactly that contract."""
    cdir = str(tmp_path)
    assert lint_main(
        ["--hlo", "dp4", "--contracts", cdir, "--fix-contracts"]
    ) == 0
    assert lint_main(["--hlo", "dp4", "--contracts", cdir]) == 0

    path = shardcheck.contract_path(cdir, "dp4")
    with open(path) as f:
        data = json.load(f)
    data["custom_calls"]["tpu_custom_call"] = {
        "count": 1, "sites": ["(f32[8,8]) -> f32[8,8]"],
    }
    with open(path, "w") as f:
        json.dump(data, f)
    assert lint_main(["--hlo", "dp4", "--contracts", cdir]) == 1


def test_checked_in_contracts_carry_custom_calls_section():
    """Every checked-in contract is SC007-armed (regenerated after the
    rule landed): the section exists, so a kernel appearing on any
    contracted mesh diffs loudly even though the CPU census is empty."""
    cdir = os.path.join(
        os.path.dirname(shardcheck.__file__), "contracts"
    )
    specs = [
        f[:-5] for f in os.listdir(cdir)
        # the mem-* files are the OTHER contract family sharing this
        # dir (memcheck MC001); each loader rejects the other's files
        if f.endswith(".json") and not f.startswith("mem-")
    ]
    assert specs
    for spec in specs:
        contract = shardcheck.load_contract(cdir, spec)
        assert contract.get("custom_calls") is not None, spec
