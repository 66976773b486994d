"""The phi4flash family's comparisons that decide ``correct``, shown to
fail for each wrong program the limits are there to catch: the memory
without ``D``'s term, the memory taken after the producer's gate, the C
layers reading another layer's keys and values, a window off by one; and
shown to pass for the program itself and for the reference rounded to
bfloat16. At the tiny size on the CPU, with weights that make every term
weigh (at sigma 1e-5 the branches would hide them)."""

import pytest

from conftest import cell_metrics, load_json, one_device_mesh

from benchmarks.families import phi4flash as family
from benchmarks.families.smallthinker import _round_trip

CELL = "phi4flash-1chip-steady"
BRANCH_ENDS = ("w_out", "w_o", "w_2", "w_down")


def _weighty(params):
    import jax

    keys = iter(jax.random.split(jax.random.key(5), 256))

    def tree(lp):
        lp = dict(lp)
        for name in lp:
            if "norm" in name:
                lp[name] = lp[name] + 0.3 * jax.random.normal(
                    next(keys), lp[name].shape)
            elif name in BRANCH_ENDS:
                lp[name] = lp[name] * 2e3
            elif name in ("w_qkv", "w_q"):
                lp[name] = lp[name] * 8.0     # scores of order one
        return lp

    return {k: tree(v) if k in ("memory", "keys") else
            {p: tree(lp) for p, lp in v.items()} if k in ("first", "second")
            else v for k, v in params.items()}


@pytest.fixture(scope="module")
def built():
    import jax

    config = load_json("configs", "tiny-cpu-phi4flash.json")
    fam = family.build(config, one_device_mesh())
    params = _weighty(fam.init_params(jax.random.key(3)))
    tokens = jax.random.randint(
        jax.random.key(4), (2, 64), 0, fam.cfg.vocab_size)
    return config, fam, params, tokens


def test_the_program_passes_every_limit(built):
    config, fam, params, tokens = built
    read, _ = family.compare(
        params, tokens, config,
        family._Program(fam.cfg, one_device_mesh(), params, tokens))
    assert set(family.LIMITS) <= set(read)
    assert family._report("program", read)


def test_the_reference_rounded_to_bfloat16_passes_and_to_float8_fails(built):
    import jax.numpy as jnp

    config, _, params, tokens = built
    passed = {}
    for name, dtype in (("bfloat16", jnp.bfloat16),
                        ("float8", jnp.float8_e4m3fn)):
        read, _ = family.compare(params, tokens, config, family._Rounded(
            config, params, tokens, _round_trip(dtype)))
        passed[name] = family._report(name, read)
    assert passed == {"bfloat16": True, "float8": False}


@pytest.mark.parametrize("mutate, fails", [
    ("no_d", "memory_rel_median"),
    ("after_gate", "memory_rel_median"),
    ("other_keys", "wired_cross_rel_median"),
    ("window_off_by_one", "window_attn_rel_median"),
])
def test_a_wrong_program_fails_the_limit_that_is_there_for_it(
        built, mutate, fails):
    config, _, params, tokens = built
    read, _ = family.compare(params, tokens, config, family._Rounded(
        config, params, tokens, mutate=mutate))
    assert read[fails] > family.LIMITS[fails], (mutate, read[fails])
    assert not family._report(mutate, read)


def test_the_cell_lists_the_familys_metrics():
    names = cell_metrics(CELL)
    for name in ("p4f_mamba_ms", "p4f_mamba_scan_ms", "p4f_sscan_roofline",
                 "p4f_gmu_ms", "p4f_attn_proj_ms", "p4f_full_flash_roofline",
                 "p4f_swa_flash_roofline", "p4f_norm_ms", "mfu",
                 "flash_attn_ms", "fused_ce_ms", "embed_ms", "hbm_peak_gib"):
        assert name in names, name
