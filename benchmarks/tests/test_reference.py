"""The plain reference against the program at a tiny size on the CPU,
and each check behind ``correct`` shown to fire."""

import numpy as np
import pytest

from conftest import make_ctx, one_device_mesh

from benchmarks.jobs import train_loop


def _params_and_tokens(ctx, seq=64, batch=2):
    import jax

    fam = ctx.family.build(ctx.config, one_device_mesh())
    params = fam.init_params(jax.random.key(3))
    tokens = jax.random.randint(
        jax.random.key(4), (batch, seq), 0, fam.cfg.vocab_size)
    return fam, params, tokens


def test_reference_agrees_with_program_in_float32():
    import jax

    fam, params, tokens = _params_and_tokens(make_ctx("tiny-cpu-steady"))
    program = float(jax.jit(fam.loss_fn)(params, tokens))
    reference = fam.reference_loss(params, tokens)
    # both in float32 here, so they agree to rounding; on the chip the
    # program computes in bfloat16 and the job allows REFERENCE_TOLERANCE
    assert abs(program - reference) < 1e-5


def test_reference_sees_a_changed_term():
    """A rotary base the program does not use moves the reference by more
    than float32 rounding: the comparison is not blind to the layer."""
    import jax

    ctx = make_ctx("tiny-cpu-steady")
    fam, params, tokens = _params_and_tokens(ctx)
    other = dict(ctx.config, rope_theta=100.0)
    from benchmarks.families import llama as family

    moved = family.reference_loss(params, tokens, other)
    assert abs(moved - fam.reference_loss(params, tokens)) > 1e-5


def test_correct_when_nothing_is_wrong():
    result = train_loop.run(make_ctx("tiny-cpu-save", seconds=0.5))
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["end_to_end"]) == {
        "saving_tokens_per_s", "step_p95_ms", "setup_s"}
    assert result["counters"]["save_stall_s"] > 0


@pytest.mark.parametrize("break_it", ["first_loss", "reference", "read_back"])
def test_correct_is_false_when_a_check_fails(break_it, monkeypatch):
    ctx = make_ctx("tiny-cpu-save", seconds=0.5)
    if break_it == "first_loss":
        monkeypatch.setattr(train_loop, "FIRST_LOSS_TOLERANCE", 1e-9)
    elif break_it == "reference":
        build = ctx.family.build

        def off_reference(config, mesh):
            fam = build(config, mesh)
            ref = fam.reference_loss
            fam.reference_loss = lambda p, t: ref(p, t) + 0.05
            return fam

        ctx.family = type(ctx.family)("family")
        ctx.family.build = off_reference
    else:
        from dlrover_tpu.checkpoint.checkpointer import Checkpointer

        load = Checkpointer.load

        def one_bit_off(self, target=None):
            step, state = load(self, target)
            leaf = np.asarray(state["params"]["lm_head"]).copy()
            leaf.view(np.uint32)[0, 0] ^= 1
            state["params"]["lm_head"] = leaf
            return step, state

        monkeypatch.setattr(Checkpointer, "load", one_bit_off)
    assert train_loop.run(ctx)["correct"] is False
