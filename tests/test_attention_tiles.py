"""The flash kernels' own tiling: what `choose_tiles` returns for the
shapes that reach it, and the causal walk (blocks skipped above the
diagonal and never fetched, masked on and below it; the GQA group as
one operand; operands in the input's dtype) against the reference, in
interpret mode on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.observability import trace
from dlrover_tpu.ops import attention
from dlrover_tpu.ops.attention import (
    choose_tiles,
    flash_attention_with_lse,
    flash_tiles,
    mha_reference_with_lse,
)

BF16, F32 = jnp.bfloat16, jnp.float32

# (seq, head_dim, group, dtype, larger_than_128): the shapes that reach
# the chooser, per device
SHAPES = {
    "mistral7b-d5-steady": (4096, 128, 4, BF16, True),
    "mistral7b-d20-fsdp4-steady": (4096, 128, 4, BF16, True),
    "chip_smoke llama-3-8b": (2048, 128, 4, BF16, True),
    "ring chunk": (1024, 128, 4, BF16, True),
    "ring chunk, group 1": (1024, 128, 1, BF16, True),
    "vit-b/16 patches": (196, 64, 1, BF16, False),
    "vit-b/16 patches + cls": (197, 64, 1, BF16, False),
    "cpu tests, seq 8": (8, 32, 2, F32, False),
    "cpu tests, seq 64": (64, 32, 2, F32, False),
    "head_dim 64, mha": (2048, 64, 1, BF16, True),
    "group 8 (llama-3-70b)": (8192, 128, 8, BF16, True),
    "f32 at the cell's shape": (4096, 128, 4, F32, True),
    "seq 1000: aligned to 8 only": (1000, 128, 4, BF16, True),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("name", list(SHAPES))
def test_chosen_tiles_divide_and_fit(name, kernel):
    s, d, group, dtype, large = SHAPES[name]
    bq, bk = choose_tiles(kernel, s, s, d, group, dtype)
    assert s % bq == 0 and s % bk == 0
    used = attention._vmem_bytes(kernel, bq, bk, d, group,
                                 jnp.dtype(dtype).itemsize)
    assert used <= attention._VMEM_BUDGET < attention._VMEM_LIMIT
    # a side is a whole number of sublanes, or the whole sequence
    assert bq % 8 == 0 or bq == s
    assert bk % 8 == 0 or bk == s
    if kernel == "dkv":  # block_q is a block's lane dim there
        assert bq % 128 == 0 or bq == s
    if large:
        assert max(bq, bk) > 128 and min(bq, bk) >= 128, (bq, bk)


#: what the chooser returned for those shapes before a window entered
#: it (PR 60's tree): the same call still does
_SQUARE = {"fwd": (512, 512), "dq": (512, 512), "dkv": (1024, 1024)}
WITHOUT_A_WINDOW = {
    "mistral7b-d5-steady": _SQUARE,
    "mistral7b-d20-fsdp4-steady": _SQUARE,
    "chip_smoke llama-3-8b": _SQUARE,
    "ring chunk": _SQUARE,
    "ring chunk, group 1": dict(_SQUARE, fwd=(1024, 512), dq=(1024, 512)),
    "vit-b/16 patches": dict.fromkeys(_SQUARE, (196, 196)),
    "vit-b/16 patches + cls": dict.fromkeys(_SQUARE, (197, 197)),
    "cpu tests, seq 8": dict.fromkeys(_SQUARE, (8, 8)),
    "cpu tests, seq 64": dict.fromkeys(_SQUARE, (64, 64)),
    "head_dim 64, mha": dict(_SQUARE, fwd=(2048, 512), dq=(2048, 512)),
    "group 8 (llama-3-70b)": dict(_SQUARE, fwd=(256, 512), dq=(256, 512)),
    "f32 at the cell's shape": _SQUARE,
    "seq 1000: aligned to 8 only": {
        "fwd": (200, 1000), "dq": (200, 1000), "dkv": (1000, 1000)},
}


@pytest.mark.parametrize("name", list(SHAPES))
def test_without_a_window_the_tiles_are_what_they_were(name):
    s, d, group, dtype, _ = SHAPES[name]
    assert flash_tiles(s, s, d, group, dtype) == WITHOUT_A_WINDOW[name]
    for kernel, pair in WITHOUT_A_WINDOW[name].items():
        assert choose_tiles(kernel, s, s, d, group, dtype, window=None) == pair


def test_a_larger_group_takes_a_shorter_q_block():
    """The group's heads share the q tile's rows: at the same budget a
    group of 8 gets half the block_q a group of 4 gets."""
    g4 = choose_tiles("fwd", 4096, 4096, 128, 4, BF16)
    g8 = choose_tiles("fwd", 4096, 4096, 128, 8, BF16)
    assert g8[0] * 8 == g4[0] * 4
    assert g8[1] == g4[1]


def test_no_tile_for_a_long_prime_sequence():
    assert choose_tiles("fwd", 8191, 8191, 128, 4, BF16) is None
    assert flash_tiles(8191, 8191, 128, 4, BF16) is None
    q = jnp.zeros((1, 8191, 4, 128), BF16)
    with pytest.raises(ValueError, match="no tile"):
        flash_attention_with_lse(q, q, q, True, None, None, True)


def test_gauges_report_the_chosen_tiles():
    attention.reset_tile_report()
    q = jnp.zeros((1, 256, 4, 32), F32)
    kv = jnp.zeros((1, 256, 2, 32), F32)
    flash_attention_with_lse(q, kv, kv, True, None, None, True)
    g = trace.gauges()
    assert (g["attn.block_q"], g["attn.block_k"]) == (256, 256)
    assert g["attn.tile_fallback"] == 0
    # a sequence nothing above 128 divides falls back, and is counted
    q = jnp.zeros((1, 64, 4, 32), F32)
    kv = jnp.zeros((1, 64, 2, 32), F32)
    flash_attention_with_lse(q, kv, kv, True, None, None, True)
    flash_attention_with_lse(q, kv, kv, False, None, None, True)
    g = trace.gauges()
    assert (g["attn.block_q"], g["attn.block_k"]) == (64, 64)
    assert g["attn.tile_fallback"] == 2
    attention.reset_tile_report()
    assert trace.gauges()["attn.tile_fallback"] == 0


def _qkv(b, s, h, hkv, d, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype))


def _loss(fn):
    def f(q, k, v):
        out, lse = fn(q, k, v)
        # both outputs carry a cotangent: the lse one folds into delta
        return ((out.astype(F32) ** 2).sum() + (lse ** 2).sum())
    return f


#: bf16 inputs, f32 reference on the same rounded inputs: what is left
#: is the rounding of P and dS to bf16 before their matmuls (2**-9
#: relative an element) and of the outputs themselves (2**-9 of values
#: up to ~4 for out, ~30 for a gradient of this loss)
BF16_OUT_ATOL = 0.03
BF16_GRAD_RTOL = 0.02

# (block_q, block_k): None = chosen; the pinned ones are odd on purpose
TILES = [(None, None), (64, 32), (32, 128), (128, 64)]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("hkv", [4, 2])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tiles", TILES, ids=str)
def test_forward_and_all_gradients_match_reference(tiles, causal, hkv, dtype):
    """out, lse, dq, dk, dv and the lse cotangent at chosen and at pinned
    odd tiles: block_q != block_k puts blocks above, on and below the
    diagonal into every causal case."""
    q, k, v = _qkv(2, 256, 4, hkv, 32, dtype, seed=7)

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, causal, *tiles, True)

    def ref(q, k, v):
        return mha_reference_with_lse(q, k, v, causal=causal)

    out, lse = flash(q, k, v)
    ref_out, ref_lse = ref(q, k, v)
    assert out.dtype == dtype and lse.dtype == F32
    g1 = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    if dtype == F32:  # today's tolerances (tests/test_ops.py)
        np.testing.assert_allclose(out, ref_out, atol=2e-5)
        np.testing.assert_allclose(lse, ref_lse, atol=2e-5)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-5)
        return
    np.testing.assert_allclose(out.astype(F32), ref_out.astype(F32),
                               atol=BF16_OUT_ATOL)
    np.testing.assert_allclose(lse, ref_lse, atol=2e-5)  # lse stays f32
    for a, b in zip(g1, g2):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.dtype == np.float32
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err < BF16_GRAD_RTOL, err


def _block_kinds(sq, sk, bq, bk):
    """How many blocks of the causal score plane lie wholly above the
    diagonal (the walk skips them), wholly below it, and across it."""
    kinds = {"above": 0, "below": 0, "crossed": 0}
    for qi in range(sq // bq):
        for ki in range(sk // bk):
            q_lo, k_lo = qi * bq, ki * bk
            if k_lo > q_lo + bq - 1:
                kinds["above"] += 1
            elif k_lo + bk - 1 > q_lo:
                kinds["crossed"] += 1
            else:
                kinds["below"] += 1
    return kinds


@pytest.mark.parametrize("bq,bk", [(64, 32), (32, 128), (128, 128)])
def test_diagonal_crosses_tiles_every_kind_of_block_taken(bq, bk):
    """A case whose diagonal crosses a tile: blocks above, below and
    across the diagonal all occur, in forward, dq and dk/dv, and a block
    the diagonal only touches at a corner is still masked right."""
    kinds = _block_kinds(256, 256, bq, bk)
    assert all(kinds.values()), kinds
    # the clamped index maps name only blocks the walk computes
    n_q, n_k = 256 // bq, 256 // bk
    for qi in range(n_q):
        last = int(attention._last_k_block(qi, bq, bk, n_k))
        assert last * bk <= qi * bq + bq - 1 < (last + 1) * bk or (
            last == n_k - 1)
    for ki in range(n_k):
        first = int(attention._first_q_block(ki, bq, bk, n_q))
        assert first * bq <= ki * bk < (first + 1) * bq

    q, k, v = _qkv(1, 256, 8, 2, 32, F32, seed=11)

    def flash(q, k, v):
        return flash_attention_with_lse(q, k, v, True, bq, bk, True)

    def ref(q, k, v):
        return mha_reference_with_lse(q, k, v, causal=True)

    for a, b in zip(flash(q, k, v), ref(q, k, v)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    g1 = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-5)


def test_bf16_products_take_bf16_operands():
    """With bf16 inputs no matmul of the three kernels sees an f32
    operand; with f32 inputs none is rounded down."""
    def operand_dtypes(dtype):
        q, k, v = _qkv(1, 128, 4, 2, 32, dtype)

        def f(q, k, v):
            return _loss(lambda *a: flash_attention_with_lse(
                *a, True, 64, 64, True))(q, k, v)

        def walk(jaxpr, in_kernel):
            for eqn in jaxpr.eqns:
                if in_kernel and eqn.primitive.name == "dot_general":
                    seen.append(tuple(str(x.aval.dtype) for x in eqn.invars))
                    assert str(eqn.outvars[0].aval.dtype) == "float32"
                kernel = in_kernel or eqn.primitive.name == "pallas_call"
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, kernel)

        seen = []
        walk(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v).jaxpr,
             False)
        # 2 products in the forward, 3 in dq, 4 in dk/dv
        assert len(seen) == 2 + 3 + 4, len(seen)
        return set(seen)

    assert operand_dtypes(BF16) == {("bfloat16", "bfloat16")}
    assert operand_dtypes(F32) == {("float32", "float32")}
