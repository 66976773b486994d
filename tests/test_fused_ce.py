"""Fused-CE Pallas kernel (ops/fused_ce.py): value and grad parity with
the dense and chunked references under interpret mode (masks, tile sizes
that do not divide tokens/vocab), the ``cross_entropy_sums`` dispatch
contract (TPU-gated, DLROVER_TPU_FUSED_CE=0 kill-switch), and
composition with the trainer's grad-accumulation scan."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import chunked_ce, fused_ce
from dlrover_tpu.ops.fused_ce import (
    cross_entropy_sums,
    fused_ce_available,
    fused_ce_enabled,
    fused_cross_entropy,
)

jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------


def dense_ce_sums(x, w, targets):
    logits = x @ w
    valid = (targets >= 0).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.sum((logz - gold) * valid), jnp.sum(valid)


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30)


B, T, D, V = 3, 8, 16, 300


@pytest.fixture(scope="module")
def xwt():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.1, jnp.float32)
    t = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    t = t.at[:, -2:].set(-1)  # masked/ignored tail
    t = t.at[0, 0].set(-1)
    return x, w, t


# ---------------------------------------------------------------------------
# kernel parity (interpret mode: exact Pallas program, CPU numerics)
# ---------------------------------------------------------------------------

# (block_t, block_v) matrix: minima (8, 128); vocab tile not dividing
# V=300 (padded final tile); token tile not dividing B*T=24; tiles
# larger than the whole problem (single-tile degenerate case)
TILES = [(8, 128), (16, 128), (8, 256), (64, 512)]


@pytest.mark.parametrize("bt,bv", TILES)
def test_value_matches_dense(xwt, bt, bv):
    x, w, t = xwt
    ns, nv = fused_cross_entropy(
        x, w, t, block_t=bt, block_v=bv, interpret=True
    )
    ds, dv = dense_ce_sums(x, w, t)
    assert float(nv) == float(dv) == B * T - 7
    assert rel_err(ns, ds) <= 1e-5


@pytest.mark.parametrize("bt,bv", [(8, 128), (64, 512)])
def test_grads_match_dense_and_chunked(xwt, bt, bv):
    x, w, t = xwt

    def mean_loss(ce):
        def f(x, w):
            ns, nv = ce(x, w)
            return ns / jnp.maximum(nv, 1.0)

        return f

    gf = jax.grad(
        mean_loss(lambda x, w: fused_cross_entropy(
            x, w, t, block_t=bt, block_v=bv, interpret=True)),
        argnums=(0, 1),
    )(x, w)
    gd = jax.grad(mean_loss(lambda x, w: dense_ce_sums(x, w, t)),
                  argnums=(0, 1))(x, w)
    gc = jax.grad(
        mean_loss(lambda x, w: chunked_ce.chunked_cross_entropy(
            x, w, t, chunk_size=128)),
        argnums=(0, 1),
    )(x, w)
    for got, ref in ((gf[0], gd[0]), (gf[1], gd[1]),
                     (gf[0], gc[0]), (gf[1], gc[1])):
        assert rel_err(got, ref) <= 1e-5


def test_all_tokens_masked(xwt):
    x, w, _ = xwt
    t = jnp.full((B, T), -1, jnp.int32)

    def loss(x, w):
        ns, nv = fused_cross_entropy(x, w, t, interpret=True)
        return ns / jnp.maximum(nv, 1.0)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    assert float(val) == 0.0
    assert float(jnp.max(jnp.abs(grads[0]))) == 0.0
    assert float(jnp.max(jnp.abs(grads[1]))) == 0.0


def test_bf16_operands_f32_accumulation(xwt):
    x, w, t = xwt
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    ns, nv = fused_cross_entropy(xb, wb, t, interpret=True)
    ds, dv = dense_ce_sums(xb.astype(jnp.float32),
                           wb.astype(jnp.float32), t)
    assert ns.dtype == jnp.float32  # accumulation contract
    assert float(nv) == float(dv)
    assert rel_err(ns, ds) <= 1e-5
    g = jax.grad(
        lambda x, w: fused_cross_entropy(x, w, t, interpret=True)[0],
        argnums=(0, 1),
    )(xb, wb)
    assert g[0].dtype == jnp.bfloat16 and g[1].dtype == jnp.bfloat16


def test_shape_validation(xwt):
    x, w, t = xwt
    with pytest.raises(ValueError, match="targets shape"):
        fused_cross_entropy(x, w, t[:, :-1], interpret=True)
    with pytest.raises(ValueError, match="w_unembed rows"):
        fused_cross_entropy(x[..., :-1], w, t, interpret=True)


def test_composes_under_jit_and_scan(xwt):
    """The trainer's grad-accum wraps value_and_grad in a lax.scan; the
    custom_vjp must be opaque to that outer AD + scan."""
    x, w, t = xwt
    micro_x = jnp.stack([x, x * 0.5])

    def loss(w, xb):
        ns, nv = fused_cross_entropy(xb, w, t, interpret=True)
        return ns / jnp.maximum(nv, 1.0)

    @jax.jit
    def accum(w, micro_x):
        def body(carry, xb):
            s, g = carry
            l, gw = jax.value_and_grad(loss)(w, xb)
            return (s + l, jax.tree.map(jnp.add, g, gw)), None

        (s, g), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros_like(w)), micro_x
        )
        return s / 2, g

    s, g = accum(w, micro_x)
    expect = (loss(w, x) + loss(w, x * 0.5)) / 2
    assert rel_err(s, expect) <= 1e-6
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# tile geometry: every kernel blocks the whole feature dim, so its VMEM
# grows with d (at the default 16 MiB grant the chip's compiler refused
# 256x512 at d=4096): the kernels ask for _VMEM_LIMIT and the tiles
# shrink only past _VMEM_BUDGET
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize(
    "d,x_dtype,w_dtype",
    [
        (16, jnp.float32, jnp.float32),
        (2048, jnp.bfloat16, jnp.bfloat16),
        (4096, jnp.bfloat16, jnp.bfloat16),   # Llama-3-8B
        (4096, jnp.bfloat16, jnp.float32),    # f32 master weights
        (4096, jnp.float32, jnp.float32),
        (6144, jnp.bfloat16, jnp.bfloat16),
        (8192, jnp.bfloat16, jnp.bfloat16),   # Llama-3-70B
    ],
)
def test_tile_geometry_fits_vmem_budget(d, x_dtype, w_dtype, backward):
    n, v = 8192, 128256
    bt, bv, n_pad, v_pad = fused_ce._tile_geometry(
        n, v, d, x_dtype, w_dtype,
        fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, backward,
    )
    xb, wb = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    assert fused_ce._vmem_bytes(bt, bv, d, xb, wb, backward) \
        <= fused_ce._VMEM_BUDGET
    assert bt % 8 == 0 and bv % 128 == 0
    assert n_pad % bt == 0 and v_pad % bv == 0
    assert n_pad >= n and v_pad >= v
    if d <= 4096 and jnp.dtype(x_dtype).itemsize == 2:
        # MXU-sized up to Llama-3-8B's width; 128256 = 334 x 384, so
        # the vocabulary tile is the one that needs no padded head
        assert (bt, bv) == (fused_ce.DEFAULT_BLOCK_T, 384)
        assert v_pad == v
    assert bt >= 128 and bv >= 128


@pytest.mark.parametrize("v,want_bv,padded", [
    (32768, 512, False),    # Mistral: 512 divides, as before
    (50304, 384, False),    # OLMoE: 131 x 384
    (128256, 384, False),   # Llama 3: 334 x 384
    (50257, 512, True),     # GPT-2: no multiple of 128 divides; padded
    (256, 256, False),      # narrower than the tile: one block
])
def test_vocab_tile_divides_the_vocabulary_where_one_can(v, want_bv, padded):
    _, bv, _, v_pad = fused_ce._tile_geometry(
        8192, v, 2048, jnp.bfloat16, jnp.bfloat16,
        fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, True,
    )
    assert bv == want_bv and (v_pad != v) == padded


def test_tile_geometry_says_when_d_cannot_fit():
    with pytest.raises(ValueError, match="DLROVER_TPU_FUSED_CE=0"):
        fused_ce._tile_geometry(
            8192, 128256, 65536, jnp.bfloat16, jnp.bfloat16,
            fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, True,
        )


@pytest.mark.parametrize("fsdp,tp", [(4, 1), (2, 2)])
def test_kernel_runs_per_shard_under_a_mesh(xwt, monkeypatch, fsdp, tp):
    """Over more than one device the dispatcher runs the kernel on each
    device's tokens under shard_map (the partitioner refuses a Mosaic
    kernel) with the head gathered whole; sums and grads match the
    chunked path on the unsharded operands."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel.mesh import BATCH_AXES, FSDP, TP

    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")
    mesh = build_mesh(MeshConfig(dp=-1, fsdp=fsdp, tp=tp),
                      devices=jax.devices()[: fsdp * tp])
    _, w, _ = xwt
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, T, D)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, size=(4, T)), jnp.int32)
    t = t.at[:, -1].set(-1)
    xs = jax.device_put(x, NamedSharding(mesh, P(BATCH_AXES, None, None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(FSDP, TP)))
    ts = jax.device_put(t, NamedSharding(mesh, P(BATCH_AXES, None)))

    def mean_nll(x, w, t, **kw):
        ns, nv = cross_entropy_sums(x, w, t, chunk_size=64, **kw)
        return ns / nv

    sharded = jax.jit(jax.value_and_grad(
        lambda x, w: mean_nll(x, w, ts, interpret=True, mesh=mesh),
        argnums=(0, 1),
    ))
    assert "shard_map" in str(jax.make_jaxpr(sharded)(xs, ws))
    val, (gx, gw) = sharded(xs, ws)
    ref, (rx, rw) = jax.value_and_grad(
        lambda x, w: mean_nll(x, w, t), argnums=(0, 1)
    )(x, w)  # CPU, no interpret: the chunked path
    assert rel_err(val, ref) <= 1e-6
    assert rel_err(gx, rx) <= 1e-5
    assert rel_err(gw, rw) <= 1e-5


# ---------------------------------------------------------------------------
# dispatch contract: TPU-gated, kill-switch, fallback equivalence
# ---------------------------------------------------------------------------


def test_dispatcher_falls_back_off_tpu(xwt, monkeypatch):
    """On CPU (no interpret), cross_entropy_sums must take the chunked
    scan even with the flag on — an _fce program must never silently
    mean "chunked measured under a fused name"."""
    x, w, t = xwt
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")
    assert fused_ce_enabled()
    assert not fused_ce_available()  # CPU backend, no interpret
    ns, nv = cross_entropy_sums(x, w, t, chunk_size=64)
    cs, cv = chunked_ce.chunked_cross_entropy(x, w, t, chunk_size=64)
    assert float(nv) == float(cv)
    assert rel_err(ns, cs) <= 1e-6
    with pytest.raises(RuntimeError, match="needs the TPU backend"):
        fused_cross_entropy(x, w, t)


def test_kill_switch(xwt, monkeypatch):
    x, w, t = xwt
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "0")
    assert not fused_ce_enabled()
    # even where the kernel COULD run (interpret), =0 takes the scan
    ns, nv = cross_entropy_sums(x, w, t, chunk_size=64, interpret=True)
    cs, cv = chunked_ce.chunked_cross_entropy(x, w, t, chunk_size=64)
    assert float(ns) == float(cs) and float(nv) == float(cv)
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")
    assert fused_ce_enabled()


def test_scoped_false_actually_disables_bool_flags(monkeypatch):
    """str(False) == "False" reads back TRUE under the raw != "0" env
    parse — a scoped(False) pin must round-trip through "0" or a
    fused-vs-chunked comparison on TPU silently compares the fused
    program against itself."""
    from dlrover_tpu.common import flags

    # set, then delete: monkeypatch restores what it saw first, and a
    # delenv of an absent name records nothing, so the "0" that
    # propagate() writes below would outlive this test
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")
    monkeypatch.delenv("DLROVER_TPU_FUSED_CE")
    with flags.FUSED_CE.scoped(False):
        assert os.environ["DLROVER_TPU_FUSED_CE"] == "0"
        assert flags.FUSED_CE.get() is False
        assert not fused_ce_enabled()
    with flags.FUSED_CE.scoped(True):
        assert flags.FUSED_CE.get() is True
    assert "DLROVER_TPU_FUSED_CE" not in os.environ
    # the propagate() and child_env() writers share the stringifier
    flags.FUSED_CE.propagate(False)
    assert flags.FUSED_CE.get() is False
    monkeypatch.delenv("DLROVER_TPU_FUSED_CE", raising=False)
    env = flags.child_env({"DLROVER_TPU_FUSED_CE": False})
    assert env["DLROVER_TPU_FUSED_CE"] == "0"


def test_dispatcher_uses_kernel_when_runnable(xwt, monkeypatch):
    """With the flag on and interpret granted, the dispatcher routes to
    the Pallas kernel — witnessed by its named_scope in the jaxpr-less
    check: values agree with the kernel called directly."""
    x, w, t = xwt
    monkeypatch.setenv("DLROVER_TPU_FUSED_CE", "1")
    ns, nv = cross_entropy_sums(x, w, t, interpret=True)
    fs, fv = fused_cross_entropy(x, w, t, interpret=True)
    assert float(ns) == float(fs) and float(nv) == float(fv)
