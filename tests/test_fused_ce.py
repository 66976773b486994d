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


# ---------------------------------------------------------------------------
# dX out of the forward sweep: under differentiation the sweep carries
# softmax(logits) @ w^T by online rescaling, the backward subtracts the
# targets' columns of the head and scales; no dx kernel
# ---------------------------------------------------------------------------


def _dx_case(name):
    """(x, w, t, g): operands, targets and the cotangent of nll_sum.
    24 tokens, V=300 at 128-column tiles: three vocabulary tiles, the
    last one padded (84 real columns of 128)."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(B, T, D)).astype(np.float32)
    w = (rng.normal(size=(D, V)) * 0.1).astype(np.float32)
    t = rng.integers(0, V, size=(B, T)).astype(np.int32)
    g, dtype = 1.0, jnp.float32
    if name == "rising_max":
        # logits ascending along V: every tile raises the running
        # maximum, so what the accumulator holds is rescaled each time
        x = np.abs(x)
        w = np.abs(w) * np.linspace(0.1, 6.0, V, dtype=np.float32)
    elif name == "falling_max":
        # the first tile holds the maximum: the rescale factor is 1
        x = np.abs(x)
        w = np.abs(w) * np.linspace(6.0, 0.1, V, dtype=np.float32)
    elif name == "targets_first_and_last_tile":
        t[:, ::2], t[:, 1::2] = 0, V - 1
        t[0, :4] = [127, 128, 255, 256]   # the tiles' edges
    elif name == "masked_rows":
        t[:, -3:] = -1
        t[1] = -1
    elif name == "cotangent":
        g = -2.5
    elif name == "bf16":
        dtype = jnp.bfloat16
    else:
        assert name == "padded_vocab"   # V=300 is, in every case
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype), jnp.asarray(t), g


DX_CASES = ["rising_max", "falling_max", "targets_first_and_last_tile",
            "masked_rows", "padded_vocab", "cotangent", "bf16"]


@pytest.mark.parametrize("bt,bv", [(8, 128), (16, 256)])
@pytest.mark.parametrize("case", DX_CASES)
def test_dx_from_the_forward_sweep(case, bt, bv):
    x, w, t, g = _dx_case(case)
    x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)

    def grads(ce, x, w):
        return jax.grad(lambda x, w: g * ce(x, w)[0], argnums=(0, 1))(x, w)

    gf = grads(lambda x, w: fused_cross_entropy(
        x, w, t, block_t=bt, block_v=bv, interpret=True), x, w)
    gd = grads(lambda x, w: dense_ce_sums(x, w, t), x32, w32)
    gc = grads(lambda x, w: chunked_ce.chunked_cross_entropy(
        x, w, t, chunk_size=128), x, w)
    assert gf[0].dtype == x.dtype and gf[0].shape == x.shape
    # bf16: p goes to the MXU rounded and the result is rounded, where
    # the chunked path rounds q and the result
    tol = 1e-5 if x.dtype == jnp.float32 else 2e-2
    for got, ref in ((gf[0], gd[0]), (gf[1], gd[1]),
                     (gf[0], gc[0]), (gf[1], gc[1])):
        assert rel_err(got.astype(jnp.float32), ref) <= tol
    masked = np.asarray(t) < 0
    assert not np.asarray(gf[0].astype(jnp.float32))[masked].any()


@pytest.mark.parametrize("gain,p_low,p_high", [(3.0, 0.8, 0.97),
                                               (4.0, 0.98, 0.999)])
def test_dx_where_the_model_is_sure_of_its_target(gain, p_low, p_high):
    """softmax @ w^T all but equals the target's column there, so dX is
    what their difference leaves: the residual stays f32 until it is
    taken. Each token's dX within 0.3 % of the f64 gradient in bf16 (a
    residual rounded to bf16 first gives 1 % at p_tgt 0.9 and 60 % at
    0.997; the dx kernel this replaced gave 0.2 to 0.45 %)."""
    n, d, v = 64, 128, 512
    rng = np.random.default_rng(3)
    w = (rng.normal(size=(d, v)) * 0.1).astype(np.float32)
    t = rng.integers(0, v, size=(n,)).astype(np.int32)
    # x along its target's column of the head, and some noise
    cols = w[:, t].T
    x = cols * 3 * gain / (cols ** 2).sum(1, keepdims=True) \
        + rng.normal(size=(n, d)).astype(np.float32) * 0.3
    x, w = jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16)

    x64 = np.asarray(x.astype(jnp.float32), np.float64)
    w64 = np.asarray(w.astype(jnp.float32), np.float64)
    logits = x64 @ w64
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    p_tgt = p[np.arange(n), t].copy()
    assert p_low < np.median(p_tgt) < p_high
    p[np.arange(n), t] -= 1
    want = p @ w64.T

    got = jax.grad(lambda x: fused_cross_entropy(
        x, w, jnp.asarray(t), block_t=16, block_v=128, interpret=True)[0])(x)
    got = np.asarray(got.astype(jnp.float32), np.float64)
    per_token = (np.linalg.norm(got - want, axis=1)
                 / np.linalg.norm(want, axis=1))
    assert per_token.max() <= 3e-3


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_undifferentiated_loss_runs_the_lean_forward(xwt):
    """jax.custom_vjp tells the two cases apart, no option does: the
    primal lowers to one kernel with O(tokens) outputs, the
    differentiated loss to the sweep with a (tokens, d) output and dw."""
    from dlrover_tpu.observability import trace

    x, w, t = xwt
    x, t = x.reshape(B * T, D), t.reshape(B * T)

    def nll(x, w):
        return fused_cross_entropy(x, w, t, block_t=8, block_v=128,
                                   interpret=True)[0]

    def kernel_outputs(fn):
        jaxpr = jax.make_jaxpr(fn)(x, w)
        calls = [e for e in _walk(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        return [[v.aval.shape for v in e.outvars] for e in calls]

    fused_ce.reset_sweep_report()   # as a step build does
    assert trace.gauges()["fused_ce.logit_sweeps"] == 0
    assert kernel_outputs(nll) == [[(B * T, 8), (B * T, 8)]]
    assert trace.gauges()["fused_ce.logit_sweeps"] == 1
    assert kernel_outputs(jax.grad(nll, argnums=(0, 1))) == [
        [(B * T, 8), (B * T, 8), (B * T, D)],   # logz, gold, softmax @ w^T
        [(D, 384)],                             # dw, over the padded V
    ]
    assert trace.gauges()["fused_ce.logit_sweeps"] == 2
    # an evaluation traced after the step does not lower what it says
    kernel_outputs(nll)
    assert trace.gauges()["fused_ce.logit_sweeps"] == 2


def test_two_heads_through_one_kernel_read_four_sweeps(xwt):
    """A step with a second loss through the same head (a multi-token
    module) forms its logits four times: the gauge counts every
    differentiated loss of the build, not the costliest one."""
    from dlrover_tpu.observability import trace

    x, w, t = xwt
    x, t = x.reshape(B * T, D), t.reshape(B * T)

    def two_heads(x, w):
        nll = lambda x: fused_cross_entropy(
            x, w, t, block_t=8, block_v=128, interpret=True)[0]
        return nll(x) + 0.3 * nll(x * 0.5)

    fused_ce.reset_sweep_report()
    jax.make_jaxpr(two_heads)(x, w)
    assert trace.gauges()["fused_ce.logit_sweeps"] == 2   # two lean forwards
    fused_ce.reset_sweep_report()
    jax.make_jaxpr(jax.grad(two_heads, argnums=(0, 1)))(x, w)
    assert trace.gauges()["fused_ce.logit_sweeps"] == 4


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


@pytest.mark.parametrize(
    "kernel", [fused_ce.LOSS, fused_ce.LOSS_DX, fused_ce.DW])
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
def test_tile_geometry_fits_vmem_budget(d, x_dtype, w_dtype, kernel):
    n, v = 8192, 128256
    bt, bv, n_pad, v_pad = fused_ce._tile_geometry(
        n, v, d, x_dtype, w_dtype,
        fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, kernel,
    )
    xb, wb = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize
    held = fused_ce._vmem_bytes(bt, bv, d, xb, wb, kernel)
    assert held <= fused_ce._VMEM_BUDGET
    # the training forward holds the lean one's blocks and an f32
    # (bt, d) gradient block it accumulates in; dw a (d, bv) block with
    # its f32 accumulator
    lean = fused_ce._vmem_bytes(bt, bv, d, xb, wb, fused_ce.LOSS)
    if kernel == fused_ce.LOSS_DX:
        assert held == lean + bt * d * 2 * 4
    elif kernel == fused_ce.DW:
        assert held == lean + d * bv * (2 * wb + 4)
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
        fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, fused_ce.DW,
    )
    assert bv == want_bv and (v_pad != v) == padded


def test_tile_geometry_says_when_d_cannot_fit():
    with pytest.raises(ValueError, match="DLROVER_TPU_FUSED_CE=0"):
        fused_ce._tile_geometry(
            8192, 128256, 65536, jnp.bfloat16, jnp.bfloat16,
            fused_ce.DEFAULT_BLOCK_T, fused_ce.DEFAULT_BLOCK_V, fused_ce.DW,
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
