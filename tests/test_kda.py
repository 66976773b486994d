"""``ops/kda.py`` at tiny sizes on the CPU: the chunked gated delta rule
against the token-by-token recurrence (``benchmarks/families/
kimi_linear.py ref_delta_rule``), outputs and every gradient, at strong
and weak decay and small and large steps, in both forms: the XLA ops
and the Pallas kernels in interpret mode (their hand-written backward
against the recurrence's autodiff and the XLA form's); the convolution
against a shifted sum; the elementwise passes around the kernels
(``conv_silu_norm``, ``norm_gate``) in interpret mode against the XLA
forms they replace, outputs and every gradient."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families.kimi_linear import ref_delta_rule
from benchmarks.families.qwen3_next import ref_delta_rule as ref_gdn_rule
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import kda

H, DK, DV = 2, 16, 8
FORMS = ["xla", "kernels"]


@functools.lru_cache(maxsize=None)
def _form(form, chunk):
    """(outputs, the five gradients of ``sum(o * weight)``) of one form,
    jitted once a chunk size: interpret mode compiles for seconds and
    runs in milliseconds."""
    def out(*a):
        return kda.chunk_kda(*a, chunk=chunk, interpret=form == "kernels")

    def loss(weight, *a):
        return jnp.sum(out(*a).astype(jnp.float32) * weight)

    return jax.jit(out), jax.jit(jax.grad(loss, argnums=range(1, 6)))


_recurrent_grads = jax.jit(jax.grad(
    lambda weight, *a: jnp.sum(ref_delta_rule(*a) * weight),
    argnums=range(1, 6)))


def _inputs(seq, decay, step, seed=0):
    """Normalised q and k as the layer makes them; ``decay``: "strong"
    is g = -5 a token and channel (G = -320 over a chunk of 64),
    "weak" within 0.01 of zero, "init" the layer's own range at init;
    ``step``: beta near 0, near 1, or across (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (2, seq, H, DK))
    k = jax.random.normal(ks[1], (2, seq, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, seq, H, DV))
    u = jax.random.uniform(ks[3], (2, seq, H, DK), minval=0.5, maxval=1.0)
    g = {"strong": jnp.full_like(u, -5.0), "weak": -0.01 * u,
         "init": -1.6 * u}[decay]
    shift = {"small": -6.0, "large": 6.0, "mid": 0.0}[step]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, H)) + shift)
    return q, k, v, g, beta


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("step", ["small", "large", "mid"])
@pytest.mark.parametrize("decay", ["strong", "weak", "init"])
@pytest.mark.parametrize("seq,chunk", [
    (64, 64),      # one chunk
    (192, 64),     # many
    (96, 32),
    (64, 16),      # a chunk of one sub-block
])
def test_chunked_form_matches_the_recurrence(seq, chunk, decay, step, form):
    args = _inputs(seq, decay, step)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form(form, chunk)
    out = forward(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, ref_delta_rule(*args), 5e-6)
    got = grads(weight, *args)
    for a, b in zip(got, _recurrent_grads(weight, *args)):
        # d/dg at g = -5 sums terms 1e5 apart in size: float32's order
        _close(a, b, 2e-4)
    if form == "kernels":
        # the hand-written backward against the XLA form's autodiff
        forward, grads = _form("xla", chunk)
        _close(out, forward(*args), 5e-6)
        for a, b in zip(got, grads(weight, *args)):
            _close(a, b, 2e-4)


@pytest.mark.parametrize("segment", [1, 2, 3])
def test_segments_carry_the_state(segment):
    """A sequence of several segments (padded to whole ones where 3 does
    not divide its 4 chunks) is the sequence of one."""
    args = _inputs(128, "init", "mid", seed=3)
    whole = kda.chunk_kda(*args, chunk=32, segment=16)
    _close(kda.chunk_kda(*args, chunk=32, segment=segment), whole, 5e-6)
    _close(whole, ref_delta_rule(*args), 5e-6)


def test_the_kernels_tiles_carry_the_state():
    """Five tiles (the last one padded): the state passes from a grid
    step to the next through scratch, its cotangent back from the last
    tile to the first, and the backward reads the state every chunk
    started from."""
    seq = 4 * kda.TILE + 70
    args = _inputs(seq, "init", "mid", seed=3)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form("kernels", 64)
    _close(forward(*args), ref_delta_rule(*args), 5e-6)
    for a, b in zip(grads(weight, *args), _recurrent_grads(weight, *args)):
        _close(a, b, 2e-4)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("seq", [40, 70])
def test_a_sequence_that_is_no_multiple_of_the_chunk(seq, form):
    args = _inputs(seq, "init", "mid", seed=5)
    weight = jax.random.normal(jax.random.key(9), (2, seq, H, DV))
    forward, grads = _form(form, 32)
    out = forward(*args)
    assert out.shape == (2, seq, H, DV)
    _close(out, ref_delta_rule(*args), 5e-6)
    for a, b in zip(grads(weight, *args), _recurrent_grads(weight, *args)):
        assert a.shape == b.shape
        _close(a, b, 2e-4)


@pytest.mark.parametrize("form", FORMS)
def test_bfloat16_operands_float32_state(form):
    args = _inputs(128, "init", "mid", seed=7)
    weight = jax.random.normal(jax.random.key(9), (2, 128, H, DV))
    q, k, v = (a.astype(jnp.bfloat16) for a in args[:3])
    forward, grads = _form(form, 64)
    out = forward(q, k, v, *args[3:])
    assert out.dtype == jnp.bfloat16
    exact = tuple(a.astype(jnp.float32) for a in (q, k, v)) + args[3:]
    _close(out.astype(jnp.float32), ref_delta_rule(*exact), 2e-2)
    got = grads(weight, q, k, v, *args[3:])
    for a, b, like in zip(got, _recurrent_grads(weight, *exact),
                          (q, k, v) + args[3:]):
        assert a.dtype == like.dtype
        _close(a.astype(jnp.float32), b, 2e-2)


@pytest.mark.parametrize("form", FORMS)
def test_the_decay_bound(form):
    """At the stated bound, |g| = 9 a token, every factor is a normal
    float32 and the form still agrees to rounding; the sub-block
    references keep G = -576 over a chunk out of any single exponent."""
    q, k, v, g, beta = _inputs(64, "strong", "mid")
    g = jnp.full_like(g, -9.0)
    weight = jax.random.normal(jax.random.key(9), (2, 64, H, DV))
    forward, grads = _form(form, 64)
    out = forward(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, ref_delta_rule(q, k, v, g, beta), 5e-6)
    got = grads(weight, q, k, v, g, beta)
    want = _recurrent_grads(weight, q, k, v, g, beta)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        # d/dg is 4.5e-5 here, the sum of terms up to 1e4 times larger
        _close(a, b, 5e-3 if name == "g" else 2e-4)


def test_the_gauges_say_which_form_ran():
    """``kda.kernel`` is 1 where the traced call took the kernels, 0
    where the XLA form; the kernels say their grid's block: both heads
    of these inputs a grid step, each an independent chain."""
    args = _inputs(256, "init", "mid")
    kda.chunk_kda(*args, chunk=64, interpret=True)
    gauges = trace.gauges()
    assert gauges["kda.kernel"] == 1
    assert gauges["kda.heads_per_step"] == H
    assert gauges["kda.chunks_per_step"] == 2     # a tile of 128 rows
    kda.chunk_kda(*args, chunk=64)                # off the TPU: XLA ops
    assert trace.gauges()["kda.kernel"] == 0
    kda.chunk_kda(*args, chunk=128, interpret=True)   # no kernel admits it
    assert trace.gauges()["kda.kernel"] == 0
    # the passes around the kernels say theirs, each where it chooses
    xs, taps, _ = _io_inputs(1, 40, 2, jnp.float32)
    o, gate, weight = _out_inputs(1, 40, 2, jnp.float32)
    for fused in (True, False):
        kda.conv_silu_norm(xs, taps, heads=2, scales=SCALES, interpret=fused)
        assert trace.gauges()["kda.io_fused"] == int(fused)
    for fused in (True, False):
        kda.norm_gate(o, gate, weight, 1e-5, interpret=fused)
        assert trace.gauges()["kda.io_fused"] == int(fused)


def test_chunk_must_be_whole_sub_blocks():
    with pytest.raises(ValueError, match="multiple of 16"):
        kda.chunk_kda(*_inputs(48, "init", "mid"), chunk=24)


@pytest.mark.parametrize("taps", [1, 4])
def test_convolution_is_a_causal_shifted_sum(taps):
    x = jax.random.normal(jax.random.key(0), (2, 11, 6))
    w = jax.random.normal(jax.random.key(1), (6, taps))
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[1]):
        for back in range(min(taps, t + 1)):
            want[:, t] += np.asarray(x[:, t - back] * w[:, taps - 1 - back])
    np.testing.assert_allclose(kda.causal_conv(x, w), want, atol=1e-5)
    # nothing of a later token reaches an earlier one
    bumped = kda.causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(bumped[:, :7], kda.causal_conv(x, w)[:, :7])


# ---------------------------------------------------------------------------
# The elementwise passes around the kernels
# ---------------------------------------------------------------------------

D = 128                                # a head's channels: whole lanes
SCALES = (D ** -0.5, 1.0, None)        # q, k, v as the layer asks
IO_SHAPES = [                          # batch rows, tokens, heads
    (2, 300, 4),    # two rows; two tiles, the second padded
    (1, 258, 8),    # two lane blocks of four heads; 2 rows past a tile
    (1, 100, 4),    # less than a tile: the first tile's halo alone
]
IO_DTYPES = [jnp.float32, jnp.bfloat16]


def _io_inputs(b, s, h, dtype, d=D, seed=0):
    ks = jax.random.split(jax.random.key(seed), 9)
    xs = tuple(jax.random.normal(k, (b, s, h * d)).astype(dtype)
               for k in ks[:3])
    taps = tuple(jax.random.uniform(k, (h * d, 4), minval=-0.5, maxval=0.5)
                 for k in ks[3:6])
    weights = tuple(jax.random.normal(k, (b, s, h, d)) for k in ks[6:])
    return xs, taps, weights


def _out_inputs(b, s, h, dtype, d=D, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    o = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    gate = (2.0 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
    return o, gate, 1.0 + 0.3 * jax.random.normal(ks[2], (d,))


def _exact(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _same(got, want, dtype):
    """Float32: the two forms to rounding. bfloat16: the pass (float32
    inside, one rounding at the store) against the XLA form in float32
    on the same inputs, within bfloat16's step of the largest entry."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a.astype(jnp.float32), b, 2e-6 if dtype == jnp.float32
               else 1e-2)


@pytest.mark.parametrize("dtype", IO_DTYPES)
@pytest.mark.parametrize("b,s,h", IO_SHAPES)
def test_input_pass_matches_the_xla_form(b, s, h, dtype):
    xs, taps, weights = _io_inputs(b, s, h, dtype)

    def form(interpret):
        def loss(xs, taps):
            out = kda.conv_silu_norm(xs, taps, heads=h, scales=SCALES,
                                     interpret=interpret)
            return sum(jnp.sum(o.astype(jnp.float32) * w)
                       for o, w in zip(out, weights)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    (_, out), (dxs, dtaps) = form(True)(xs, taps)
    (_, want), (want_dxs, want_dtaps) = form(False)(_exact(xs), taps)
    for o, dx, x in zip(out, dxs, xs):
        assert o.shape == (b, s, h, D) and o.dtype == dtype
        assert dx.shape == x.shape and dx.dtype == dtype
    assert all(dw.shape == (h * D, 4) for dw in dtaps)
    _same(out, want, dtype)
    _same(dxs, want_dxs, dtype)
    _same(dtaps, want_dtaps, dtype)
    # q and k leave normed a head, q scaled
    q, k = (jnp.linalg.norm(o.astype(jnp.float32), axis=-1) for o in out[:2])
    np.testing.assert_allclose(q, D ** -0.5, rtol=1e-2)
    np.testing.assert_allclose(k, 1.0, rtol=1e-2)


@pytest.mark.parametrize("dtype", IO_DTYPES)
@pytest.mark.parametrize("b,s,h", IO_SHAPES)
def test_output_pass_matches_the_xla_form(b, s, h, dtype):
    o, gate, weight = _out_inputs(b, s, h, dtype)
    w_out = jax.random.normal(jax.random.key(2), (b, s, h * D))

    def form(interpret):
        def loss(o, gate, weight):
            out = kda.norm_gate(o, gate, weight, 1e-5, interpret=interpret)
            return jnp.sum(out.astype(jnp.float32) * w_out), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))

    (_, out), grads = form(True)(o, gate, weight)
    (_, want), want_grads = form(False)(*_exact((o, gate)), weight)
    assert out.shape == (b, s, h * D) and out.dtype == dtype
    assert [g.dtype for g in grads] == [dtype, dtype, weight.dtype]
    _same(out, want, dtype)
    _same(grads, want_grads, dtype)


@pytest.mark.parametrize("at", [0, 2, 254, 255, 256, 299])
def test_input_pass_is_causal_across_the_tiles(at):
    """A bump at token ``at`` moves nothing before it and, forward,
    nothing past the convolution's reach; its own gradient reads the
    cotangents of ``at .. at + 3`` and no other token's: the first
    tile's masked halo, the rows a tile takes of the one before it, and
    the rows the backward takes of the one after."""
    xs, taps, weights = _io_inputs(1, 300, 4, jnp.float32, seed=4)

    def out(xs):
        return kda.conv_silu_norm(xs, taps, heads=4, scales=SCALES,
                                  interpret=True)

    bumped = out(tuple(x.at[:, at].add(1.0) for x in xs))
    for a, b in zip(bumped, out(xs)):
        np.testing.assert_array_equal(a[:, :at], b[:, :at])
        np.testing.assert_array_equal(a[:, at + 4:], b[:, at + 4:])
        assert float(jnp.max(jnp.abs(a[:, at] - b[:, at]))) > 0.0

    def dx(weights):
        return jax.grad(lambda xs: sum(
            jnp.sum(o * w) for o, w in zip(out(xs), weights)))(xs)

    moved = dx(tuple(
        w.at[:, :at].add(1.0).at[:, at + 4:].add(1.0) for w in weights))
    for a, b in zip(moved, dx(weights)):
        np.testing.assert_array_equal(a[:, at], b[:, at])


def test_passes_over_a_mesh_run_on_each_devices_batch_rows():
    """Under ``shard_map`` on the batch rows the passes give what they
    give on one device; the taps' and the norm weight's gradients are
    summed over the devices."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dlrover_tpu.parallel.mesh import BATCH_AXES

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2).resolve(4),
                      devices=jax.devices()[:4])
    rows = NamedSharding(mesh, P(BATCH_AXES))
    xs, taps, weights = _io_inputs(4, 260, 2, jnp.float32, d=16)
    o, gate, weight = _out_inputs(4, 260, 2, jnp.float32, d=16)

    def loss(mesh):
        def fn(xs, taps, o, gate, weight):
            q, k, v = kda.conv_silu_norm(
                xs, taps, heads=2, scales=(0.25, 1.0, None), interpret=True,
                mesh=mesh)
            out = kda.norm_gate(o * v, gate, weight, 1e-5, interpret=True,
                                mesh=mesh)
            return jnp.sum(out.reshape(q.shape) * q * k * weights[0])
        return jax.jit(jax.grad(fn, argnums=range(5)))

    sharded = jax.device_put((xs, o, gate), rows)
    got = loss(mesh)(sharded[0], taps, sharded[1], sharded[2], weight)
    _same(got, loss(None)(xs, taps, o, gate, weight), jnp.float32)


# ---------------------------------------------------------------------------
# The per-head form (one decay a head over grouped value heads): both
# forms against the token-by-token recurrence of
# ``benchmarks/families/qwen3_next.py``, at decays the channel form's
# bound does not admit.
# ---------------------------------------------------------------------------

GDN_DECAYS = [-0.1, -5.0, -21.0]


@functools.lru_cache(maxsize=None)
def _gdn_form(form, chunk):
    def out(*a):
        return kda.chunk_gdn(*a, chunk=chunk, interpret=form == "kernels")

    def loss(weight, *a):
        return jnp.sum(out(*a).astype(jnp.float32) * weight)

    return jax.jit(out), jax.jit(jax.grad(loss, argnums=range(1, 6)))


_gdn_recurrent_grads = jax.jit(jax.grad(
    lambda weight, *a: jnp.sum(ref_gdn_rule(*a) * weight),
    argnums=range(1, 6)))


def _gdn_inputs(seq, decay, hk=2, r=2, seed=0, dtype=jnp.float32):
    """``g`` between ``0.2 decay`` and ``decay`` a token, a number a
    value head; ``r`` value heads a key head."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (2, seq, hk, DK))
    k = jax.random.normal(ks[1], (2, seq, hk, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, seq, hk * r, DV))
    g = decay * jax.random.uniform(ks[3], (2, seq, hk * r), minval=0.2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, seq, hk * r)))
    weight = jax.random.normal(ks[5], v.shape)
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta), weight


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("decay", GDN_DECAYS)
# 300 rows of 16: 19 chunks, so two of the XLA form's segments, the
# second padded
@pytest.mark.parametrize("seq,chunk", [(300, 16), (128, 32), (192, 64)])
def test_per_head_form_matches_the_recurrence(seq, chunk, decay, form):
    """Outputs and the five gradients, two value heads a key head; at
    ``g = -21`` a token (the public initialisation's reach) as at
    -0.1: the mask ``e^(G_i - G_j)`` is at most 1 whatever ``g`` is."""
    args, weight = _gdn_inputs(seq, decay)
    forward, grads = _gdn_form(form, chunk)
    with jax.default_matmul_precision("highest"):
        out = forward(*args)
        want = ref_gdn_rule(*args)
        got = grads(weight, *args)
        want_grads = _gdn_recurrent_grads(weight, *args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, want, 5e-6)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want_grads):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a)))
        # dg at -21 is the small difference of terms a thousand times it
        _close(a, b, 2e-4 if name == "g" else 2e-5)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("hk,r", [(1, 1), (1, 4), (4, 1), (2, 8)])
def test_per_head_form_at_other_groupings(hk, r, form):
    """One value head a key head (no sharing), one key head under all,
    and more value heads a key head than a grid step's chains."""
    args, weight = _gdn_inputs(96, -3.0, hk=hk, r=r, seed=3)
    forward, grads = _gdn_form(form, 32)
    with jax.default_matmul_precision("highest"):
        _close(forward(*args), ref_gdn_rule(*args), 5e-6)
        for a, b in zip(grads(weight, *args),
                        _gdn_recurrent_grads(weight, *args)):
            _close(a, b, 5e-5)


def test_per_head_kernels_with_bfloat16_operands():
    """bf16 q, k, v: the kernels against the XLA form on the same
    operands (both round the state's products to bf16; the solve, the
    decays and the state are float32 in both)."""
    args, weight = _gdn_inputs(256, -2.0, dtype=jnp.bfloat16)
    (fx, gx), (fk, gk) = _gdn_form("xla", 64), _gdn_form("kernels", 64)
    out = fk(*args)
    assert out.dtype == jnp.bfloat16
    _close(out.astype(jnp.float32), fx(*args).astype(jnp.float32), 2e-2)
    for a, b in zip(gk(weight, *args), gx(weight, *args)):
        assert a.dtype == b.dtype
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 3e-2)


def test_per_head_form_says_which_form_ran_and_refuses_ragged_groups():
    args, _ = _gdn_inputs(64, -1.0)
    kda.chunk_gdn(*args, chunk=16, interpret=True)
    assert trace.gauges()["attn.gdn_kernel"] == 1
    kda.chunk_gdn(*args, chunk=16)                # off the TPU: XLA ops
    assert trace.gauges()["attn.gdn_kernel"] == 0
    kda.chunk_gdn(*args, chunk=8, interpret=True)     # no kernel admits it
    assert trace.gauges()["attn.gdn_kernel"] == 0
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="value heads over"):
        kda.chunk_gdn(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


@pytest.mark.parametrize("act", ["sigmoid", "silu"])
def test_output_pass_takes_either_gate(act):
    o, gate, weight = _out_inputs(2, 70, 2, jnp.float32)

    def loss(fused, *a):
        return jnp.sum(kda.norm_gate(*a, 1e-6, act=act, interpret=fused) ** 2)

    want = jax.nn.silu(gate) if act == "silu" else jax.nn.sigmoid(gate)
    plain = (kda.rms_norm(o, weight, 1e-6) * want).reshape(2, 70, -1)
    for fused in (True, False):
        _close(kda.norm_gate(o, gate, weight, 1e-6, act=act, interpret=fused),
               plain, 1e-5)
    for a, b in zip(jax.grad(loss, argnums=(1, 2, 3))(True, o, gate, weight),
                    jax.grad(loss, argnums=(1, 2, 3))(False, o, gate, weight)):
        _close(a, b, 1e-5)
    with pytest.raises(ValueError, match="one of"):
        kda.norm_gate(o, gate, weight, 1e-6, act="tanh")


def test_input_pass_leaves_a_projection_unnormed():
    """``scales=(None,)``: convolved and SiLU'd, no norm (a Gated
    DeltaNet's v), in both forms, outputs and gradients."""
    xs, taps, _ = _io_inputs(2, 70, 2, jnp.float32)

    def loss(fused, x, w):
        out, = kda.conv_silu_norm([x], [w], heads=2, scales=(None,),
                                  scope="gdn_conv", interpret=fused)
        return jnp.sum(out ** 2)

    out, = kda.conv_silu_norm(xs[:1], taps[:1], heads=2, scales=(None,),
                              interpret=True)
    plain = jax.nn.silu(kda.causal_conv(xs[0], taps[0]))
    _close(out.reshape(plain.shape), plain, 1e-5)
    for a, b in zip(jax.grad(loss, argnums=(1, 2))(True, xs[0], taps[0]),
                    jax.grad(loss, argnums=(1, 2))(False, xs[0], taps[0])):
        _close(a, b, 1e-5)
