"""The elementwise passes around ``ops/kda.py``'s kernels
(``conv_silu_norm``, ``norm_gate``) in interpret mode against the XLA
forms they replace, outputs and every gradient (see ``test_kda.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import kda
from tests.kda_inputs import D, SCALES, _close, _io_inputs, _out_inputs


IO_SHAPES = [                          # batch rows, tokens, heads
    (2, 300, 4),    # two rows; two tiles, the second padded
    (1, 258, 8),    # two lane blocks of four heads; 2 rows past a tile
    (1, 100, 4),    # less than a tile: the first tile's halo alone
]
IO_DTYPES = [jnp.float32, jnp.bfloat16]


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
