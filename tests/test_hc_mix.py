"""``ops/hc_mix.py`` at small sizes on the CPU: the four passes in
interpret mode against the ``jnp`` form they replace
(``xing4.hc_coefficients``, ``hc_pre_mix``, ``hc_post_mix``): ``y``, X'
and every gradient; which form ``xing4.hc_sublayer`` takes; the passes
under ``shard_map`` on four devices."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import xing4
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import hc_mix

N = 4
# (batch rows, tokens, channels a stream): 200 tokens are short of one
# row block of `hc_pre_fwd`'s 256, one and a half of the others' 128 and
# three and an eighth of `hc_pre_bwd`'s 64; 72 are one short block
SHAPES = [(2, 200, 256), (2, 72, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _cfg(d):
    return xing4.Xing4Config.tiny(dim=d, hc_mult=N)


def _inputs(b, s, d, dtype, seed=0):
    """The streams, a sublayer's parameters (``phi`` wide enough that
    the coefficients differ a token, ``alpha`` and the bias off their
    initial values), a sublayer ``fn`` with a weight and an added ``z``
    of its own, and the weight of the result in the loss."""
    cfg = _cfg(d)
    ks = jax.random.split(jax.random.key(seed), 7)
    X = jax.random.normal(ks[0], (N, b, s, d)).astype(dtype)
    lp = {"hc_phi": 0.05 * jax.random.normal(ks[1], (N, d, cfg.hc_width)),
          "hc_alpha": jnp.asarray([0.5, 0.8, 1.1]),
          "hc_bias": jax.random.normal(ks[2], (cfg.hc_width,))}
    w = (0.1 * jax.random.normal(ks[3], (d, d))).astype(dtype)
    z0 = jax.random.normal(ks[4], (b, s, d)).astype(dtype)
    weight = jax.random.normal(ks[5], (N, b, s, d))
    return X, lp, w, z0, weight


@functools.lru_cache(maxsize=None)
def _form(d, interpret, mesh=None):
    """(X' and ``y``, the gradients of ``sum(X' weight)`` to X, the
    sublayer's parameters, ``fn``'s weight and ``z``) of one form,
    jitted once a width."""
    cfg = _cfg(d)

    def out(X, lp, w, z0):
        seen = []

        def fn(y):
            # float32 inside, as the passes: what the comparison in
            # bfloat16 sees is the mixing's rounding, not a product's
            seen.append(y)
            f32 = jnp.float32
            return (jnp.tanh(y.astype(f32) @ w.astype(f32))
                    + z0.astype(f32)).astype(y.dtype)

        Xn = xing4.hc_sublayer(cfg, lp, "hc", X, fn, interpret=interpret,
                               mesh=mesh)
        return Xn, seen[0]

    def loss(X, lp, w, z0, weight):
        return jnp.sum(out(X, lp, w, z0)[0].astype(jnp.float32) * weight)

    return jax.jit(out), jax.jit(jax.grad(loss, argnums=range(4)))


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _same(got, want, dtype):
    """Float32: the two forms to rounding. bfloat16: the passes (float32
    inside, one rounding at a store) against the ``jnp`` form in float32
    on the same inputs, within bfloat16's step of the largest entry."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        _close(a.astype(jnp.float32), b,
               2e-6 if dtype == jnp.float32 else 1e-2)


def _same_grads(got, want, dtype):
    """``alpha``'s gradient is three sums over every token and
    coefficient of terms of both signs, which cancel to a hundredth of
    their size: the ``jnp`` form in bfloat16 stands as far from itself
    in float32 (1.1 % at 400 tokens). Ten times the room for it."""
    tol = 2e-5 if dtype == jnp.float32 else 1e-1
    (gx, glp, *rest), (wx, wlp, *wrest) = got, want
    glp, wlp = dict(glp), dict(wlp)
    _close(glp.pop("hc_alpha").astype(jnp.float32), wlp.pop("hc_alpha"), tol)
    _same((gx, glp, rest), (wx, wlp, wrest), dtype)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,s,d", SHAPES)
def test_passes_match_the_jnp_form(b, s, d, dtype):
    *args, weight = _inputs(b, s, d, dtype)
    out, grads = _form(d, True)
    want_out, want_grads = _form(d, False)
    assert out(*args)[0].dtype == dtype
    _same(out(*args), want_out(*_f32(args)), dtype)
    _same_grads(grads(*args, weight), want_grads(*_f32(args), weight), dtype)


def test_passes_at_the_initial_coefficients():
    """``alpha`` 0.01 and the bias of ``hc_bias_init``: ``H_res`` near
    the identity, where a column of it is 1e-3 of another."""
    X, lp, w, z0, weight = _inputs(2, 200, 256, jnp.float32, seed=3)
    lp = dict(lp, hc_alpha=jnp.full((3,), 0.01),
              hc_bias=xing4.hc_bias_init(N))
    out, grads = _form(256, True)
    want_out, want_grads = _form(256, False)
    _same(out(X, lp, w, z0), want_out(X, lp, w, z0), jnp.float32)
    _same_grads(grads(X, lp, w, z0, weight),
                want_grads(X, lp, w, z0, weight), jnp.float32)


def test_h_res_through_the_passes_is_doubly_stochastic():
    """What pass 2 mixes with, formed from pass 1's ``raw``: rows and
    columns sum to one as ``hc_coefficients``' do, and are its values."""
    d = 256
    cfg = _cfg(d)
    X, lp, *_ = _inputs(2, 80, d, jnp.float32, seed=5)
    # logits near a permutation close their columns slowly (test_xing4)
    lp["hc_phi"] = 0.2 * lp["hc_phi"]
    hp = hc_mix.Static(cfg.norm_eps, tuple(cfg.hc_clamp),
                       cfg.hc_sinkhorn_iters, cfg.hc_eps, True)
    phi, alpha, bias = lp["hc_phi"], lp["hc_alpha"], lp["hc_bias"]
    y, raw, H = hc_mix._pre_forward(X, phi, alpha, bias, hp)
    h_pre, h_post, h_res = xing4.hc_coefficients(cfg, phi, alpha, bias, X)
    k = cfg.hc_width
    got = jnp.moveaxis(H[..., 2 * N:k], -1, 0).reshape(N, N, 2, 80)
    np.testing.assert_allclose(jnp.sum(got, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(got, axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(got, h_res, atol=2e-6)
    np.testing.assert_allclose(
        jnp.moveaxis(H[..., N:2 * N], -1, 0), h_post, atol=2e-6)
    # the pre columns are the passes' own; the token's 1 / rms rides in
    # column k of `raw`, and nothing else past it
    assert float(jnp.max(jnp.abs(H[..., :N]))) == 0.0
    x32 = X.astype(jnp.float32)
    np.testing.assert_allclose(
        raw[..., k], jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=(0, 3)) + cfg.norm_eps), rtol=2e-6)
    assert float(jnp.max(jnp.abs(raw[..., k + 1:]))) == 0.0
    _close(y, xing4.hc_pre_mix(h_pre, X), 2e-6)


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("d,on_tpu", [(192, False), (192, True),
                                      (256, False)])
def test_the_jnp_form_where_the_passes_cannot_run(d, on_tpu, monkeypatch):
    """Off the TPU without ``interpret``, and at a width that is not
    whole lanes on it, ``hc_sublayer`` is the ``jnp`` form: the gauge
    reads 0 and the lowered program is that of the three functions in
    line."""
    if on_tpu:
        monkeypatch.setattr(hc_mix, "_on_tpu", lambda: True)
    cfg = _cfg(d)
    X, lp, w, z0, _ = _inputs(1, 32, d, jnp.float32)
    fn = lambda y: jnp.tanh(y @ w) + z0

    def plain(X, lp):
        h_pre, h_post, h_res = xing4.hc_coefficients(
            cfg, lp["hc_phi"], lp["hc_alpha"], lp["hc_bias"], X)
        return xing4.hc_post_mix(h_post, h_res, X, fn(xing4.hc_pre_mix(h_pre, X)))

    trace.gauge("layers.hc_fused", 1)
    got = _lowered(lambda X, lp: xing4.hc_sublayer(cfg, lp, "hc", X, fn),
                   X, lp)
    assert trace.gauges()["layers.hc_fused"] == 0
    assert got == _lowered(lambda X, lp: plain(X, lp), X, lp)
    assert "hc_pre_fwd" not in got


def test_the_passes_where_they_can_run(monkeypatch):
    monkeypatch.setattr(hc_mix, "_on_tpu", lambda: True)
    assert hc_mix.fused(False, 3584) and hc_mix.fused(False, 256)
    assert trace.gauges()["layers.hc_fused"] == 1
    assert trace.gauges()["hc.fused"] == 1   # the xing4 cell's log prints `hc.`
    assert not hc_mix.fused(False, 192)
    assert trace.gauges()["hc.fused"] == 0
    assert hc_mix.fused(True, 192)   # the numerics tests' word


def test_passes_over_a_mesh_run_on_each_devices_batch_rows():
    """Under ``shard_map`` on the batch rows the passes give what they
    give on one device; ``phi``'s, ``alpha``'s and the bias's gradients
    are summed over the devices."""
    from dlrover_tpu.parallel import MeshConfig, build_mesh
    from dlrover_tpu.parallel.mesh import BATCH_AXES
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2).resolve(4),
                      devices=jax.devices()[:4])
    X, lp, w, z0, weight = _inputs(4, 40, 256, jnp.float32, seed=9)
    streams = NamedSharding(mesh, P(None, BATCH_AXES))
    rows = NamedSharding(mesh, P(BATCH_AXES))
    sharded = (jax.device_put(X, streams), lp, w, jax.device_put(z0, rows))
    out, grads = _form(256, True, mesh)
    want_out, want_grads = _form(256, True)
    _same(out(*sharded), want_out(X, lp, w, z0), jnp.float32)
    _same_grads(grads(*sharded, jax.device_put(weight, streams)),
                want_grads(X, lp, w, z0, weight), jnp.float32)
