"""MoE model + expert parallelism tests on the 8-device CPU mesh.

The program's sorted dispatch into a grouped matmul is held to a plain
oracle: a Python loop over the experts, each applied to every token and
weighted by that token's router probability for it or 0. The oracle is
this file's own code, float32, and shares nothing with ``models/moe.py``
but the parameter tree.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import grouped_matmul, moe_rows
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import shard_pytree

# what the two families' config.json files state
CONVENTIONS = {
    "mixtral": dict(norm_topk_prob=True, qk_norm=False),
    "olmoe": dict(norm_topk_prob=False, qk_norm=True),
}

# float32 on the CPU, the same mathematics in another order of
# summation (sorted rows and one grouped product against 4 dense
# products and a masked sum): the loss agrees to a few ulps of a value
# near 5.5, gradients to 1e-4 of their largest entry. A dropped pair, a
# renormalisation where none belongs or a skipped q_norm moves the loss
# by 1e-3 or more (test_conventions_differ: 1.7e-3 and 0.35).
LOSS_TOL = 1e-5
GRAD_TOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    return moe.MoeConfig.tiny()


def _cfg(convention: str, **kw):
    return moe.MoeConfig.tiny(**CONVENTIONS[convention], **kw)


def _params(cfg, seed=0):
    """Seeded weights; norm weights away from one, so that a norm left
    out or applied to the wrong tensor shows."""
    params = moe.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        if name in params["layers"]:
            w = params["layers"][name]
            params["layers"][name] = w + 0.3 * jax.random.normal(
                next(keys), w.shape, w.dtype)
    # a router that spreads its probabilities: at sigma 0.02 every
    # expert gets about 1 / e and the conventions barely differ
    params["layers"]["router"] = params["layers"]["router"] * 40.0
    # and blocks whose output weighs against the residual stream, so
    # that the loss feels what the experts and the attention compute
    for name, scale in (("w_down", 300.0), ("w_up", 4.0), ("wo", 30.0),
                        ("wq", 5.0)):
        params["layers"][name] = params["layers"][name] * scale
    params["lm_head"] = params["lm_head"] * 10.0
    return params


# ---------------------------------------------------------------------------
# the oracle
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def oracle_moe_mlp(cfg, lp, y):
    """(t, d) -> (out (t, d), aux): every expert on every token."""
    e, k = cfg.n_experts, cfg.experts_per_token
    probs = jax.nn.softmax(y @ lp["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(y)
    counts = []
    for i in range(e):
        chose = top_e == i                                   # (t, k)
        weight = jnp.sum(jnp.where(chose, top_p, 0.0), -1)   # (t,)
        hidden = jax.nn.silu(y @ lp["w_gate"][i]) * (y @ lp["w_up"][i])
        out = out + weight[:, None] * (hidden @ lp["w_down"][i])
        counts.append(jnp.sum(chose))
    fraction = jnp.stack(counts) / (y.shape[0] * k)
    return out, e * jnp.sum(fraction * probs.mean(0))


def oracle_loss(params, tokens, cfg):
    b, s = tokens.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens]
    aux_sum = 0.0
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        y = _rms(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = y @ lp["wq"], y @ lp["wk"], y @ lp["wv"]
        if cfg.qk_norm:
            q = _rms(q, lp["q_norm"], cfg.norm_eps)
            k = _rms(k, lp["k_norm"], cfg.norm_eps)
        q = _rope(q.reshape(b, s, h, hd), cfg.rope_theta)
        k = _rope(k.reshape(b, s, kvh, hd), cfg.rope_theta)
        v = v.reshape(b, s, kvh, hd)
        k, v = (jnp.repeat(a, h // kvh, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(b, s, h * hd) @ lp["wo"]
        y = _rms(x, lp["mlp_norm"], cfg.norm_eps)
        out, aux = oracle_moe_mlp(cfg, lp, y.reshape(b * s, -1))
        x = x + out.reshape(b, s, -1)
        aux_sum = aux_sum + aux
    logits = _rms(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(gold) + cfg.router_aux_coef * aux_sum / cfg.n_layers


def _assert_grads_close(got, want):
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want = jax.tree.leaves(want)
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err <= GRAD_TOL, (jax.tree_util.keystr(path), err)


# ---------------------------------------------------------------------------
# program against oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("convention", sorted(CONVENTIONS))
def test_loss_and_grads_match_oracle(convention):
    cfg = _cfg(convention, n_experts=8, experts_per_token=3)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_fn(p, tokens, cfg)))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: oracle_loss(p, tokens, cfg)))(params)
    assert abs(float(loss) - float(want)) <= LOSS_TOL
    _assert_grads_close(grads, want_grads)


def test_conventions_differ():
    """What the tolerance above has to see: the other family's router
    convention, or a q_norm left out, moves the loss by far more."""
    cfg = _cfg("olmoe", n_experts=8, experts_per_token=3)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
    base = float(moe.loss_fn(params, tokens, cfg))
    import dataclasses

    renorm = dataclasses.replace(cfg, norm_topk_prob=True)
    assert abs(float(moe.loss_fn(params, tokens, renorm)) - base) > 1e-3
    no_qk = dataclasses.replace(cfg, qk_norm=False)
    assert abs(float(moe.loss_fn(params, tokens, no_qk)) - base) > 1e-3


def _skewed_layer(cfg, seed=0):
    """One layer's weights with a router whose first column is all ones:
    on positive inputs every token's first choice is expert 0."""
    lp = jax.tree.map(lambda a: a[0], _params(cfg, seed)["layers"])
    lp["router"] = lp["router"].at[:, 0].set(1.0)
    y = 1.0 + jnp.abs(
        jax.random.normal(jax.random.key(seed + 2), (48, cfg.dim)))
    return lp, y


@pytest.mark.parametrize("convention", sorted(CONVENTIONS))
def test_dropless_under_skew(convention):
    """One expert takes every token: nothing is dropped, the oracle
    still agrees, forward and backward."""
    cfg = _cfg(convention, n_experts=8, experts_per_token=2)
    lp, y = _skewed_layer(cfg)
    _, _, top_e = moe.route(cfg, lp["router"], y)
    _, _, group_sizes = moe.sort_pairs(top_e, cfg.n_experts)
    assert int(group_sizes[0]) == y.shape[0]      # 8 times the mean load
    assert int(group_sizes.sum()) == y.shape[0] * cfg.experts_per_token

    def program(lp, y):
        out, aux = moe.moe_mlp(cfg, lp, y[None])
        return jnp.sum(out * out) + aux, out[0]

    def oracle(lp, y):
        out, aux = oracle_moe_mlp(cfg, lp, y)
        return jnp.sum(out * out) + aux, out

    with jax.default_matmul_precision("highest"):
        (val, out), grads = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(lp, y)
        (want, want_out), want_grads = jax.value_and_grad(
            oracle, argnums=(0, 1), has_aux=True)(lp, y)
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    assert abs(float(val) - float(want)) <= LOSS_TOL * max(1.0, float(want))
    used = {k: grads[0][k] for k in ("router", "w_gate", "w_up", "w_down")}
    _assert_grads_close((used, grads[1]),
                        ({k: want_grads[0][k] for k in used}, want_grads[1]))


@pytest.mark.parametrize("held,bounded", [(2, 1), (8, 0), (None, 0)])
def test_dispatch_is_bounded_where_the_dead_rows_pay_for_it(
        monkeypatch, held, bounded):
    """The gradient program of a held share of 32 experts, traced as it
    is on the TPU (nothing is lowered). A sixteenth held: the gauge says
    dispatch's forward stops at the count, no gather makes a ``(t k, d)``
    array, and the kernel is there twice, the forward's call and the one
    the backward makes again. A quarter held: the row kernels run and
    the forward's gather is XLA's, over every row, once. Every expert
    held: the program names no count and keeps XLA's gathers."""
    monkeypatch.setattr(moe_rows, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    d = 128
    cfg = moe.MoeConfig.tiny(dim=d, ffn_dim=d, n_experts=32,
                             experts_held=held)
    layers = moe.init_params(cfg, jax.random.key(0))["layers"]
    lp = {name: layers[name][0]
          for name in ("router", "w_gate", "w_up", "w_down")}
    y = jnp.zeros((2, 64, d), cfg.dtype)
    pairs = 2 * 64 * cfg.experts_per_token

    def loss(lp, y):
        out, aux = moe.moe_mlp(cfg, lp, y)
        return jnp.sum(out) + aux

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(lp, y))
    gauges = trace.gauges()
    assert gauges["moe.dispatch_bounded"] == bounded
    assert gauges["moe.rows_kernel"] == int(held is not None)
    whole = len(re.findall(rf":f32\[{pairs},{d}\] = gather\[", text))
    kernels = text.count("name=moe_rows_gathered")
    assert (whole, kernels) == {2: (0, 2), 8: (1, 0), None: (4, 0)}[held]


@pytest.mark.parametrize("t,k,e", [(16, 2, 4), (40, 8, 64), (7, 3, 5)])
def test_group_sizes_sum_to_pairs(t, k, e):
    top_e = jax.random.randint(jax.random.key(t), (t, k), 0, e)
    order, inverse, group_sizes = moe.sort_pairs(top_e, e)
    assert int(group_sizes.sum()) == t * k
    flat = np.asarray(top_e).reshape(-1)
    np.testing.assert_array_equal(group_sizes, np.bincount(flat, minlength=e))
    assert (np.diff(flat[np.asarray(order)]) >= 0).all()   # by expert
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inverse)],
                                  np.arange(t * k))
    # one ep rank's view: experts 1 and 2 of e; the rest fall in the tail
    order, _, local = moe.sort_pairs(top_e, 2, first=1)
    np.testing.assert_array_equal(local, np.bincount(flat, minlength=e)[1:3])
    head = flat[np.asarray(order)][: int(local.sum())]
    assert set(head) <= {1, 2} and (np.diff(head) >= 0).all()


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("convention", sorted(CONVENTIONS))
def test_every_token_gets_k_experts_at_any_load(convention, skewed):
    """Experts that all hold the same weights: whatever the routing and
    the load, a token's output is its k weights' sum times that one
    expert's output (the sum is 1 where the family renormalises)."""
    cfg = _cfg(convention, n_experts=8, experts_per_token=3)
    lp, y = _skewed_layer(cfg)
    if not skewed:
        lp["router"] = _params(cfg)["layers"]["router"][0]
    for name in ("w_gate", "w_up", "w_down"):
        lp[name] = jnp.broadcast_to(lp[name][:1], lp[name].shape)
    out, _ = moe.moe_mlp(cfg, lp, y[None])
    _, top_p, _ = moe.route(cfg, lp["router"], y)
    one = (jax.nn.silu(y @ lp["w_gate"][0]) * (y @ lp["w_up"][0])
           ) @ lp["w_down"][0]
    total = top_p.sum(-1)
    if cfg.norm_topk_prob:
        np.testing.assert_allclose(total, 1.0, atol=1e-6)
    np.testing.assert_allclose(out[0], total[:, None] * one,
                               atol=1e-5, rtol=1e-5)


def test_forward_shapes_and_finite(cfg):
    params = moe.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = moe.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # balanced-at-init routing: aux loss near its uniform minimum of 1.0
    assert 0.9 < float(aux) < 1.5


def test_presets_state_their_conventions():
    mixtral, olmoe = moe.MoeConfig.mixtral_8x7b(), moe.MoeConfig.olmoe_1b_7b()
    assert (mixtral.norm_topk_prob, mixtral.qk_norm) == (True, False)
    assert (olmoe.norm_topk_prob, olmoe.qk_norm) == (False, True)
    assert (olmoe.n_experts, olmoe.experts_per_token, olmoe.ffn_dim,
            olmoe.dim, olmoe.n_heads, olmoe.vocab_size) == (
                64, 8, 1024, 2048, 16, 50304)
    assert not hasattr(mixtral, "capacity_factor")
    layers = moe.abstract_params(_cfg("olmoe"))["layers"]
    assert layers["q_norm"].shape == (2, 64)
    assert "q_norm" not in moe.abstract_params(_cfg("mixtral"))["layers"]


def test_param_count_and_active_params(cfg):
    total = moe.param_count(cfg)
    active = moe.active_param_count(cfg)
    assert active < total
    dense_like = 3 * cfg.dim * cfg.ffn_dim * cfg.n_layers
    assert total - active == dense_like * (cfg.n_experts - cfg.experts_per_token)


# ---------------------------------------------------------------------------
# under a mesh
# ---------------------------------------------------------------------------

MESHES = {
    "dp2-ep2-tp2": dict(dp=2, fsdp=1, ep=2, sp=1, tp=2),
    "fsdp2-ep4": dict(dp=1, fsdp=2, ep=4, sp=1, tp=1),
}


@pytest.mark.parametrize("axes", sorted(MESHES))
@pytest.mark.parametrize("convention", sorted(CONVENTIONS))
def test_mesh_matches_one_device(convention, axes):
    """The shard_map path (rows gathered over ep, local experts, tail,
    psum_scatter; tp over the expert width; fsdp gathered) computes what
    one device computes: loss and gradients."""
    cfg = _cfg(convention, n_experts=8, experts_per_token=3)
    mc = MeshConfig(**MESHES[axes]).resolve(8)
    mesh = build_mesh(mc)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_fn(p, tokens, cfg)))(params)
        sharded = shard_pytree(mesh, moe.param_specs(cfg), params)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_fn(p, tokens, cfg, mesh)))(sharded)
    assert abs(float(loss) - float(want)) <= LOSS_TOL
    _assert_grads_close(jax.device_get(grads), want_grads)


def test_training_learns_on_ep_mesh(cfg):
    """Full sharded train loop on dp2 x ep2 x tp2: loss decreases."""
    mc = MeshConfig(dp=2, fsdp=1, ep=2, sp=1, tp=2).resolve(8)
    mesh = build_mesh(mc)
    specs = moe.param_specs(cfg)
    params = moe.init_params(cfg, jax.random.key(0))
    params = shard_pytree(mesh, specs, params)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.key(3), (8, 16), 0, cfg.vocab_size)

    @jax.jit
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(moe.loss_fn)(
            params, tokens, cfg, mesh
        )
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses
    # expert weights actually sharded over ep
    w = params["layers"]["w_gate"]
    ep_axis_sizes = {s.data.shape[1] for s in w.addressable_shards}
    assert ep_axis_sizes == {cfg.n_experts // 2}


def test_validate_rejects_bad_ep(cfg):
    mc = MeshConfig(dp=1, fsdp=1, ep=8, sp=1, tp=1).resolve(8)
    mesh = build_mesh(mc)
    with pytest.raises(ValueError, match="n_experts"):
        moe.validate_for_mesh(cfg, mesh)  # 4 experts, ep=8


def test_moe_checkpoint_roundtrip_with_ep_sharding(cfg, tmp_path, monkeypatch):
    """Sharded expert weights stage + restore through the flash engine."""
    import time

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler, shm_name
    from dlrover_tpu.common.constants import NodeEnv

    job = f"moe-ckpt-{int(time.time() * 1000) % 100000}"
    monkeypatch.setenv(NodeEnv.JOB_NAME, job)
    monkeypatch.setenv(NodeEnv.NODE_ID, "0")
    monkeypatch.setenv(NodeEnv.PROCESS_ID, "0")
    try:
        mc = MeshConfig(dp=2, fsdp=1, ep=2, sp=1, tp=2).resolve(8)
        mesh = build_mesh(mc)
        params = shard_pytree(
            mesh, moe.param_specs(cfg), moe.init_params(cfg, jax.random.key(0))
        )
        engine = CheckpointEngine(str(tmp_path / "ckpt"))
        engine.save_to_memory(5, params)
        step, restored = engine.load(target=params)
        assert step == 5
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored["layers"]["w_gate"])),
            np.asarray(jax.device_get(params["layers"]["w_gate"])),
        )
        assert (
            restored["layers"]["w_gate"].sharding
            == params["layers"]["w_gate"].sharding
        )
        engine.close()
    finally:
        h = SharedMemoryHandler(shm_name(job, 0, 0))
        if h.attach():
            h.close(unlink=True)
