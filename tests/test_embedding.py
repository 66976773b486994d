"""The token embedding (``ops/embedding.py``): a row lookup with a
row-summing gradient where the vocabulary is whole on a device, the
one-hot contraction where tp shards it.

The oracle is the one-hot product itself, the form every mesh ran
before: ``one_hot(tokens) @ table`` forward, ``one_hot^T @ dY``
backward. It is this file's own code and shares nothing with the
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama, moe
from dlrover_tpu.observability import trace
from dlrover_tpu.ops import embedding
from dlrover_tpu.ops.embedding import embed_lookup
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings

VOCAB, DIM = 1000, 64
BF16_EPS = 2.0 ** -8  # one rounding of an f32 sum to bf16


def one_hot_lookup(table, tokens, dtype=jnp.bfloat16):
    hot = jax.nn.one_hot(tokens, table.shape[0], dtype=dtype)
    return jnp.einsum("bsv,vd->bsd", hot, table.astype(dtype))


def _zipf(key, shape, vocab):
    # P(id) ~ 1 / (id + 1): a few ids hundreds of times, most never
    u = jax.random.uniform(key, shape)
    return jnp.minimum(jnp.exp(u * np.log(vocab)).astype(jnp.int32) - 1,
                       vocab - 1)


def _token_cases():
    k = jax.random.key(11)
    return {
        "distinct": jax.random.permutation(k, VOCAB)[:768].reshape(2, 384),
        # a run through five 512-row blocks, beside short ones
        "one_token_2048_times": jnp.concatenate([
            jnp.full((2048,), 7),
            jax.random.randint(k, (1024,), 0, VOCAB)]).reshape(2, 1536),
        "zipf": _zipf(k, (4, 700), VOCAB),
        "outside_the_table": jnp.array(
            [[-1, VOCAB, 3, 3, 5, VOCAB + 7, -5, VOCAB - 1]]),
        # 1113 rows: the last block is padded
        "not_whole_blocks": jax.random.randint(k, (3, 371), 0, VOCAB),
    }


TOKEN_CASES = sorted(_token_cases())


@pytest.fixture(scope="module")
def table():
    return jax.random.normal(jax.random.key(0), (VOCAB, DIM), jnp.bfloat16)


def _tokens_and_dy(case):
    tokens = _token_cases()[case].astype(jnp.int32)
    dy = jax.random.normal(jax.random.key(3), tokens.shape + (DIM,),
                           jnp.bfloat16)
    return tokens, dy


def _table_grad(lookup, table, tokens, dy):
    return jax.jit(jax.grad(
        lambda t: jnp.sum(lookup(t, tokens).astype(jnp.float32) * dy)
    ))(table)


@pytest.mark.parametrize("case", TOKEN_CASES)
def test_forward_is_bit_equal_to_one_hot(table, case):
    tokens, _ = _tokens_and_dy(case)
    got = jax.jit(lambda t: embed_lookup(t, tokens))(table)
    want = one_hot_lookup(table, tokens)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("case", TOKEN_CASES)
def test_table_gradient_matches_one_hot(table, case):
    """Within one bf16 rounding of the exact f32 sums, which is all the
    one-hot product's own gradient is."""
    tokens, dy = _tokens_and_dy(case)
    got = _table_grad(embed_lookup, table, tokens, dy)
    assert got.dtype == table.dtype
    exact = jnp.einsum(
        "bsv,bsd->vd", jax.nn.one_hot(tokens, VOCAB, dtype=jnp.float32),
        dy.astype(jnp.float32), precision="highest")
    err = jnp.abs(got.astype(jnp.float32) - exact)
    assert bool(jnp.all(err <= BF16_EPS * jnp.abs(exact) + 1e-30)), float(
        err.max())
    want = _table_grad(one_hot_lookup, table, tokens, dy)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2 * BF16_EPS, atol=0)


def test_id_outside_the_table_reads_zero_and_leaves_no_gradient(table):
    tokens, dy = _tokens_and_dy("outside_the_table")
    x = embed_lookup(table, tokens)
    outside = np.asarray((tokens < 0) | (tokens >= VOCAB))
    assert outside.sum() == 4
    assert not np.asarray(x, np.float32)[outside].any()
    assert np.asarray(x, np.float32)[~outside].any(axis=-1).all()
    grad = np.asarray(_table_grad(embed_lookup, table, tokens, dy),
                      np.float32)
    touched = np.zeros(VOCAB, bool)
    touched[[3, 5, VOCAB - 1]] = True
    assert not grad[~touched].any()
    # -1 wraps to the last row in a plain table[tokens]: that row holds
    # its own token's dY and nothing else
    np.testing.assert_array_equal(
        grad[VOCAB - 1], np.asarray(dy, np.float32)[0, 7])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradient_keeps_the_parameters_dtype(dtype):
    """f32 parameters under bf16 activations get an f32 gradient, as the
    cast's transpose gave them before; f32 activations sum exactly."""
    table = jax.random.normal(jax.random.key(0), (VOCAB, DIM), jnp.float32)
    tokens, dy = _tokens_and_dy("zipf")
    grads = [
        jax.jit(jax.grad(lambda t: jnp.sum(
            lookup(t, tokens, dtype=dtype).astype(jnp.float32) * dy)))(table)
        for lookup in (lambda t, tok, dtype: embed_lookup(t, tok, None, dtype),
                       one_hot_lookup)
    ]
    assert grads[0].dtype == jnp.float32
    tol = 1e-6 if dtype == jnp.float32 else 2 * BF16_EPS
    np.testing.assert_allclose(grads[0], grads[1], rtol=tol, atol=1e-6)


def test_backward_adds_by_no_scatter_and_no_vocabulary_wide_product(table):
    tokens, dy = _tokens_and_dy("zipf")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda t: jnp.sum(embed_lookup(t, tokens).astype(jnp.float32) * dy)
    ))(table)
    # rows are summed by products; the one scatter writes, each row once
    assert "scatter-add" not in str(jaxpr) and "scatter_add" not in str(jaxpr)

    def dots(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    products = list(dots(jaxpr.jaxpr))
    assert products  # the block sums
    for eqn in products:
        for v in eqn.invars:
            assert VOCAB not in v.aval.shape, eqn


def test_every_operation_carries_the_scope(table):
    tokens, dy = _tokens_and_dy("zipf")
    text = jax.jit(jax.grad(
        lambda t: jnp.sum(embed_lookup(t, tokens).astype(jnp.float32) * dy)
    )).lower(table).compile().as_text()
    assert "embed_lookup" in text
    assert "transpose(jvp(embed_lookup))" in text


# ---------------------------------------------------------------------------
# under a mesh
# ---------------------------------------------------------------------------

LOOKUP_MESHES = {
    "dp2-fsdp2": dict(dp=2, fsdp=2),
    "fsdp4": dict(dp=1, fsdp=4),
    "sp2": dict(dp=1, sp=2),
    "dp2-fsdp2-sp2": dict(dp=2, fsdp=2, sp=2),
}
ONE_HOT_MESH = dict(dp=1, fsdp=2, sp=2, tp=2)


def _mesh(axes):
    mc = MeshConfig(**axes)
    n = mc.pp * mc.fsdp * mc.ep * mc.sp * mc.tp * max(mc.dp, 1)
    return build_mesh(mc, devices=jax.devices()[:n])


@pytest.mark.parametrize("axes", sorted(LOOKUP_MESHES))
def test_lookup_on_a_mesh_matches_one_device(table, axes, capfd):
    """tp == 1: the lookup form, the table's dim gathered over fsdp, the
    gradient reduced back to the table's own sharding."""
    from jax.sharding import NamedSharding

    mesh = _mesh(LOOKUP_MESHES[axes])
    tokens = _zipf(jax.random.key(5), (4, 64), VOCAB)
    dy = jax.random.normal(jax.random.key(6), (4, 64, DIM), jnp.bfloat16)
    spec = llama.param_specs(llama.LlamaConfig.tiny())["embed"]
    sharded = jax.device_put(table, NamedSharding(mesh, spec))

    def value_and_grad(lookup, t):
        return jax.jit(jax.value_and_grad(
            lambda t: jnp.sum(lookup(t).astype(jnp.float32) * dy)))(t)

    trace.gauge("embed.gather", -1)
    got, got_grad = value_and_grad(
        lambda t: embed_lookup(t, tokens, mesh), sharded)
    assert trace.gauges()["embed.gather"] == 1
    assert trace.gauges()["embed.vocab"] == VOCAB
    want, want_grad = value_and_grad(
        lambda t: embed_lookup(t, tokens), table)
    assert "Involuntary full rematerialization" not in capfd.readouterr().err
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    assert got_grad.sharding.is_equivalent_to(sharded.sharding, 2)
    # each device rounds its own tokens' sums to bf16 before they are
    # added in bf16, as the one-hot product's partial sums were: a sum
    # that cancels keeps the rounding of its largest part
    want_grad = np.asarray(want_grad, np.float32)
    np.testing.assert_allclose(
        np.asarray(got_grad, np.float32), want_grad,
        rtol=4 * BF16_EPS, atol=BF16_EPS * np.abs(want_grad).max())


def test_tp_mesh_keeps_the_one_hot_product(table):
    mesh = _mesh(ONE_HOT_MESH)
    tokens, _ = _tokens_and_dy("zipf")
    trace.gauge("embed.gather", -1)
    jaxpr = jax.make_jaxpr(lambda t: embed_lookup(t, tokens, mesh))(table)
    assert trace.gauges()["embed.gather"] == 0
    assert "custom_vjp" not in str(jaxpr)
    got = jax.jit(lambda t: embed_lookup(t, tokens, mesh))(table)
    assert bool(jnp.all(got == one_hot_lookup(table, tokens)))


MODEL_MESHES = dict(LOOKUP_MESHES, **{"fsdp2-sp2-tp2": ONE_HOT_MESH})


@pytest.mark.parametrize("axes", sorted(MODEL_MESHES))
def test_llama_on_a_mesh_matches_one_device(axes, capfd):
    """Same loss and gradients as the one-device run, and a compile
    without "Involuntary full rematerialization", in either form."""
    mesh = _mesh(MODEL_MESHES[axes])
    ring = mesh.shape["sp"] > 1
    cfg = llama.LlamaConfig.tiny(attn_impl="ring" if ring else "auto")
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, llama.LlamaConfig.tiny())))(params)
    sharded = jax.device_put(
        params, named_shardings(mesh, llama.param_specs(cfg)))
    trace.gauge("embed.gather", -1)
    got, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, cfg, mesh)))(sharded)
    jax.block_until_ready(grads)
    assert "Involuntary full rematerialization" not in capfd.readouterr().err
    assert trace.gauges()["embed.gather"] == int(mesh.shape["tp"] == 1)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    for g, w in zip(jax.tree.leaves(jax.device_get(grads)),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5)


# ---------------------------------------------------------------------------
# the models, against themselves with the one-hot product put back
# ---------------------------------------------------------------------------

def _with_one_hot(monkeypatch, module):
    monkeypatch.setattr(
        module, "embed_lookup",
        lambda embed, tokens, mesh=None, dtype=jnp.bfloat16:
        one_hot_lookup(embed, tokens, dtype))


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_loss_fn_is_what_it_was_with_the_one_hot_product(monkeypatch, family):
    module, cfg = {
        "llama": (llama, llama.LlamaConfig.tiny()),
        "moe": (moe, moe.MoeConfig.tiny()),
    }[family]
    params = module.init_params(cfg, jax.random.key(0))
    tokens = _zipf(jax.random.key(1), (2, 48), cfg.vocab_size)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda p: module.loss_fn(p, tokens, cfg)))(params)

    got, grads = run()
    _with_one_hot(monkeypatch, module)
    want, want_grads = run()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-6, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("block", [128, 1000, 4096])
def test_any_run_block_sums_the_same(monkeypatch, block):
    """The block is a cost, not a contract."""
    tokens, dy = _tokens_and_dy("one_token_2048_times")
    t = tokens.size
    want = embedding._row_sums(tokens.reshape(t), dy.reshape(t, DIM), VOCAB)
    monkeypatch.setattr(embedding, "_RUN_BLOCK", block)
    got = embedding._row_sums(tokens.reshape(t), dy.reshape(t, DIM), VOCAB)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2 * BF16_EPS)
