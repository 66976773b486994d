"""The phi4flash family on the CPU at ``tiny-cpu-phi4flash`` (``M S M S M F
G C G C`` at d 64, 4 / 2 heads of 16, 128 channels of 16 states, window
16, seq 64): the program against the family's plain reference (forward,
loss, every gradient, remat on and off); that the shared tensors come
from the two producers alone and their cotangents are the readers' sum;
the window's edge; the absence of a position term; the LayerNorm. Sizes,
FLOPs, the first loss, gauges, meshes and the trainer are
``test_phi4flash_mesh.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import phi4flash as family
from dlrover_tpu.models import phi4flash
from dlrover_tpu.ops.norms import layer_norm
from tests.phi4flash_family import (  # noqa: F401  (fixtures by import)
    BRANCH_ENDS, built, config, mesh)
from tests.plain_forms import jitted_plain_loss
from tests.smallthinker_family import _assert_grads_agree


# ---------------------------------------------------------------------------
# The program against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("remat", ["off", "all"])
def test_loss_and_every_gradient_are_the_plain_references(config, mesh, built,
                                                          remat):
    _, params, tokens = built
    config = dict(config, assumed=dict(config["assumed"], remat=remat))
    fam = family.build(config, mesh)
    assert fam.cfg.remat == (remat == "all")
    loss, grads = jax.jit(jax.value_and_grad(fam.loss_fn))(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        jitted_plain_loss(family, config)))(params, tokens)
    assert abs(float(loss) - float(want)) < 2e-5
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    _assert_grads_agree(grads, want_grads)
    # every leaf has a gradient that is not nothing: no layer is skipped
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert float(jnp.max(jnp.abs(g))) > 0, jax.tree_util.keystr(path)


def test_every_mixers_output_is_the_plain_references(config, built):
    """``forward_taps``: the program's own wiring, a layer's mixer at a
    time, against the reference's chain."""
    fam, params, tokens = built
    hidden, mixes = jax.jit(
        lambda p, t: phi4flash.forward_taps(p, t, fam.cfg))(params, tokens)
    kinds = config["layer_kinds"]

    @jax.jit
    def chain(params, tokens):
        x, shared, out = params["embed"][tokens], (None, None, None), []
        for i, (kind, lp) in enumerate(family.layers_of(params, kinds)):
            pieces = family._ref_block(x, lp, config, kind, shared)
            shared = family._handed(shared, kind, i == kinds.rindex("M"),
                                    pieces)
            x = pieces["after"]
            out.append(pieces["mix"])
        return x, jnp.stack(out)

    want_hidden, want_mixes = chain(params, tokens)
    assert mixes.shape == (10, 2, 64, 64)
    for i, kind in enumerate(kinds):
        scale = float(jnp.max(jnp.abs(want_mixes[i])))
        assert scale > 1e-3, (i, kind)
        assert float(jnp.max(jnp.abs(mixes[i] - want_mixes[i]))) < (
            2e-5 * scale), (i, kind)
    np.testing.assert_allclose(hidden, want_hidden, atol=2e-5)


@pytest.mark.parametrize("mutate", family.MUTATIONS)
def test_a_reference_made_wrong_is_another_function(config, built, mutate):
    """Each way `MUTATIONS` names moves the loss: the plain reference
    states what the memory is, which keys a C layer reads and where the
    window ends."""
    _, params, tokens = built
    right = float(jitted_plain_loss(family, config)(params, tokens))
    wrong = float(jax.jit(lambda p, t: family.plain_loss(
        p, t, config, mutate))(params, tokens))
    assert abs(wrong - right) > 1e-4, (mutate, wrong, right)


# ---------------------------------------------------------------------------
# The shared tensors: whose they are, and what flows back into them
# ---------------------------------------------------------------------------

def _silenced(params, part):
    """``params`` with the layer(s) of ``part`` adding nothing to the
    residual: their parameters reach later layers only through what the
    layer hands on."""
    def tree(lp):
        return {k: jnp.zeros_like(v) if k in BRANCH_ENDS else v
                for k, v in lp.items()}

    if part in ("memory", "keys"):
        return dict(params, **{part: tree(params[part])})
    group, pos = part
    return dict(params, **{group: dict(
        params[group], **{pos: tree(params[group][pos])})})


def _nudged(params, part, name, columns=slice(None)):
    lp = params[part] if isinstance(part, str) else params[part[0]][part[1]]
    new = dict(lp, **{name: lp[name].at[..., columns].multiply(1.5)})
    if isinstance(part, str):
        return dict(params, **{part: new})
    return dict(params, **{part[0]: dict(params[part[0]], **{part[1]: new})})


@pytest.mark.parametrize("part, name, columns, readers", [
    # layer 4's scan (W_x: its B, C and step) is the G layers' memory
    ("memory", "w_x", slice(None), "G"),
    # layer 5's keys and values are the C layers'
    ("keys", "w_qkv", slice(64, 128), "C"),
    # the first decoder's scans and keys are nobody's but their layer's
    (("first", "pos0"), "w_x", slice(None), ""),
    (("first", "pos1"), "w_qkv", slice(64, 128), ""),
])
def test_the_shared_tensors_come_from_the_two_producers_alone(
        built, part, name, columns, readers):
    """With a layer's own branches silenced, a change to what makes its
    scan (or its keys and values) reaches exactly the later mixers that
    read it: the G layers for layer 4's scan, the C layers for layer 5's
    keys, none for a first-decoder layer's."""
    fam, params, tokens = built
    taps = jax.jit(lambda p: phi4flash.forward_taps(p, tokens, fam.cfg)[1])
    base = _silenced(params, part)
    before, after = taps(base), taps(_nudged(base, part, name, columns))
    kinds = fam.cfg.kinds
    own = {"memory": [4], "keys": [5], ("first", "pos0"): [0, 2],
           ("first", "pos1"): [1, 3]}[part]
    first_reader = min((i for i, kind in enumerate(kinds)
                        if kind in readers and i > own[0]), default=len(kinds))
    for i, kind in enumerate(kinds):
        moved = float(jnp.max(jnp.abs(after[i] - before[i])))
        if i in own:
            continue      # its own mixer moves (and is silenced after)
        if kind in readers and i > own[0]:
            assert moved > 1e-6, (i, kind)
        elif i < first_reader:
            # what follows a reader reads the reader's residual
            assert moved == 0.0, (i, kind, moved)


def test_the_shared_cotangents_are_the_sums_over_their_readers(built):
    """d of the second decoder's output in ``m*``, ``k*``, ``v*`` (its
    scan's constants) is the sum of what each reading layer alone gives,
    the layers run in line each on a copy of its own."""
    fam, params, tokens = built
    cfg = fam.cfg
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (2, 64, 64)) * 0.1
    memory = jax.random.normal(ks[1], (2, 64, 128))
    k = jax.random.normal(ks[2], (2, 64, 2, 16))
    v = jax.random.normal(ks[3], (2, 64, 2, 16))
    g = jax.random.normal(ks[4], (2, 64, 64))

    @jax.jit
    def program(memory, k, v):
        return jax.grad(lambda m, k, v: jnp.sum(g * phi4flash.second_decoder(
            cfg, None, params, x, m, k, v)[0]), argnums=(0, 1, 2))(
                memory, k, v)

    @jax.jit
    def a_copy_a_reader(memory, k, v):
        def run(copies):
            h = x
            for layer, (m_i, k_i, v_i) in zip(range(6, 10), copies):
                h = phi4flash.block(
                    cfg, None, cfg.kinds[layer],
                    phi4flash.layer_params(cfg, params, layer), h, m_i, k_i,
                    v_i)[0]
            return jnp.sum(g * h)

        return jax.grad(run)([(memory, k, v)] * 4)

    got = program(memory, k, v)
    each = a_copy_a_reader(memory, k, v)
    for j, name in enumerate(("m", "k", "v")):
        alone = [each[i][j] for i in range(4)]
        reads = [float(jnp.max(jnp.abs(a))) > 0 for a in alone]
        # G layers (6, 8) read the memory alone, C layers (7, 9) k and v
        assert reads == ([True, False] * 2 if name == "m"
                         else [False, True] * 2), name
        np.testing.assert_allclose(got[j], sum(alone), rtol=2e-5, atol=1e-7)


def test_which_tensors_a_reader_reads(built):
    fam, params, _ = built
    cfg = fam.cfg
    ks = jax.random.split(jax.random.key(8), 4)
    y = jax.random.normal(ks[0], (1, 32, 64))
    shared = (jax.random.normal(ks[1], (1, 32, 128)),
              jax.random.normal(ks[2], (1, 32, 2, 16)),
              jax.random.normal(ks[3], (1, 32, 2, 16)))
    for layer, reads in ((6, (True, False, False)), (7, (False, True, True))):
        lp = phi4flash.layer_params(cfg, params, layer)
        grads = jax.grad(lambda *s: jnp.sum(phi4flash.mixer(
            cfg, None, cfg.kinds[layer], lp, y, s)[0] ** 2),
            argnums=(0, 1, 2))(*shared)
        assert tuple(float(jnp.max(jnp.abs(d))) > 0 for d in grads) == reads


# ---------------------------------------------------------------------------
# The window's edge, no position term, the LayerNorm
# ---------------------------------------------------------------------------

def _window_layer(built):
    fam, params, _ = built
    lp = phi4flash.layer_params(fam.cfg, params, 1)
    y = jax.random.normal(jax.random.key(9), (1, 64, 64))
    return fam.cfg, lp, y


def test_a_window_layer_sees_fifteen_keys_back_and_not_sixteen(built):
    cfg, lp, y = _window_layer(built)
    run = jax.jit(lambda y: phi4flash.attention_mixer(cfg, lp, y, cfg.window
                                                      )[0])
    base, moved = run(y), run(y.at[0, 20].add(1.0))
    change = jnp.max(jnp.abs(moved - base), axis=-1)[0]
    assert float(change[20 + 15]) > 1e-6
    assert float(jnp.max(change[20 + 16:])) == 0.0
    assert float(jnp.max(change[:20])) == 0.0
    # the full layer has no such edge
    full = jax.jit(lambda y: phi4flash.attention_mixer(cfg, lp, y, None)[0])
    change = jnp.max(jnp.abs(full(y.at[0, 20].add(1.0)) - full(y)), -1)[0]
    assert float(change[63]) > 1e-7


def test_no_position_term_a_shift_relabels_nothing(built):
    """A window layer's output at a position is a function of the last 16
    inputs alone, whatever their positions are called: the sequence cut
    at its front gives the same rows once a whole window lies behind.
    And the family's parameters name no position table."""
    cfg, lp, y = _window_layer(built)
    run = jax.jit(lambda y: phi4flash.attention_mixer(cfg, lp, y, cfg.window
                                                      )[0])
    whole, cut = run(y), run(y[:, 24:])
    np.testing.assert_allclose(whole[:, 24 + 15:], cut[:, 15:], atol=1e-6)
    names = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(built[1])[0]}
    assert not any(w in n for n in names for w in ("pos_", "rope", "rotary"))


def test_layer_norm_is_its_closed_form_with_a_bias():
    ks = jax.random.split(jax.random.key(10), 3)
    x = jax.random.normal(ks[0], (3, 5, 64)) * 2 + 0.7
    w, b = jax.random.normal(ks[1], (64,)), jax.random.normal(ks[2], (64,))
    x64 = np.asarray(x, np.float64)
    mean = x64.mean(-1, keepdims=True)
    var = ((x64 - mean) ** 2).mean(-1, keepdims=True)
    want = (x64 - mean) / np.sqrt(var + 1e-5) * np.asarray(w) + np.asarray(b)
    np.testing.assert_allclose(layer_norm(x, w, b, 1e-5), want, atol=2e-6)
    np.testing.assert_allclose(family._ln(x, w, b, 1e-5), want, atol=2e-6)
    # a bias of zero and a mean of zero would hide both
    assert abs(float(x.mean())) > 0.5 and float(jnp.abs(b).min()) > 0
