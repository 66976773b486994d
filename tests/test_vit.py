"""ViT family: patchify, forward numerics, sharded training on the
virtual mesh through the same ElasticTrainer as the LM families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import vit
from dlrover_tpu.parallel import MeshConfig, build_mesh, named_shardings
from dlrover_tpu.train.trainer import ElasticTrainer, TrainConfig

CFG = vit.ViTConfig.tiny()


@pytest.fixture(scope="module")
def params():
    return vit.init_params(CFG, jax.random.key(0))


def _batch(key, n=4):
    k1, k2 = jax.random.split(jax.random.key(key))
    images = jax.random.normal(k1, (n, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(k2, (n,), 0, CFG.n_classes)
    return images, labels


def test_patchify_roundtrip_layout():
    """Each patch row is the raster-order pixels of one 8x8 tile."""
    images = jnp.arange(32 * 32 * 3, dtype=jnp.float32).reshape(1, 32, 32, 3)
    patches = vit.patchify(CFG, images)
    assert patches.shape == (1, 16, 8 * 8 * 3)
    # first patch, first pixel == image[0, 0, 0, :]
    np.testing.assert_array_equal(
        np.asarray(patches[0, 0, :3]), np.asarray(images[0, 0, 0])
    )
    # second grid-row patch starts at image row 8
    np.testing.assert_array_equal(
        np.asarray(patches[0, 4, :3]), np.asarray(images[0, 8, 0])
    )


def test_forward_shapes_and_loss(params):
    images, labels = _batch(1)
    logits = vit.forward(params, images, CFG)
    assert logits.shape == (4, CFG.n_classes)
    loss = float(vit.loss_fn(params, (images, labels), CFG))
    # random init ~ log(n_classes)
    assert abs(loss - np.log(CFG.n_classes)) < 0.5


def test_noncausal_flash_kernel_matches_reference():
    """The Pallas kernel itself (interpret mode, so the real kernel code
    runs on CPU) against full attention, non-causal, at the tiles the
    ViT path gets: the kernel's own choice."""
    from dlrover_tpu.ops.attention import (
        flash_attention,
        flash_tiles,
        mha_reference,
    )

    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(k1, (2, 256, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (2, 256, 4, 32), jnp.float32)
    v = jax.random.normal(k3, (2, 256, 4, 32), jnp.float32)
    assert flash_tiles(256, 256, 32, 1, q.dtype)["fwd"] == (256, 256)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_vit_trains_sharded_with_elastic_trainer(params):
    mc = MeshConfig(dp=2, fsdp=2, sp=1, tp=2).resolve(8)
    mesh = build_mesh(mc)
    specs = vit.param_specs(CFG)
    sharded = jax.device_put(params, named_shardings(mesh, specs))
    tc = TrainConfig(global_batch_size=8, micro_batch_size=2,
                     learning_rate=1e-2, warmup_steps=0, total_steps=20)
    trainer = ElasticTrainer(
        lambda p, b: vit.loss_fn(p, b, CFG, mesh), specs, mesh, mc, tc
    )
    state = trainer.init_state(sharded)
    a, b = trainer.step_batch_shape
    k1, k2 = jax.random.split(jax.random.key(3))
    images = jax.random.normal(k1, (a, b, 32, 32, 3), jnp.float32)
    labels = jax.random.randint(k2, (a, b), 0, CFG.n_classes)
    losses = []
    for _ in range(3):
        state, loss = trainer.step(state, (images, labels))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses  # same batch: must drop


def test_base16_patch_count_gets_valid_flash_blocks():
    """ViT-B/16 has 196 patches, 197 with a class token: no aligned
    tile divides them, so the kernel's chooser takes the sequence as
    one block (a block equal to the array's dim is always legal); only
    a sequence too long for that has no tile and takes reference."""
    from dlrover_tpu.ops.attention import flash_tiles

    def fwd(s):
        tiles = flash_tiles(s, s, 64, 1, jnp.bfloat16)
        return tiles and tiles["fwd"]

    assert fwd(196) == (196, 196)
    assert fwd(197) == (197, 197)  # prime
    assert fwd(16) == (16, 16)
    assert fwd(192) == (192, 192)
    assert fwd(576) == (576, 576)  # ViT-L/14 at 336 px
    assert fwd(1024) == (1024, 512)
    assert fwd(8191) is None  # prime, and too long for one block


def test_loss_ignores_pad_labels():
    # fresh params: the trainer test above donated the fixture's buffers
    params = vit.init_params(CFG, jax.random.key(0))
    images, labels = _batch(4)
    full = float(vit.loss_fn(params, (images, labels), CFG))
    padded_labels = labels.at[2:].set(-1)
    masked = float(vit.loss_fn(params, (images, padded_labels), CFG))
    only_first_two = float(
        vit.loss_fn(params, (images[:2], labels[:2]), CFG)
    )
    assert masked != full
    np.testing.assert_allclose(masked, only_first_two, rtol=1e-5)
