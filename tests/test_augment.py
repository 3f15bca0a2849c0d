"""On-device augmentation tests: shapes, determinism, actual variation,
and bit-equality of the batch crop with the per-image form it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dp.analysis import hlo
from tpu_dp.data.augment import make_augment_fn, random_crop_flip
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step


def test_shapes_and_dtype_preserved():
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(np.random.default_rng(0).normal(
        size=(8, 32, 32, 3)).astype(np.float32))
    out = random_crop_flip(rng, images)
    assert out.shape == images.shape and out.dtype == images.dtype


def test_deterministic_in_seed_and_step():
    aug = make_augment_fn(7)
    images = jnp.ones((4, 32, 32, 3), jnp.float32)
    a = aug(jnp.int32(3), images)
    b = aug(jnp.int32(3), images)
    c = aug(jnp.int32(4), images)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_crop_shifts_and_pads_black():
    # A constant-1 image: any nonzero shift drags padding into view. The
    # pad value is -1 — black in the step's [-1, 1]-normalized pixel space,
    # matching torchvision RandomCrop's zero-pad *before* Normalize.
    aug = make_augment_fn(0)
    images = jnp.ones((64, 32, 32, 3), jnp.float32)
    out = np.asarray(aug(jnp.int32(0), images))
    assert (out == -1).any()  # padding visible on shifted images
    assert (out == 1).sum() > out.size * 0.5  # mostly original content
    # Raw-pixel-space use keeps the zero-pad default.
    raw = np.asarray(random_crop_flip(jax.random.PRNGKey(0), images))
    assert ((raw == 0) | (raw == 1)).all()


def test_augmented_training_still_learns(mesh8):
    model, opt = Net(), SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step = make_train_step(
        model, opt, mesh8, constant_lr(0.05), augment_fn=make_augment_fn(1)
    )
    ds = make_synthetic(256, 10, seed=1, name="aug")
    losses = []
    for i in range(12):
        sel = slice((i * 64) % 256, (i * 64) % 256 + 64)
        state, m = step(
            state, {"image": normalize(ds.images[sel]), "label": ds.labels[sel]}
        )
        losses.append(float(m["loss"]))
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_augment_with_accum_runs(mesh8):
    model, opt = Net(), SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step = make_train_step(
        model, opt, mesh8, constant_lr(0.05), accum_steps=2,
        augment_fn=make_augment_fn(1),
    )
    ds = make_synthetic(32, 10, seed=2, name="aug")
    batch = {
        "image": normalize(ds.images).reshape(2, 16, 32, 32, 3),
        "label": ds.labels.reshape(2, 16),
    }
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"])) and int(m["count"]) == 32


def _per_image_crop_flip(rng, images, pad=4, fill=0.0):
    """The crop as the program had it until PR 26, frozen here as the
    yardstick: `vmap` of a `dynamic_slice` at each image's own offset. The
    program must not go back to it (`tpu_dp/data/augment.py`: a loop of one
    trip an image on the TPU); its pixels are what the batch form has to
    return, bit for bit, for the same key."""
    n, h, w, c = images.shape
    k_off, k_flip = jax.random.split(rng)
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     constant_values=fill)
    offsets = jax.random.randint(k_off, (n, 2), 0, 2 * pad + 1)
    flips = jax.random.bernoulli(k_flip, 0.5, (n,))

    def one(img, off, flip):
        crop = jax.lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, c))
        return jnp.where(flip, crop[:, ::-1, :], crop)

    return jax.vmap(one)(padded, offsets, flips)


def _images(shape, dtype, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), dtype=dtype)


def _same_bits(a, b):
    assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_array_equal(
        np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("fill", [0.0, -1.0])
@pytest.mark.parametrize("pad", [4, 2])
@pytest.mark.parametrize("shape,dtype", [
    ((8, 32, 32, 3), jnp.float32),
    ((5, 28, 28, 1), jnp.float32),
    ((6, 32, 32, 3), jnp.bfloat16),
])
def test_batch_crop_equals_per_image_crop(shape, dtype, pad, fill):
    images = _images(shape, dtype)
    for k in (0, 1, 2):
        key = jax.random.PRNGKey(k)
        got = random_crop_flip(key, images, pad=pad, fill=fill)
        want = _per_image_crop_flip(key, images, pad=pad, fill=fill)
        _same_bits(got, want)
        # Not the identity: some image moved.
        assert not np.array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(images.astype(jnp.float32)))


@pytest.mark.parametrize("seed,step", [(1, 0), (7, 12345)])
def test_replay_draws_the_crops_it_always_drew(seed, step):
    """Checkpoint replay: `(seed, step)` names the same crops as before."""
    images = _images((8, 32, 32, 3), jnp.float32, seed=seed)
    got = jax.jit(make_augment_fn(seed))(jnp.int32(step), images)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    _same_bits(got, _per_image_crop_flip(key, images, fill=-1.0))


def test_batch_crop_under_the_accumulation_vmap():
    """`_make_accum_body` maps the crop over microbatches with one key a
    microbatch: the same arrays as one call a microbatch."""
    k, step, seed = 3, 5, 2
    aug = make_augment_fn(seed)
    images = _images((k, 4, 32, 32, 3), jnp.float32)

    def mapped(step, images):
        return jax.vmap(lambda i, im: aug(step * k + i, im))(
            jnp.arange(k), images)

    got = jax.jit(mapped)(jnp.int32(step), images)
    for i in range(k):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step * k + i)
        _same_bits(got[i], _per_image_crop_flip(key, images[i], fill=-1.0))
    # No gather and no dynamic slice: nothing for the TPU's compiler to
    # turn into a loop (`tests/test_tpu_compile.py` asks that compiler).
    text, _, _ = hlo.lower_and_compile(
        jax.jit(mapped), (jnp.int32(step), images))
    assert " select(" in text
    for kind in ("gather", "dynamic-slice", "dynamic-update-slice"):
        assert f" {kind}(" not in text, kind


def test_sharded_crop_equals_one_device_and_needs_no_collective(mesh8):
    """Elementwise along the batch: each device crops its own shard, with
    the offsets the whole batch drew, and nothing crosses devices."""
    aug = make_augment_fn(3)
    images = _images((32, 32, 32, 3), jnp.float32)
    sharded = jax.device_put(images, NamedSharding(mesh8, P("data")))
    fn = jax.jit(aug, out_shardings=NamedSharding(mesh8, P("data")))
    got = fn(jnp.int32(9), sharded)
    assert got.sharding.spec == P("data")
    _same_bits(got, jax.jit(aug)(jnp.int32(9), images))
    text, _, _ = hlo.lower_and_compile(fn, (jnp.int32(9), sharded))
    assert hlo.count_collectives(text) == {}
