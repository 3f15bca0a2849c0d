"""The job's inputs, made from ``--seed`` by the benchmark.

One general generator reads a traffic file's parameters and makes the data
set a run trains on: class templates plus Gaussian pixel noise, uint8
NHWC, so that the loss falls as on real images. It is made on the device
(the program's host generator takes 19 s for these 400 MB on the chip's
host), and the same function gives the plain reference the rows of the
steps it follows, so the reference takes nothing from the program.

The order in which rows reach the step is the program's `ShardedSampler`
contract, restated here for the reference: epoch ``e`` draws
``numpy.random.default_rng([train.seed, e]).permutation(n)`` and step ``s`` takes
rows ``[s * batch, (s + 1) * batch)`` of it, cut over the chips in order.
"""

from __future__ import annotations

import functools

import numpy as np

NOISE_SIGMA = 24.0


CHUNK = 16384


@functools.lru_cache(maxsize=None)
def _chunk_fn(num_classes: int, image_size: int, channels: int):
    import jax
    import jax.numpy as jnp

    shape = (image_size, image_size, channels)

    def gen(key, chunk):
        k_t, k_c = jax.random.split(key)
        templates = jax.random.randint(
            k_t, (num_classes, *shape), 0, 256, dtype=jnp.int32
        ).astype(jnp.float32)
        k_l, k_n = jax.random.split(jax.random.fold_in(k_c, chunk))
        labels = jax.random.randint(k_l, (CHUNK,), 0, num_classes,
                                    dtype=jnp.int32)
        noise = jax.random.normal(k_n, (CHUNK, *shape), jnp.float32)
        images = jnp.clip(templates[labels] + noise * NOISE_SIGMA,
                          0.0, 255.0).astype(jnp.uint8)
        return images, labels

    return jax.jit(gen)


def make_chunk(seed: int, chunk: int, num_classes: int, image_size: int = 32,
               channels: int = 3):
    """Rows ``[chunk * CHUNK, (chunk + 1) * CHUNK)`` of the seed's data set,
    as device arrays ``(images uint8, labels int32)``. A chunk depends on
    the seed and its own number alone: the run makes the set chunk by
    chunk, so that the benchmark's own footprint on the device stays under
    the program's, and the reference makes the same chunks again."""
    import jax
    import jax.numpy as jnp

    # The data key is apart from the weights' key, PRNGKey(seed).
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0x64617461)
    return _chunk_fn(num_classes, image_size, channels)(
        key, jnp.int32(chunk))


def _chunks(seed, n, num_classes, image_size, channels):
    return [make_chunk(seed, c, num_classes, image_size, channels)
            for c in range(-(-n // CHUNK))]


@functools.lru_cache(maxsize=None)
def _rows_fn():
    import jax

    return jax.jit(lambda x: x.reshape(x.shape[0], -1))


def make_dataset(seed: int, n: int, num_classes: int, image_size: int = 32,
                 channels: int = 3):
    """The whole set as host arrays ``(images [n,h,w,c], labels [n])``,
    C-contiguous as the program's own loaders hand theirs over.

    Each chunk is flattened to ``(CHUNK, h*w*c)`` on the device before it
    is fetched and viewed back here: a four-dimensional uint8 array reaches
    the host in the device's own stride order (on a TPU the batch is the
    minor dimension), and a gather of rows out of that costs a hundred
    times what it costs out of row-major bytes. A chunk is on its way to the
    host while the next two are made, and leaves the device once it has
    arrived: the benchmark's own footprint there stays at a few chunks."""
    in_flight, images, labels = [], [], []

    def land():
        flat, y = in_flight.pop(0)
        images.append(np.asarray(flat))
        labels.append(np.asarray(y))

    for c in range(-(-n // CHUNK)):
        x, y = make_chunk(seed, c, num_classes, image_size, channels)
        flat = _rows_fn()(x)
        del x
        flat.copy_to_host_async()
        y.copy_to_host_async()
        in_flight.append((flat, y))
        if len(in_flight) > 2:
            land()
    while in_flight:
        land()
    images = np.concatenate(images)[:n]
    return (images.reshape(n, image_size, image_size, channels),
            np.concatenate(labels)[:n])


def device_dataset(seed: int, n: int, num_classes: int, image_size: int = 32,
                   channels: int = 3):
    """The ``n``-row set as device arrays: the chunks made again on the
    device, for the reference to take its rows from."""
    import jax.numpy as jnp

    chunks = _chunks(seed, n, num_classes, image_size, channels)
    return (jnp.concatenate([x for x, _ in chunks])[:n],
            jnp.concatenate([y for _, y in chunks])[:n])


def epoch_order(seed: int, epoch: int, n: int) -> np.ndarray:
    """Row order of one epoch (the sampler contract, one process)."""
    return np.random.default_rng([int(seed), int(epoch)]).permutation(n)


def step_rows(seed: int, epoch: int, n: int, batch: int,
              step: int) -> np.ndarray:
    """Rows of global step ``step`` of ``epoch``, in the order they are cut
    over the chips."""
    order = epoch_order(seed, epoch, n)
    return order[step * batch:(step + 1) * batch]
