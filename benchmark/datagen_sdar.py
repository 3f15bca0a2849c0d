"""Rows of tokens from ``--seed``, made on the device: ids Zipf(1.0) over
the configuration's slice of the vocabulary through a seeded permutation,
so that some ids, and through the router some experts, are far busier than
others. One document a row (no packing). The mask token, the last id of the
slice, is never drawn. The order in which rows reach the step is
`datagen.step_rows`, the sampler's contract, as for every family."""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _rows_fn(n: int, length: int, vocab: int):
    import jax
    import jax.numpy as jnp

    ids = vocab - 1

    def gen(key):
        k_p, k_u = jax.random.split(key)
        id_of_rank = jax.random.permutation(k_p, ids).astype(jnp.int32)
        cdf = jnp.cumsum(1.0 / jnp.arange(1, ids + 1, dtype=jnp.float32))
        u = jax.random.uniform(k_u, (n, length), jnp.float32) * cdf[-1]
        rank = jnp.minimum(jnp.searchsorted(cdf, u), ids - 1)
        return id_of_rank[rank]

    return jax.jit(gen)


def device_tokens(seed: int, n: int, length: int, vocab: int):
    """The seed's ``[n, length]`` int32 rows, on the device."""
    import jax

    # The data key is apart from the weights' key.
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0x746F6B73)
    return _rows_fn(int(n), int(length), int(vocab))(key)


def make_tokens(seed: int, n: int, length: int, vocab: int):
    """The same rows on the host, C-contiguous."""
    import numpy as np

    return np.ascontiguousarray(device_tokens(seed, n, length, vocab))
