"""The masking noise of block-diffusion training, drawn inside the step.

A block-diffusion language model (BD3-LM, arXiv:2503.09573) trains on a
row of clean tokens ``x0`` and a noised copy ``xt``: the row is cut into
blocks of ``block`` tokens, each block draws ``t ~ U[T_MIN, 1]``, and each
of its tokens is replaced by the mask token with probability ``t``. The
loss weighs a masked token by ``1/t``.

Like the random crop (`tpu_dp.data.augment`) this is a pure function of
``(step, batch)`` compiled into the train step and keyed by the global
step counter: deterministic, replayable from a checkpoint, and the host
ships ``x0`` only. It runs in the step's augmentation seam under the phase
name it carries (``tpu_dp.noise``). Evaluation draws it once, on a key of
its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

T_MIN = 0.001
EVAL_STEP = 2**31 - 1  # the step whose key evaluation uses


def block_mask_noise(rng: jax.Array, x0: jnp.ndarray, block: int,
                     mask_id: int) -> dict:
    """``{"xt", "x0", "weight"}``, each ``[rows, L]``: the noised rows, the
    clean rows, and ``1/t`` on the masked tokens (0 elsewhere)."""
    rows, length = x0.shape
    if length % block:
        raise ValueError(f"a row of {length} tokens is not whole blocks "
                         f"of {block}")
    k_t, k_u = jax.random.split(rng)
    t = jax.random.uniform(k_t, (rows, length // block), jnp.float32,
                           T_MIN, 1.0)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(k_u, (rows, length), jnp.float32) < t
    return {"xt": jnp.where(masked, jnp.int32(mask_id), x0), "x0": x0,
            "weight": jnp.where(masked, 1.0 / t, 0.0)}


def make_block_noise_fn(seed: int, block: int, mask_id: int):
    """Build ``noise(step, x0)``: deterministic in (seed, step)."""
    base = jax.random.PRNGKey(seed)

    def noise(step, x0: jnp.ndarray) -> dict:
        return block_mask_noise(jax.random.fold_in(base, step), x0, block,
                                mask_id)

    noise.phase = "tpu_dp.noise"   # the step's name for this seam
    noise.in_eval = True           # the objective needs it, unlike a crop
    return noise
