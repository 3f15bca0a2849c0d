"""On-device data augmentation, compiled into the train step.

The reference has no augmentation (its transform is ToTensor+Normalize only,
`/root/reference/cifar_example.py:38-40`), but BASELINE.json's 93% top-1
north star needs the standard CIFAR recipe: pad-4 random crop + horizontal
flip. TPU-first design: instead of host-side per-example transforms (which
would serialize on the single host core), the augmentation is a pure jax
function of ``(step, images)`` executed *on device inside the compiled train
step* — keyed by the global step counter, so it is deterministic, replayable
from a checkpoint, and bitwise-identical on every replica (each device
augments only its own shard; the vmapped per-example keys are derived from
the global step, not from device identity).

How the crop moves its pixels is decided by the TPU, not by taste. The
plain form — `vmap` of a `lax.dynamic_slice` whose start is the image's own
offset — is one HLO `gather` whose slice is a whole image, and the TPU
compiler runs such a gather as a `while` of one trip an image (slice one
image, copy it, `dynamic-update-slice` it into the result): 4,096 trips
and 74 ms of a 204 ms ResNet-18 step on the v5e (PERF.md §6, PR 25/26). So
`random_crop_flip` holds no per-image slice at all: every one of the
`2*pad + 1` row shifts and column shifts is a *static* slice of the whole
batch, and an image's drawn offset only selects among them. That is
elementwise, fuses into one pass, splits along the batch with no
collective, and returns the same array bit for bit. Do not "simplify" it
back to a `dynamic_slice` or a `gather`: `tests/test_augment.py` holds the
old form for comparison and `tests/test_step_scopes.py` refuses a loop or
a gather under `tpu_dp.augment`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def random_crop_flip(
    rng: jax.Array, images: jnp.ndarray, pad: int = 4, fill: float = 0.0
) -> jnp.ndarray:
    """Pad-`pad` constant-pad random crop + random horizontal flip, per image.

    Shape- and dtype-preserving; NHWC. ``fill`` is the pad value: 0 for raw
    pixel space, -1 for [-1, 1]-normalized inputs (black in both cases).
    """
    n, h, w, _ = images.shape
    k_off, k_flip = jax.random.split(rng)
    padded = jnp.pad(
        images, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
        constant_values=fill,
    )
    offsets = jax.random.randint(k_off, (n, 2), 0, 2 * pad + 1)
    flips = jax.random.bernoulli(k_flip, 0.5, (n,))

    # Rows, then columns: the shift by `d` is a static slice of the whole
    # batch, kept for the images whose drawn offset is `d` (module
    # docstring: a per-image `dynamic_slice` is a loop on the TPU).
    off_y = offsets[:, 0].reshape(n, 1, 1, 1)
    off_x = offsets[:, 1].reshape(n, 1, 1, 1)
    rows = padded[:, 0:h]
    for d in range(1, 2 * pad + 1):
        rows = jnp.where(off_y == d, padded[:, d:d + h], rows)
    out = rows[:, :, 0:w]
    for d in range(1, 2 * pad + 1):
        out = jnp.where(off_x == d, rows[:, :, d:d + w], out)
    return jnp.where(flips.reshape(n, 1, 1, 1), out[:, :, ::-1, :], out)


def make_augment_fn(seed: int, fill: float = -1.0):
    """Build ``aug(step, images)``: deterministic in (seed, step).

    The train step calls it with the global step counter (and the microbatch
    index under gradient accumulation), so every optimizer step sees fresh —
    but reproducible — crops/flips. The step augments *after* its on-device
    normalize, so the default ``fill`` of -1 reproduces the standard recipe
    (torchvision RandomCrop pads black *before* Normalize).
    """
    base = jax.random.PRNGKey(seed)

    def aug(step, images: jnp.ndarray) -> jnp.ndarray:
        return random_crop_flip(
            jax.random.fold_in(base, step), images, fill=fill
        )

    return aug
