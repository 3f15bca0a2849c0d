"""The family of residual image classifiers trained with SGD and momentum:
what `run.py`, `loop_hook.py` and `calibrate.py` ask of a configuration's
family, answered from `reference.py`, `work.py` and `datagen.py`.

`benchmark/README.md` ("What a family file answers") states the eight
functions and what each is given. A ``job`` carries the configuration's and
the traffic's files, ``seed``, ``program_seed``, ``chips``,
``batch_per_chip``, ``train_size``, ``global_batch`` and
``steps_per_epoch``.
"""

from __future__ import annotations

import datagen
import reference
import work

# The nearest precision below the stated bfloat16 first: the control.
PRECISIONS = ("float8", "float8_operands", "bfloat16")
FAULTS = ("half_batch", "no_exchange")


def _image_shape(job) -> tuple[int, int, int]:
    model = job.config["model"]
    return (int(model["num_classes"]), int(model["image_size"]),
            int(model["image_channels"]))


def items_per_row(job) -> int:
    """A batch row is one image."""
    return 1


def datasets(job):
    """The ``(train, test)`` host data sets of the seed, for
    ``Trainer(cfg, datasets=...)``; the test set is the first global batch
    (no run evaluates)."""
    from tpu_dp.data.cifar import ArrayDataset

    classes, size, channels = _image_shape(job)
    images, labels = datagen.make_dataset(
        job.seed, job.train_size, classes, size, channels)
    name, head = job.config["name"], job.global_batch
    return (ArrayDataset(images, labels, name, classes, synthetic=True),
            ArrayDataset(images[:head], labels[:head], name, classes,
                         synthetic=True))


def init_params(job):
    """The seed's weights, made on the device in one call."""
    return reference.init_params(job.config["model"], job.seed)


def first_gradient(opt_state, params0, optimizer: dict):
    """The first gradient as SGD got it: after step 1 the momentum buffer
    is ``g + wd * p0`` (torch's form, the buffer starting at zero)."""
    import jax
    import jax.numpy as jnp

    wd = jnp.float32(optimizer["weight_decay"])
    return jax.tree_util.tree_map(lambda b, p: b - wd * p, opt_state, params0)


def variants(job) -> dict:
    """The lower precisions `reference_readings` knows, the control first,
    and the faults this job can have: an exchange left out needs chips to
    exchange between."""
    faults = tuple(f for f in FAULTS if job.chips > 1 or f != "no_exchange")
    return {"precisions": PRECISIONS, "faults": faults}


def reference_readings(job, steps: int, precision: str = "float32",
                       fault: str | None = None) -> dict:
    """The plain reference's reading of the first ``steps`` steps, on the
    job's chips: its own weights and rows from the seed."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sharding = None
    if job.chips > 1:
        mesh = Mesh(np.asarray(jax.devices()[:job.chips]), ("data",))
        sharding = NamedSharding(mesh, P("data"))
    images, labels = datagen.device_dataset(
        job.seed, job.train_size, *_image_shape(job))
    batches = []
    for k in range(steps):
        rows = datagen.step_rows(job.program_seed, 0, job.train_size,
                                 job.global_batch, k)
        x, y = images[rows], labels[rows]
        if sharding is not None:
            x, y = jax.device_put((x, y), sharding)
        batches.append((x, y))
    del images, labels
    return reference.follow(
        job.config["model"], job.config["optimizer"], job.seed,
        job.program_seed, job.steps_per_epoch,
        "augment" in job.config["input"], batches, precision=precision,
        fault=fault, chips=job.chips, batch_sharding=sharding)


def train_flops_per_item(job) -> float:
    return work.train_flops_per_item(job.config["model"])


def least_step_seconds(job, peaks: dict) -> dict:
    return work.least_step_seconds(
        job.config["model"], job.batch_per_chip,
        job.config["precision"]["compute"], peaks["bf16_flops_per_s"],
        peaks["hbm_bytes_per_s"])
