"""Sharding specs and host→device batch placement.

Replaces the reference's device-placement layer: `torch.cuda.set_device`
(`cifar_example_ddp.py:53`), `.to(args.gpu)` of model and batches
(`cifar_example_ddp.py:82,97-98`). On TPU, placement is a sharding
annotation: parameters are *replicated* over the ``data`` axis (what DDP's
wrap-time broadcast achieves, `cifar_example_ddp.py:83`) and batches are
*sharded* along their leading dimension (what `DistributedSampler` +
per-rank DataLoader achieve, `cifar_example_ddp.py:70-71`).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dp.parallel.dist import DATA_AXIS


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-dim sharding over the ``data`` axis for a batch array."""
    return NamedSharding(mesh, P(DATA_AXIS))


def scan_batch_sharding(mesh: Mesh, prefix_dims: int = 1) -> NamedSharding:
    """Sharding for batches with ``prefix_dims`` leading scan axes
    (microbatches under gradient accumulation, step windows under
    `make_train_step`'s scanned feeds, or both at once — scan-of-scan): scan dims
    replicated, batch dim sharded over ``data``."""
    return NamedSharding(mesh, P(*([None] * prefix_dims), DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (parameters, opt state, scalars)."""
    return NamedSharding(mesh, P())


def shard_batch(
    batch: Any, mesh: Mesh, spec: P | NamedSharding | None = None
) -> Any:
    """Place a host batch pytree onto the mesh, sharded on dim 0.

    The host→device copy boundary of the reference's hot loop
    (`cifar_example_ddp.py:97-98`), hoisted out of the compiled step. In
    multi-process runs each process holds only its local shard of the global
    batch; `jax.make_array_from_process_local_data` assembles the logical
    global array from per-process slices. ``spec`` overrides the default
    leading-dim partitioning (e.g. ``P(None, 'data')`` for
    gradient-accumulation batches with a scan axis in front).
    """
    if spec is None:
        sharding = batch_sharding(mesh)
    elif isinstance(spec, NamedSharding):
        sharding = spec
    else:
        sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, x), batch
        )
    return jax.device_put(batch, sharding)
