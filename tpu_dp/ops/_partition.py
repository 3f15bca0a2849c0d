"""Shared GSPMD/shard_map plumbing for the Pallas op modules.

One copy of the custom-partitioning support code used by both
`tpu_dp.ops.conv_block` and `tpu_dp.ops.xent`: the interpret-mode request,
the batch-axis extraction from operand shardings, batch padding, the
varying-mesh-axes (vma) union for `shard_map`'s check_vma, and the guard
for per-shard interpret-mode code (Pallas interpret lowers to a grid scan
whose index scalars are vma-unvarying, which check_vma rejects — per-shard
code runs the op's identical XLA statement there).
"""

from __future__ import annotations

import contextlib
import logging

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

logger = logging.getLogger(__name__)

# Open `interpret_kernels()` requests (nesting depth). The kernels are
# compiled for the TPU unless a caller asked, on purpose, for the Pallas
# interpreter: tests, the CPU-mesh dry run, `--platform cpu` tool runs.
_interpret_requests = 0


@contextlib.contextmanager
def interpret_kernels():
    """Run the Pallas kernels traced inside this block in interpret mode.

    The request is read when a kernel is traced, so the block must cover
    the first call (the compile) of every jitted function that holds one.
    """
    global _interpret_requests
    _interpret_requests += 1
    try:
        yield
    finally:
        _interpret_requests -= 1


def interpret() -> bool:
    """True inside `interpret_kernels()`; otherwise the kernel is compiled,
    which needs a TPU — any other backend is an error, not a fallback."""
    if _interpret_requests:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernel called on backend {backend!r}: the kernels "
            "compile for the TPU only. To run one in the Pallas interpreter "
            "on purpose, call it inside tpu_dp.ops.interpret_kernels().")
    return False


def kernels_can_run() -> bool:
    """Whether a Pallas kernel traced now can run: on a TPU, or inside
    `interpret_kernels()`. What a model asks before it takes a kernel's path
    (with its own test of the shapes)."""
    return jax.default_backend() == "tpu" or bool(_interpret_requests)


def shard_map_interp(x) -> bool:
    """True when per-shard interpret-mode code must run the XLA statement."""
    return bool(_interpret_requests) and bool(jax.typeof(x).vma)


def shape_struct(shape, dtype, *operands):
    """`ShapeDtypeStruct` declaring the operands' vma union."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma_of(*operands))


def batch_axis(arg_infos):
    """The mesh-axis resource operand 0's leading (batch) dim is sharded
    over, or None.

    The partition rules built on this shard only the batch dim; when
    operand 0 arrives sharded on some *other* dim (batch unsharded), the
    rule forces full replication and GSPMD inserts an all-gather on the
    hot path — legal but almost certainly not what the caller meant, so
    it is logged rather than silent (compile-time only, once per trace).
    """
    sh = arg_infos[0].sharding
    if sh is None or not isinstance(sh, NamedSharding) or not len(sh.spec):
        return None
    if sh.spec[0] is None and any(ax is not None for ax in sh.spec[1:]):
        logger.warning(
            "Pallas op partition: operand 0 is sharded on a non-batch dim "
            "(spec %s); the batch-only partition rule will replicate it "
            "(all-gather inserted on the hot path)", sh.spec)
    return sh.spec[0]


def pad_batch(x, block):
    """Zero-pad the leading dim up to a multiple of ``block``."""
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])
    return x


def vma_of(*arrays):
    """Union of the mesh axes the arrays vary over (empty outside
    shard_map)."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))
