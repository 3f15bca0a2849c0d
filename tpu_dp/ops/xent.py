"""Fused softmax-cross-entropy Pallas TPU kernel (forward + custom VJP).

The reference's loss is `nn.CrossEntropyLoss()` (`/root/reference/
cifar_example.py:63`), lowered there to cuDNN/cuBLAS softmax+NLL kernels.
XLA already fuses the logsumexp chain well; this kernel goes one step
further and keeps the whole per-example computation — max, logsumexp,
label gather (forward) and softmax-minus-onehot scaling (backward) — in
VMEM with a single pass over the logits per direction, one (block_b, C)
tile per grid step. For CIFAR head sizes (C = 10/100, padded to the
128-lane tile) this trades a few HBM round trips of (B, C) intermediates
for none.

API: `softmax_xent(logits, labels) -> per-example loss (B,)`, differentiable
wrt logits via `jax.custom_vjp`. The kernels compile for the TPU; inside
`tpu_dp.ops.interpret_kernels()` the same code runs in the Pallas
interpreter, which is how the tests exercise it on CPU. `tpu_dp.train.step`
uses the jnp path by default; the kernel is opt-in (`use_pallas=True` /
bench) and numerically validated against the jnp path in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.custom_partitioning import custom_partitioning
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_dp.ops._partition import (
    batch_axis as _batch_axis_shared,
    interpret as _interpret,
    pad_batch as _pad_batch,
    shape_struct as _shape_struct,
    shard_map_interp as _shard_map_interp,
)

_BLOCK_B = 256  # max batch rows per grid step; (256, 128) f32 tiles fit VMEM


def _block_for(b: int) -> int:
    # Adapt the block to the (per-shard) batch so small shards don't pad to
    # 256 and compute multiples of the needed rows.
    return min(_BLOCK_B, max(8, -(-b // 8) * 8))


def _jnp_fwd(logits, labels):
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)) + m
    true_logit = jnp.take_along_axis(
        logits, labels.astype(jnp.int32)[:, None], axis=-1)
    return (lse - true_logit)[:, 0]


def _jnp_bwd(logits, labels, ct):
    logits32 = logits.astype(jnp.float32)
    m = jnp.max(logits32, axis=-1, keepdims=True)
    e = jnp.exp(logits32 - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return ((probs - onehot) * ct.astype(jnp.float32)[:, None]).astype(
        logits.dtype)


_batch_axis = _batch_axis_shared


def _fwd_kernel(logits_ref, labels_ref, loss_ref):
    logits = logits_ref[:].astype(jnp.float32)  # (B, C)
    m = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)) + m
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (classes == labels_ref[:]).astype(jnp.float32)  # labels (B, 1)
    true_logit = jnp.sum(logits * onehot, axis=-1, keepdims=True)
    loss_ref[:] = lse - true_logit  # (B, 1)


def _bwd_kernel(logits_ref, labels_ref, ct_ref, dlogits_ref):
    logits = logits_ref[:].astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (classes == labels_ref[:]).astype(jnp.float32)
    dlogits_ref[:] = ((probs - onehot) * ct_ref[:]).astype(dlogits_ref.dtype)


def _block_specs(num_classes, block):
    row_spec = pl.BlockSpec(
        (block, num_classes), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    col_spec = pl.BlockSpec(
        (block, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    return row_spec, col_spec


def _fwd_local(logits, labels):
    if _shard_map_interp(logits):
        return _jnp_fwd(logits, labels)
    b, c = logits.shape
    block = _block_for(b)
    logits_p = _pad_batch(logits, block)
    labels_p = _pad_batch(labels.astype(jnp.int32)[:, None], block)
    row_spec, col_spec = _block_specs(c, block)
    loss = pl.pallas_call(
        _fwd_kernel,
        grid=(logits_p.shape[0] // block,),
        in_specs=[row_spec, col_spec],
        out_specs=col_spec,
        out_shape=_shape_struct((logits_p.shape[0], 1), jnp.float32,
                                logits_p, labels_p),
        interpret=_interpret(),
    )(logits_p, labels_p)
    return loss[:b, 0]


def _bwd_local(logits, labels, ct):
    if _shard_map_interp(logits):
        return _jnp_bwd(logits, labels, ct)
    b, c = logits.shape
    block = _block_for(b)
    logits_p = _pad_batch(logits, block)
    labels_p = _pad_batch(labels.astype(jnp.int32)[:, None], block)
    ct_p = _pad_batch(ct.astype(jnp.float32)[:, None], block)
    row_spec, col_spec = _block_specs(c, block)
    dlogits = pl.pallas_call(
        _bwd_kernel,
        grid=(logits_p.shape[0] // block,),
        in_specs=[row_spec, col_spec, col_spec],
        out_specs=row_spec,
        out_shape=_shape_struct(logits_p.shape, logits.dtype,
                                logits_p, labels_p, ct_p),
        interpret=_interpret(),
    )(logits_p, labels_p, ct_p)
    return dlogits[:b]


def _make_cp(fn, n_args, out_spec_fn, rule):
    """Batch-shard a per-example kernel over the mesh (GSPMD would
    otherwise treat the pallas_call as opaque and replicate it —
    all-gathering every shard's logits; see conv_block.py)."""
    cp = custom_partitioning(fn)

    def infer(*cb_args):
        mesh, arg_infos, _ = cb_args[-3:]
        return out_spec_fn(mesh, _batch_axis(arg_infos))

    def part(*cb_args):
        mesh, arg_infos, _ = cb_args[-3:]
        batch = _batch_axis(arg_infos)
        row = NamedSharding(mesh, P(batch, None))
        vec = NamedSharding(mesh, P(batch))
        arg_shardings = (row, vec, vec)[:n_args]
        return mesh, fn, out_spec_fn(mesh, batch), arg_shardings

    cp.def_partition(partition=part, infer_sharding_from_operands=infer,
                     sharding_rule=rule)
    return cp


_cp_fwd = _make_cp(_fwd_local, 2,
                   lambda mesh, b: NamedSharding(mesh, P(b)),
                   "b c, b -> b")
_cp_bwd = _make_cp(_bwd_local, 3,
                   lambda mesh, b: NamedSharding(mesh, P(b, None)),
                   "b c, b, b -> b c")


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-example softmax cross-entropy, fused on TPU. Returns (B,).

    Batch-sharded under a mesh: the custom partitioning rule runs the
    kernel on each device's shard of the rows."""
    return _cp_fwd(logits, labels)


def _fwd_rule(logits, labels):
    return _cp_fwd(logits, labels), (logits, labels)


def _bwd_rule(residuals, ct):
    logits, labels = residuals
    return _cp_bwd(logits, labels, ct), None


softmax_xent.defvjp(_fwd_rule, _bwd_rule)


def mean_softmax_xent(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    weight: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(Weighted) mean loss via the fused kernel — drop-in for
    `tpu_dp.train.step.cross_entropy_loss`."""
    per_example = softmax_xent(logits, labels)
    if weight is None:
        return jnp.mean(per_example)
    return jnp.sum(per_example * weight) / jnp.maximum(jnp.sum(weight), 1.0)
