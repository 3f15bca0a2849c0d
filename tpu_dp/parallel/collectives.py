"""Collective helpers over the named mesh axis.

The collective *primitives* parity with the reference requires are
allreduce(mean/sum) and barrier (SURVEY.md §5 "Distributed communication
backend"): NCCL allreduce-mean backs DDP's gradient hooks
(`cifar_example_ddp.py:83`) and allreduce-sum backs torchmetrics' state sync
(`cifar_example_ddp.py:124`). On TPU these lower to XLA all-reduces over ICI;
inside `shard_map` they are `lax.pmean`/`lax.psum` on the ``data`` axis, and
under plain `jit` with sharding annotations GSPMD inserts them automatically.
A host-side CPU ring-allreduce fallback (C++, `tpu_dp.ops.native`) backs the
same semantics for host-only coordination outside any compiled program.

The sharded weight-update path (`train.update_sharding=sharded`; Xu et al.,
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training", PAPERS.md) decomposes the gradient all-reduce into its two ring
halves and moves the optimizer in between: ``psum_scatter`` (each replica
receives the *sum* of one 1/N shard of every gradient leaf), a per-shard
update, then ``all_gather`` of the updated parameters. The wrappers here own
the one non-trivial piece of that decomposition: flattening + zero-padding
every leaf to a multiple of the axis size, so leaves whose element counts do
not divide the mesh (CIFAR `Net`'s f32[5,5,3,6] on 8 chips) shard exactly
like the rest, and un-padding on the gather side.

The wire format of both ring halves is pluggable (`tpu_dp.parallel.quant`):
``psum_scatter(dtype=bf16)`` casts the payload (PR 4's knob, 2x fewer
bytes), and `psum_scatter_quant` is the blockwise-scaled **int8** wire
(EQuARX, arXiv:2506.17615; `train.collective_dtype=int8`) — quantize once
before the exchange, ONE int8 all-to-all (+f32 scales) instead of the f32
reduce-scatter, dequantize-and-sum once after, with per-sender
error-feedback residuals so rounding bias cannot accumulate. This module
owns every raw collective (the dplint DP103 choke point); the codec math
lives in `quant.py`.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
# The Varying -> Invariant all-gather. `lax.all_gather` types its result
# device-varying, which cannot leave a replication-checked `shard_map` under
# `out_specs=P()` nor ride a `lax.scan` carry that entered invariant; this
# primitive lowers to the same HLO all-gather and is typed replicated by
# construction. JAX 0.9.0 ships it beside the public one but does not
# re-export it under `jax.lax`.
from jax._src.lax.parallel import all_gather_invariant

from tpu_dp.parallel.dist import DATA_AXIS


def pmean(tree: Any, axis_name: str = DATA_AXIS) -> Any:
    """All-reduce-mean a pytree across the mesh axis (inside shard_map/pmap).

    The TPU-native form of DDP's gradient averaging: the reference's C++
    `Reducer` fires NCCL allreduces from autograd hooks during backward
    (`cifar_example_ddp.py:83`); here the mean is one more op XLA schedules
    and fuses into the compiled train step.
    """
    return jax.tree_util.tree_map(lambda x: lax.pmean(x, axis_name), tree)


def psum(tree: Any, axis_name: str = DATA_AXIS) -> Any:
    """All-reduce-sum a pytree across the mesh axis (inside shard_map/pmap).

    Backs metric state sync — the equivalent of
    `torchmetrics.Accuracy(dist_sync_on_step=True)`'s per-update allreduce
    (`cifar_example_ddp.py:124,133`).
    """
    return jax.tree_util.tree_map(lambda x: lax.psum(x, axis_name), tree)


def pmax(tree: Any, axis_name: str = DATA_AXIS) -> Any:
    """All-reduce-max a pytree across the mesh axis (inside shard_map):
    how the sharded update agrees on a scalar optimizer slot of which
    every replica keeps a copy (`train.optim.ShardedUpdate`)."""
    return jax.tree_util.tree_map(lambda x: lax.pmax(x, axis_name), tree)


def padded_size(n: int, world: int) -> int:
    """``n`` rounded up to a multiple of ``world`` (the flat shard layout)."""
    return n + (-n) % world


def shard_size(n: int, world: int) -> int:
    """Per-replica elements of a flat-sharded leaf with ``n`` elements."""
    return padded_size(n, world) // world


def _flat_padded(x: jnp.ndarray, world: int) -> jnp.ndarray:
    """Leaf flattened to 1-D and zero-padded to a multiple of ``world``."""
    flat = x.reshape(-1)
    pad = (-flat.size) % world
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def psum_scatter(
    tree: Any,
    axis_name: str = DATA_AXIS,
    *,
    world: int,
    mean: bool = False,
    dtype: Any = None,
) -> Any:
    """Reduce-scatter a pytree: each replica gets the sum of its 1/world shard.

    The first ring half of the gradient all-reduce, with the second half
    (`all_gather`) deferred until after the per-shard optimizer update — the
    cross-replica-sharded weight update of Xu et al. (PAPERS.md). Every leaf
    is flattened and zero-padded to a multiple of ``world`` (`_flat_padded`),
    so the output leaf is 1-D of `shard_size(leaf.size, world)` elements.
    ``mean=True`` divides by ``world`` (DDP gradient averaging). ``dtype``
    optionally casts the payload *before* the collective and back after —
    the EQuARX-style compressed-collective knob (`train.collective_dtype`):
    half the bytes on the wire for bf16, at bf16 rounding cost.
    """

    def scatter(x):
        out_dtype = x.dtype
        if dtype is not None:
            x = x.astype(dtype)
        shard = lax.psum_scatter(
            _flat_padded(x, world), axis_name, scatter_dimension=0, tiled=True
        ).astype(out_dtype)
        if mean:
            # Divide in the output dtype (after any compressed-wire cast):
            # matches pmean's psum-then-divide ordering, so the f32 path is
            # bitwise-identical to the replicated update.
            shard = shard / world
        return shard

    return jax.tree_util.tree_map(scatter, tree)


def psum_scatter_quant(
    tree: Any,
    residuals: dict,
    axis_name: str = DATA_AXIS,
    *,
    world: int,
    mean: bool = False,
    block_size: int | None = None,
    error_feedback: bool = True,
) -> tuple[Any, dict, dict]:
    """Reduce-scatter with a blockwise-scaled **int8 wire format**.

    The EQuARX-style compressed collective (`train.collective_dtype=int8`;
    `tpu_dp.parallel.quant` holds the codec, this wrapper owns the wire
    schedule — the DP103 choke-point discipline). Per quantizable leaf:

    1. **error feedback**: this replica's pending rounding error
       (``residuals``, per-replica row of the flat-sharded residual state)
       is added to the local flat-padded gradient;
    2. **quantize once** (`quant.quantize_blocks`): int8 payload + one f32
       scale per ``block_size`` elements; the new residual is the exact
       rounding error of what goes on the wire;
    3. **exchange**: ONE int8 `all_to_all` over the data axis (plus the
       f32 scales riding alongside) — the same traffic pattern as a
       reduce-scatter's scatter phase, at ~1/4 the bytes. XLA cannot sum
       int8 payloads under per-replica scales, so the reduction is
       explicit: each replica dequantizes the ``world`` chunks it received
       and sums them in f32 — *dequantize once*, per Xu et al.'s schedule;
    4. the summed 1/world shard is trimmed to `psum_scatter`'s layout
       (``shard_size(n, world)`` elements), ``mean=True`` divides by
       ``world`` after the reduce, exactly like the f32 path.

    Leaves too small to block-align (`quant.leaf_quantizes` False — biases,
    norm scales) ride the plain f32 `psum_scatter`; they carry no residual.

    Returns ``(shards, new_residuals, stats)``: shards in `psum_scatter`'s
    flat layout, the updated residual pytree (same structure as
    ``residuals``), and ``stats`` with **rank-local** s32 ``overflow`` /
    ``clip`` block counts (`quant.block_stats`) — the caller reduces them
    (the step's reduce hook psums, like the other metrics).
    ``error_feedback=False`` is the ablation seam: residuals are neither
    read nor updated (fed in as zeros, emitted unchanged), isolating what
    the residual path buys (tests/test_quant.py proves it is measurably
    worse without).
    """
    from tpu_dp.parallel import quant

    if block_size is None:
        block_size = quant.DEFAULT_BLOCK_SIZE
    overflow = jnp.zeros((), jnp.int32)
    clip = jnp.zeros((), jnp.int32)
    new_residuals = dict(residuals)

    def scatter_leaf(path, x):
        nonlocal overflow, clip
        key = quant.leaf_key(path)
        if key not in residuals:
            # Small-leaf fallback: the uncompressed scatter.
            return psum_scatter(
                x, axis_name, world=world, mean=mean
            )
        out_dtype = x.dtype
        res = residuals[key].reshape(-1)  # per-replica row -> flat [qpad]
        qpad = res.shape[0]
        # Layout discipline: the reduced shard must land in EXACTLY
        # `psum_scatter`'s flat layout (replica i owns elements
        # [i*pchunk, (i+1)*pchunk) of the world-padded leaf) — the sharded
        # optimizer pairs it positionally with `shard_slice`'s param
        # shards. So the block-alignment padding goes at the tail of EACH
        # chunk, never the tail of the flat vector: chunk boundaries stay
        # where the f32 path puts them, and every chunk is a whole number
        # of blocks (world * cpad == quant_padded_size, both f32-zero in
        # the pad region).
        pchunk = shard_size(x.size, world)
        cpad = qpad // world
        rows = _flat_padded(x, world).astype(jnp.float32).reshape(
            world, pchunk
        )
        rows = jnp.pad(rows, ((0, 0), (0, cpad - pchunk)))
        eff = rows.reshape(-1)
        if error_feedback:
            eff = eff + res
        q, scales = quant.quantize_blocks(eff, block_size)
        if error_feedback:
            deq_local = quant.dequantize_blocks(q, scales, block_size)
            new_residuals[key] = (eff - deq_local).reshape(1, qpad)
        ov, cl = quant.block_stats(q, scales)
        overflow, clip = overflow + ov, clip + cl
        qx = lax.all_to_all(
            q.reshape(world, cpad), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
        )
        sx = lax.all_to_all(
            scales.reshape(world, cpad // block_size), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
        )
        deq = (qx.reshape(world, cpad // block_size, block_size)
               .astype(jnp.float32) * sx[..., None])
        shard = jnp.sum(deq, axis=0).reshape(cpad)
        shard = shard[:pchunk].astype(out_dtype)
        if mean:
            shard = shard / world
        return shard

    shards = jax.tree_util.tree_map_with_path(scatter_leaf, tree)
    return shards, new_residuals, {"overflow": overflow, "clip": clip}


def _issue_barrier(payload, token):
    """Keep a bucket's exchange separate with `jax.lax.optimization_barrier`.

    The scheduling hint of the bucketed schedule (docs/PERF.md "Overlapped
    collectives"): each bucket's wire payload is coupled to a scalar token
    carried from the PREVIOUS bucket's barrier, so the optimizer passes
    (CSE, fusion, collective combining) cannot glob the per-bucket payloads
    back into one monolithic exchange across the barrier. The chain
    sequences the *barriers* in plan order (reverse production: bucket 0's
    payload passes first, while backward still computes the earlier
    layers); it does NOT order the collectives behind them. The token rides
    the barrier's *input* side only — bucket i+1's issue never waits on
    bucket i's *completion* — so XLA's scheduler stays free to keep several
    exchanges in flight under the remaining backward compute, and equally
    free to place two independent exchanges either way round: the compiled
    order usually follows the plan and is not guaranteed to (XLA of JAX
    0.9.0 on the CPU mesh swaps Net's last two small buckets at 0.01 MB).
    """
    return lax.optimization_barrier((payload, token))


def _bucket_rows(leaves, world: int, wire_dtype) -> jnp.ndarray:
    """Concatenate a bucket's leaves into the world-chunked wire layout.

    Each leaf is flat-padded to a multiple of ``world`` and viewed as
    [world, pchunk]; concatenating along dim 1 keeps chunk c of the result
    equal to the concatenation of every leaf's chunk c — so after a tiled
    reduce-scatter of the flattened rows, replica i's row splits back into
    exactly the per-leaf shards `shard_slice` pairs with the param shards
    (the layout contract of the sharded optimizer, unchanged by bucketing).
    """
    return jnp.concatenate(
        [_flat_padded(x, world).astype(wire_dtype).reshape(world, -1)
         for x in leaves],
        axis=1,
    )


def _split_bucket_shard(shard, bucket, leaves, world: int, mean: bool,
                        out: dict) -> None:
    """Split one bucket's reduced row back into per-leaf flat shards."""
    off = 0
    for key, x in zip(bucket.keys, leaves):
        pchunk = shard_size(x.size, world)
        seg = shard[off:off + pchunk].astype(x.dtype)
        if mean:
            seg = seg / world
        out[key] = seg
        off += pchunk


def psum_scatter_bucketed(
    tree: Any,
    axis_name: str = DATA_AXIS,
    *,
    world: int,
    mean: bool = False,
    dtype: Any = None,
    bucket_bytes: int,
) -> Any:
    """`psum_scatter` issued as K size-targeted bucket reductions.

    The overlap schedule (`train.bucket_mb`, docs/PERF.md "Overlapped
    collectives"): leaves are planned into buckets in reverse production
    order (`bucketing.plan_buckets` — the single source of truth shared
    with the analyzer and the wire report), each bucket's leaves are
    concatenated in the world-chunked layout (`_bucket_rows`) and reduced
    by ONE tiled reduce-scatter, with `optimization_barrier` token
    chaining keeping the K exchanges separate so XLA can hide each
    bucket's wire time under the remaining backward compute. Per-leaf output layout is
    identical to `psum_scatter`'s (same flat shards, same padding), and
    the per-element reduction arithmetic is unchanged — on the same
    backend the bucketed f32 result is bitwise the unbucketed one
    (pinned by tests/test_overlap.py; the documented contract is the
    reduction-order tolerance of docs/PERF.md in case a backend's
    combined kernel sums differently).

    ``dtype`` compresses the wire exactly like `psum_scatter` (bf16 cast
    per bucket payload); leaves of mixed dtypes reduce in f32 (the wire
    layout concatenates, so a common accumulation dtype is required —
    gradients are f32 everywhere in this repo).
    """
    from tpu_dp.parallel import bucketing, quant

    leaves_wp = jax.tree_util.tree_leaves_with_path(tree)
    by_key = {quant.leaf_key(p): x for p, x in leaves_wp}
    plan = bucketing.plan_for_tree(tree, world, bucket_bytes)
    wire_dt = dtype if dtype is not None else jnp.float32
    out: dict = {}
    token = jnp.zeros((), jnp.float32)
    for bucket in plan:
        leaves = [by_key[k] for k in bucket.keys]
        rows = _bucket_rows(leaves, world, wire_dt)
        rows, token = _issue_barrier(rows, token)
        shard = lax.psum_scatter(
            rows.reshape(-1), axis_name, scatter_dimension=0, tiled=True
        ).astype(jnp.float32)
        _split_bucket_shard(shard, bucket, leaves, world, mean, out)
    return jax.tree_util.tree_map_with_path(
        lambda p, x: out[quant.leaf_key(p)], tree
    )


def psum_scatter_quant_bucketed(
    tree: Any,
    residuals: dict,
    axis_name: str = DATA_AXIS,
    *,
    world: int,
    mean: bool = False,
    block_size: int | None = None,
    error_feedback: bool = True,
    bucket_bytes: int,
) -> tuple[Any, dict, dict]:
    """`psum_scatter_quant` issued as K bucket exchanges.

    Same codec math as the monolithic path — quantize once, ONE int8
    all-to-all + f32 scales, dequantize-and-sum once — applied per
    *bucket*: each quantizing bucket's leaves concatenate into the
    world-chunked layout, block-pad at the tail of each chunk, and carry
    ONE error-feedback residual keyed by the bucket's composition key
    (`bucketing.GradBucket.key` — self-describing, so checkpoint restore
    can reshard pending corrections bucket-exact across bucket-size or
    world changes, `checkpoint._reconcile_residuals`). Buckets below the
    quantization threshold ride the plain f32 reduce-scatter and carry
    no residual — note the threshold is per bucket, so the small leaves
    (biases, norm scales) that always took the f32 fallback alone now
    compress inside their bucket. Issue order and anti-combining hints
    as in `psum_scatter_bucketed`.
    """
    from tpu_dp.parallel import bucketing, quant

    if block_size is None:
        block_size = quant.DEFAULT_BLOCK_SIZE
    leaves_wp = jax.tree_util.tree_leaves_with_path(tree)
    by_key = {quant.leaf_key(p): x for p, x in leaves_wp}
    plan = bucketing.plan_for_tree(tree, world, bucket_bytes,
                                   block_size=block_size, int8=True)
    overflow = jnp.zeros((), jnp.int32)
    clip = jnp.zeros((), jnp.int32)
    new_residuals = dict(residuals)
    out: dict = {}
    token = jnp.zeros((), jnp.float32)
    for bucket in plan:
        leaves = [by_key[k] for k in bucket.keys]
        rows = _bucket_rows(leaves, world, jnp.float32)
        rows, token = _issue_barrier(rows, token)
        if not bucket.quantizes:
            shard = lax.psum_scatter(
                rows.reshape(-1), axis_name, scatter_dimension=0, tiled=True
            )
            _split_bucket_shard(shard, bucket, leaves, world, mean, out)
            continue
        bkey = bucket.key
        if bkey not in residuals:
            raise ValueError(
                f"bucketed int8 exchange found no residual for bucket "
                f"{bkey!r} — the residual dict's layout does not match "
                f"the bucket plan (initialize with quant.init_residuals("
                f"..., bucket_bytes=...) at the SAME bucket_bytes/"
                f"block_size, or restore through the Trainer so "
                f"checkpoint._reconcile_residuals reshards it)"
            )
        res = residuals[bkey].reshape(-1)  # per-replica row -> flat [qpad]
        qpad = res.shape[0]
        schunk = rows.shape[1]             # Σ per-leaf pchunk
        cpad = qpad // world               # block-aligned chunk length
        rows = jnp.pad(rows, ((0, 0), (0, cpad - schunk)))
        eff = rows.reshape(-1)
        if error_feedback:
            eff = eff + res
        q, scales = quant.quantize_blocks(eff, block_size)
        if error_feedback:
            deq_local = quant.dequantize_blocks(q, scales, block_size)
            new_residuals[bkey] = (eff - deq_local).reshape(1, qpad)
        ov, cl = quant.block_stats(q, scales)
        overflow, clip = overflow + ov, clip + cl
        qx = lax.all_to_all(
            q.reshape(world, cpad), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
        )
        sx = lax.all_to_all(
            scales.reshape(world, cpad // block_size), axis_name,
            split_axis=0, concat_axis=0, tiled=True,
        )
        deq = (qx.reshape(world, cpad // block_size, block_size)
               .astype(jnp.float32) * sx[..., None])
        shard = jnp.sum(deq, axis=0).reshape(cpad)[:schunk]
        _split_bucket_shard(shard, bucket, leaves, world, mean, out)
    shards = jax.tree_util.tree_map_with_path(
        lambda p, x: out[quant.leaf_key(p)], tree
    )
    return shards, new_residuals, {"overflow": overflow, "clip": clip}


def shard_slice(tree: Any, axis_name: str = DATA_AXIS, *, world: int) -> Any:
    """This replica's 1/world flat shard of every (replicated) leaf.

    Pure local slicing — no communication: replica i of the flattened,
    zero-padded leaf takes elements [i*chunk, (i+1)*chunk). The layout
    twin of `psum_scatter`'s output, used to pair parameter shards with
    reduce-scattered gradient shards for the per-shard optimizer update.
    """

    def slice_leaf(x):
        flat = _flat_padded(x, world)
        chunk = flat.size // world
        idx = lax.axis_index(axis_name)
        return lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)

    return jax.tree_util.tree_map(slice_leaf, tree)


def all_gather(shards: Any, like: Any, axis_name: str = DATA_AXIS,
               *, codec: Any = None) -> Any:
    """Reassemble flat 1/world shards into leaves shaped like ``like``.

    The second ring half of the decomposed all-reduce: concatenate every
    replica's shard (tiled all-gather), drop the zero padding, restore the
    original shape/dtype. `all_gather(psum_scatter(t, mean=True), t)` is
    numerically `pmean(t)` — the parity test asserts it bitwise for f32.

    ``codec`` compresses the gather's wire format the same way the scatter
    side compresses (`quant.CastCodec` casts, `quant.Int8BlockCodec`
    quantizes each shard blockwise and dequantizes after the exchange —
    stateless here: there is no residual on the gather side). The shipped
    train path deliberately does NOT enable it: the gathered payload is
    the *updated parameters*, so wire rounding there would quantize the
    weights themselves every step rather than one gradient contribution —
    a different accuracy contract than the EQuARX gradient compression
    this PR lands (documented in docs/PERF.md; the knob exists so the
    trade can be measured).
    """
    from tpu_dp.parallel import quant

    def gather(shard, ref):
        full = all_gather_invariant(shard, axis_name, axis=0, tiled=True)
        return full[: ref.size].reshape(ref.shape).astype(ref.dtype)

    if codec is None:
        return jax.tree_util.tree_map(gather, shards, like)

    if isinstance(codec, quant.CastCodec):
        def gather_cast(shard, ref):
            full = all_gather_invariant(
                shard.astype(codec.dtype), axis_name, axis=0, tiled=True
            )
            return full[: ref.size].reshape(ref.shape).astype(ref.dtype)

        return jax.tree_util.tree_map(gather_cast, shards, like)

    if isinstance(codec, quant.Int8BlockCodec):
        block = codec.block_size

        def gather_q(shard, ref):
            flat = shard.reshape(-1).astype(jnp.float32)
            pad = (-flat.size) % block
            padded = jnp.pad(flat, (0, pad))
            q, scales = quant.quantize_blocks(padded, block)
            qx = all_gather_invariant(q, axis_name, axis=0, tiled=True)
            sx = all_gather_invariant(scales, axis_name, axis=0, tiled=True)
            full = quant.dequantize_blocks(qx, sx, block)
            # Drop each replica's block padding, then the shard padding.
            full = full.reshape(-1, flat.size + pad)[:, : flat.size]
            return full.reshape(-1)[: ref.size].reshape(ref.shape).astype(
                ref.dtype
            )

        return jax.tree_util.tree_map(gather_q, shards, like)

    raise TypeError(f"unknown wire codec {codec!r}")
