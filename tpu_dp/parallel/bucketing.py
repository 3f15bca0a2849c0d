"""Gradient-bucket planning for overlap-scheduled collectives.

The reference DDP's entire perf story is that the gradient allreduce hides
under backward compute: its C++ ``Reducer`` chops the parameter set into
~25 MB buckets and fires one NCCL allreduce per bucket from autograd hooks,
as soon as the bucket's gradients are produced. Our explicit sharded update
historically waited for the FULL gradient pytree and issued one monolithic
reduce-scatter — every wire byte exposed latency.

``train.bucket_mb`` brings the bucketed schedule to the explicit-collectives
path: this module is the ONE source of truth for how gradient leaves map to
buckets. The same plan drives

- the wire schedule (`collectives.psum_scatter_bucketed` /
  `psum_scatter_quant_bucketed` — one collective per bucket, kept separate
  by `jax.lax.optimization_barrier` token chaining),
- the error-feedback residual layout (`quant.init_residuals` — one residual
  per *quantizing bucket*, keyed by the bucket's self-describing
  composition key),
- the byte accounting (`quant.wire_report(bucket_bytes=...)`),
- and the analyzer's legality check (dplint DP301 verifies the compiled
  module carries exactly K bucketed reductions covering the union of
  gradient leaves exactly once; DP304 fingerprints the layout).

Planning rules
--------------

Leaves are assigned in **reverse pytree order** — backward produces
gradients in reverse forward order, so the first-closed bucket holds the
LAST layers' gradients and its collective can issue while backward still
computes the earlier layers. A bucket closes when its accumulated f32
payload (world-padded) reaches ``bucket_bytes``; the first leaf always
enters the current bucket, so a single giant leaf becomes its own bucket
rather than an error. ``bucket_bytes <= 0`` means bucketing is off (the
historical single-reduction schedule).

With the int8 wire codec, a bucket *quantizes* when its total element
count clears the same threshold a single leaf had to
(`quant.leaf_quantizes`: ``>= world * block_size``) — concatenation is
what finally lets the small leaves (biases, norm scales) ride the
compressed wire instead of the f32 fallback. Sub-threshold buckets keep
the plain f32 reduce-scatter and carry no residual.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

#: Composition-key separator: a bucket's residual/report key is its leaf
#: keys joined in issue order. Leaf keys are '/'-joined flax paths, which
#: never contain '+', so the composition parse is unambiguous — and a
#: single-leaf bucket's key degenerates to the plain leaf key, keeping
#: unbucketed residual checkpoints a special case of the same grammar.
KEY_SEP = "+"


@dataclasses.dataclass(frozen=True)
class GradBucket:
    """One bucket of the gradient-collective plan (static metadata only)."""

    index: int                 # issue order (0 = first produced in backward)
    keys: tuple[str, ...]      # leaf keys (quant.leaf_key), issue order
    sizes: tuple[int, ...]     # true (unpadded) element counts per leaf
    quantizes: bool = False    # rides the int8 wire (codec on + threshold)

    @property
    def key(self) -> str:
        """Self-describing composition key (residual dict / report key)."""
        return KEY_SEP.join(self.keys)

    @property
    def elements(self) -> int:
        return sum(self.sizes)

    def padded_elements(self, world: int) -> int:
        """World-padded element count of the concatenated f32 payload."""
        from tpu_dp.parallel.collectives import padded_size

        return sum(padded_size(n, world) for n in self.sizes)

    def shard_elements(self, world: int) -> int:
        """One replica's chunk of the concatenated payload (Σ per-leaf
        `shard_size` — the pre-block-padding chunk length)."""
        from tpu_dp.parallel.collectives import shard_size

        return sum(shard_size(n, world) for n in self.sizes)

    def quant_padded(self, world: int, block_size: int) -> int:
        """Flat length of the bucket's block-padded int8 wire layout (the
        residual leaf's qpad; every 1/world chunk a whole number of
        blocks). The ONE definition every consumer derives — the residual
        state, the wire report, and DP301's exchange expectations."""
        from tpu_dp.parallel.quant import quant_padded_size

        return quant_padded_size(self.shard_elements(world) * world,
                                 world, block_size)


def composition(key: str) -> list[str]:
    """Leaf keys of a residual/bucket key (single-leaf keys included)."""
    return key.split(KEY_SEP)


def parse_bucket_mb(bucket_mb: Any) -> int:
    """``train.bucket_mb`` -> target bucket payload bytes (0 = off)."""
    mb = float(bucket_mb or 0.0)
    if mb < 0:
        raise ValueError(f"train.bucket_mb must be >= 0, got {bucket_mb!r}")
    return int(mb * 2**20)


def plan_buckets(
    leaves: Sequence[tuple[str, int]],
    world: int,
    bucket_bytes: int,
    *,
    block_size: int | None = None,
    int8: bool = False,
) -> list[GradBucket]:
    """Partition ``leaves`` (ordered ``(key, element_count)`` pairs, pytree
    order) into size-targeted buckets in reverse production order.

    Deterministic in the leaf order + sizes alone — every consumer
    (wire schedule, residual init, wire report, analyzer, checkpoint
    reshard) derives the identical plan, which is the invariant the
    exactly-once proof and the bucket-exact residual reshard rest on.
    """
    from tpu_dp.parallel.collectives import padded_size
    from tpu_dp.parallel.quant import DEFAULT_BLOCK_SIZE, leaf_quantizes

    if bucket_bytes <= 0:
        raise ValueError("plan_buckets needs bucket_bytes > 0 "
                         "(bucketing off has no plan)")
    block = DEFAULT_BLOCK_SIZE if block_size is None else int(block_size)
    buckets: list[GradBucket] = []
    cur_keys: list[str] = []
    cur_sizes: list[int] = []
    cur_bytes = 0

    def close() -> None:
        nonlocal cur_keys, cur_sizes, cur_bytes
        if not cur_keys:
            return
        total = sum(cur_sizes)
        buckets.append(GradBucket(
            index=len(buckets),
            keys=tuple(cur_keys),
            sizes=tuple(cur_sizes),
            quantizes=bool(int8) and leaf_quantizes(total, world, block),
        ))
        cur_keys, cur_sizes, cur_bytes = [], [], 0

    for key, n in reversed(list(leaves)):
        cur_keys.append(key)
        cur_sizes.append(int(n))
        cur_bytes += padded_size(int(n), world) * 4
        if cur_bytes >= bucket_bytes:
            close()
    close()
    return buckets


def plan_for_tree(tree: Any, world: int, bucket_bytes: int, *,
                  block_size: int | None = None,
                  int8: bool = False) -> list[GradBucket]:
    """`plan_buckets` over a (gradient/params) pytree's leaves."""
    import jax

    from tpu_dp.parallel.quant import leaf_key

    leaves = [(leaf_key(p), int(x.size))
              for p, x in jax.tree_util.tree_leaves_with_path(tree)]
    return plan_buckets(leaves, world, bucket_bytes,
                        block_size=block_size, int8=int8)


def plan_summary(plan: Sequence[GradBucket], world: int,
                 block_size: int | None = None) -> list[dict]:
    """JSON-able per-bucket layout (the DP304 fingerprint's ``buckets``
    field and the BENCH overlap block's per-config record)."""
    from tpu_dp.parallel.quant import DEFAULT_BLOCK_SIZE

    block = DEFAULT_BLOCK_SIZE if block_size is None else int(block_size)
    out = []
    for b in plan:
        entry = {
            "index": b.index,
            "leaves": len(b.keys),
            "elements": b.elements,
            "padded_elements": b.padded_elements(world),
            "shard_elements": b.shard_elements(world),
            "wire": "int8" if b.quantizes else "f32",
        }
        if b.quantizes:
            entry["quant_padded_elements"] = b.quant_padded(world, block)
        out.append(entry)
    return out
