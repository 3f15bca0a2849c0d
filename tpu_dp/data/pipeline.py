"""Batching + prefetch-to-device: the framework's input feed.

Replaces the reference's `DataLoader(batch_size=4, num_workers=2)` +
`DistributedSampler` pair (`/root/reference/cifar_example.py:46-52`,
`/root/reference/cifar_example_ddp.py:70-76`) with a TPU-shaped pipeline:

- each *process* draws its disjoint shard of the epoch permutation
  (`ShardedSampler`, the `DistributedSampler` contract) and gathers
  ``batch_size`` examples per step, so the logical global batch is
  ``batch_size × process_count`` — the reference's per-rank batch-4
  accounting (SURVEY.md §2A);
- batches ship as **uint8** and are normalized on device inside the compiled
  step (4× less host→HBM traffic than float32); the device placement shards
  the leading dim over the mesh's ``data`` axis
  (`jax.make_array_from_process_local_data` across processes);
- a background thread prefetches ahead of the consumer — the reference's
  `num_workers=2` overlap, done with device double-buffering instead of
  forked workers + pinned-memory IPC (SURVEY.md §2B "DataLoader workers").
  Device placement is **genuinely asynchronous**: `jax.device_put` is
  dispatch-only (the h2d copy runs in the background), the pipeline never
  blocks on a placed batch (no per-batch host sync — unless
  ``sync_placement`` opts into the old world for measurement), and a
  two-slot double buffer (`_double_buffered`) keeps the NEXT batch's
  placement in flight while the consumer still computes on the current
  one — so the copy overlaps the step even with the prefetch thread
  disabled, and the consumer's ``data_wait`` span shrinks to the host
  gather alone (proven by tests/test_overlap.py);
- the final partial batch (eval, ``drop_remainder=False``) is padded by
  wraparound to keep shapes static for XLA, with a float ``weight`` mask so
  the compiled eval step excludes the batch-level pad from counts/loss.
  (Shard-level padding is a different matter: when the dataset size is not
  divisible by the process count, `ShardedSampler` duplicates a few examples
  so every process runs the same step count — exactly the
  `DistributedSampler` + torchmetrics semantics of the reference
  (`cifar_example_ddp.py:75,124`), where those duplicates are counted too;
  single-process eval is exact);
- with ``accum_steps > 1``, ``accum_steps`` consecutive microbatches are
  stacked on a leading scan axis (replicated; the microbatch dim is the
  sharded one) for the gradient-accumulation train step.
"""

from __future__ import annotations

import queue
import threading

import jax
import numpy as np
from jax.sharding import Mesh

from tpu_dp.data.cifar import ArrayDataset
from tpu_dp.data.sampler import ShardedSampler
from tpu_dp.parallel.sharding import scan_batch_sharding, shard_batch

_END = object()


class DataPipeline:
    """Iterable over device-placed, mesh-sharded batches of one dataset."""

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        mesh: Mesh,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        prefetch: int = 2,
        accum_steps: int = 1,
        sampler=None,
        sync_placement: bool = False,
    ):
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.mesh = mesh
        self.drop_remainder = drop_remainder
        self.prefetch = int(prefetch)
        self.accum_steps = int(accum_steps)
        # Per-batch host sync after placement (`data.sync_placement`):
        # the measurement escape hatch; off = the async double-buffered
        # default (module docstring).
        self.sync_placement = bool(sync_placement)
        if self.batch_size * jax.process_count() % mesh.devices.size:
            raise ValueError(
                f"global batch {self.batch_size * jax.process_count()} not "
                f"divisible by mesh size {mesh.devices.size}"
            )
        if self.accum_steps > 1 and not drop_remainder:
            # The accumulation train step assumes full microbatches (it
            # carries no weight mask); a wraparound-padded final stack would
            # silently give duplicated examples full gradient weight.
            raise ValueError("accum_steps > 1 requires drop_remainder=True")
        # An injected sampler overrides the epoch-permutation default: the
        # elastic-regroup path feeds an `ElasticTailSampler` carrying the
        # re-split remainder of an interrupted epoch
        # (`tpu_dp.data.sampler.elastic_resplit`) — same iteration
        # machinery, explicit index stream.
        self.sampler = sampler if sampler is not None else ShardedSampler(
            len(dataset),
            num_shards=jax.process_count(),
            shard_id=jax.process_index(),
            shuffle=shuffle,
            seed=seed,
        )

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    def __len__(self) -> int:
        """Steps per epoch (optimizer updates, not microbatches)."""
        per_step = self.batch_size * self.accum_steps
        shard = len(self.sampler)
        if self.drop_remainder:
            return shard // per_step
        return -(-shard // per_step)  # ceil

    def _host_batches(self, skip_steps: int = 0):
        """Yield host-side numpy batches for this process's shard.

        ``skip_steps`` fast-forwards past the epoch's first N optimizer
        steps without touching the data arrays — the resume path after a
        mid-epoch snapshot (no batch replayed, none skipped: step ``s``
        always draws ``idx[s*per_step:(s+1)*per_step]`` regardless of
        where iteration starts).
        """
        arrays = self.dataset.arrays
        idx = self.sampler.shard_indices()
        per_step = self.batch_size * self.accum_steps
        steps = len(self)
        for s in range(int(skip_steps), steps):
            take = idx[s * per_step : (s + 1) * per_step]
            weight = None
            if len(take) < per_step:
                # Pad-by-wraparound for a static shape; the weight mask
                # zeroes the pad out of the eval counts/loss. np.resize
                # tiles the shard if the pad exceeds its length.
                pad = per_step - len(take)
                weight = np.concatenate(
                    [np.ones(len(take), np.float32), np.zeros(pad, np.float32)]
                )
                take = np.concatenate([take, np.resize(idx, pad)])
            batch = {k: v[take] for k, v in arrays.items()}
            if weight is not None:
                batch["weight"] = weight
            if self.accum_steps > 1:
                batch = {
                    k: v.reshape(self.accum_steps, self.batch_size,
                                 *v.shape[1:])
                    for k, v in batch.items()
                }
            yield batch

    def _place(self, batch):
        if self.accum_steps == 1:
            placed = shard_batch(batch, self.mesh)
        else:
            placed = shard_batch(batch, self.mesh,
                                 spec=scan_batch_sharding(self.mesh))
        if self.sync_placement:
            # The old world, kept as an explicit knob: block until the
            # h2d copy lands — a host sync per batch, serializing copy
            # and compute. The async default returns the dispatched
            # arrays immediately and lets XLA overlap the transfer.
            jax.block_until_ready(placed)
        return placed

    def _double_buffered(self, thunks):
        """Keep the NEXT item's device placement in flight while the
        current one is consumed.

        ``thunks`` yields zero-arg callables whose call runs the (host
        gather +) non-blocking `jax.device_put` dispatch; this stage
        runs each thunk one item AHEAD of the consumer, so the h2d copy
        of batch k+1 overlaps the consumer's step on batch k even when
        the prefetch thread is off (prefetch=0) — and composes with it
        when on (the thread then stages ahead of the double buffer).
        Two slots: one being consumed, one in flight — the classic
        device double buffer, bounded HBM.
        """
        pending = None
        for thunk in thunks:
            nxt = thunk()
            if pending is not None:
                yield pending
            pending = nxt
        if pending is not None:
            yield pending

    def _prefetched(self, placed_items):
        """Drain `placed_items` through the bounded background prefetcher.

        The producer stages the next `prefetch` items onto the devices while
        the consumer's step executes. Early-exit safe: a stop flag unblocks
        the producer if the consumer abandons the iterator mid-epoch.
        """
        if self.prefetch <= 0:
            yield from placed_items
            return

        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def _producer():
            try:
                for item in placed_items:
                    if not _put(item):
                        return
                _put(_END)
            except BaseException as e:  # surface in the consumer
                _put(e)

        t = threading.Thread(
            target=_producer, name="tpu_dp-prefetch", daemon=True
        )
        t.start()
        try:
            while True:
                # Bounded get (DP402): a producer thread that dies without
                # delivering its sentinel (killed interpreter shutdown,
                # `BaseException` path losing the race to `_put`) used to
                # wedge the consumer on a bare q.get() forever. The
                # timeout exists only to run the liveness check — the
                # sentinel/exception protocol is still the real handoff.
                try:
                    item = q.get(timeout=1.0)
                except queue.Empty:
                    if not t.is_alive():
                        raise RuntimeError(
                            "prefetch producer thread died without "
                            "delivering its end-of-epoch sentinel"
                        ) from None
                    continue
                if item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    def __iter__(self):
        return self._prefetched(self._double_buffered(
            (lambda b=b: self._place(b)) for b in self._host_batches()
        ))

    def dataset_bytes(self) -> int:
        """Host-side size of the dataset arrays (resident-staging budget)."""
        return sum(v.nbytes for v in self.dataset.arrays.values())

    @property
    def sample_shapes(self) -> dict[str, tuple[int, ...]]:
        """A row's shape by array: what the resident step restores after
        its gather (`resident_data` stages the rows flat)."""
        return {k: v.shape[1:] for k, v in self.dataset.arrays.items()}

    def resident_data(self):
        """Stage the WHOLE dataset on device, replicated over the mesh,
        every row flat: ``(N, ...)`` of rank over 2 goes up as
        ``(N, prod(...))``, rank 1 and 2 as they are.

        One transfer per run (CIFAR-10 train: 150 MB uint8); afterwards the
        resident path feeds the compiled window only indices
        (`index_windows`). Every process holds the full dataset (the loader
        materializes it everywhere), so replicated assembly is uniform.

        Flat, because the step gathers rows of it and the chip's default
        layout of a ``uint8[N, 32, 32, 3]`` argument puts N on the minor
        (lane) dimension: to gather along it the v5e compiler first relays
        the whole data set out, on every call (10 ms a step for 131,072
        images, PERF.md §6, PR 29). ``uint8[N, 3072]`` is row-major there
        and the gather reads it in place; ``(N, 32, 96)`` is N-minor again.
        The step puts the row's shape (`sample_shapes`) back after the
        gather (`tpu_dp.train.step.gather_rows`).
        """
        from tpu_dp.parallel.sharding import replicated_sharding

        flat = {k: v.reshape(len(v), -1) if v.ndim > 2 else v
                for k, v in self.dataset.arrays.items()}
        return shard_batch(flat, self.mesh, spec=replicated_sharding(self.mesh))

    def index_windows(self, k: int, skip_steps: int = 0):
        """Yield ``(n_steps, idx_device)`` windows of dataset indices.

        The resident-path twin of `windows`: same sampler order, same
        window/tail structure (full k-windows, then per-step singles), but
        each item is an int32 index array — (n, [accum,] batch), sharded on
        the batch dim — instead of the gathered examples. ~KBs per window
        over the host→device link instead of ~MBs per step.
        ``skip_steps`` resumes mid-epoch: the remaining steps re-window
        from the resume point (same step order; grouping may differ from
        the uninterrupted epoch's).
        """
        k = int(k)
        if not self.drop_remainder:
            # No weight masks in the resident train path (same invariant as
            # `windows`); eval keeps the standard pipeline.
            raise ValueError("index_windows requires drop_remainder=True")
        return self._index_windows_iter(k, int(skip_steps))

    def _index_windows_iter(self, k: int, skip_steps: int = 0):
        # No prefetch wrapper: index windows are KB-scale; placement is an
        # async device_put that never becomes the bottleneck.
        idx = np.ascontiguousarray(self.sampler.shard_indices(), np.int32)
        per_step = self.batch_size * self.accum_steps
        steps = len(self)
        step_shape = ((self.batch_size,) if self.accum_steps == 1
                      else (self.accum_steps, self.batch_size))
        remaining = max(0, steps - skip_steps)
        full = skip_steps + (remaining - remaining % k if k > 1 else 0)
        spec = scan_batch_sharding(
            self.mesh, prefix_dims=1 if self.accum_steps == 1 else 2
        )
        for s in range(skip_steps, full, k):
            take = idx[s * per_step : (s + k) * per_step]
            yield (k, shard_batch(take.reshape(k, *step_shape),
                                  self.mesh, spec=spec))
        for s in range(full, steps):
            take = idx[s * per_step : (s + 1) * per_step]
            yield (1, shard_batch(take.reshape(1, *step_shape),
                                  self.mesh, spec=spec))

    def windows(self, k: int, skip_steps: int = 0):
        """Yield ``(n_steps, device_item)`` pairs for
        `make_train_step(feed="window")`.

        Full windows stack ``k`` consecutive host batches on a leading scan
        axis (one host→device transfer, one dispatch for ``k`` optimizer
        steps); the epoch's trailing ``len(self) % k`` batches yield as
        ``(1, batch)`` singles for the per-step path — the scanned loop is
        compiled for a fixed window, and padding an optimizer-update window
        would train on fabricated steps. With ``accum_steps > 1`` each
        stacked element is itself a microbatch stack — leaves shaped
        (k, accum, batch, ...) for the scan-of-scan step. Requires
        ``drop_remainder=True`` (windows carry no weight masks).
        ``skip_steps`` resumes mid-epoch (see `_host_batches`).
        """
        k = int(k)
        # Validate eagerly (this is a plain function returning a generator,
        # not a generator function) so misconfiguration surfaces at the call
        # site, not at first iteration.
        if k > 1 and not self.drop_remainder:
            raise ValueError("windows(k) requires drop_remainder=True")
        return self._windows_iter(k, int(skip_steps))

    def _windows_iter(self, k: int, skip_steps: int = 0):
        if k <= 1:
            placed = self._double_buffered(
                (lambda b=b: self._place(b))
                for b in self._host_batches(skip_steps)
            )
            yield from ((1, b) for b in self._prefetched(placed))
            return
        # Batch dim after the window axis — and after the microbatch-stack
        # axis when accumulating. Same helper the step's in_shardings use,
        # so placement cannot drift from the compiled program.
        spec = scan_batch_sharding(
            self.mesh, prefix_dims=1 if self.accum_steps == 1 else 2
        )

        def _place_pool(pool):
            placed = shard_batch(pool, self.mesh, spec=spec)
            if self.sync_placement:
                jax.block_until_ready(placed)
            return placed

        def _host_thunks():
            buf = []
            for b in self._host_batches(skip_steps):
                buf.append(b)
                if len(buf) == k:
                    pool = {
                        key: np.stack([bb[key] for bb in buf])
                        for key in buf[0]
                    }
                    yield (lambda p=pool: (k, _place_pool(p)))
                    buf = []
            for b in buf:
                yield (lambda bb=b: (1, self._place(bb)))

        return (yield from self._prefetched(
            self._double_buffered(_host_thunks())
        ))
