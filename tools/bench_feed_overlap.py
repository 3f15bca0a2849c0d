#!/usr/bin/env python
"""Measure how well the input feed overlaps device execution.

Answers, with a number, where the end-to-end vs device-bound throughput
gap comes from (`benchmarks/longrun_r3/README.md`: ~2,200 img/s end-to-end
vs ~34,000 img/s for the same step in `bench.py`): runs the production
window loop (`DataPipeline.windows` -> `make_multi_step`, the exact
`Trainer.train_epoch` dispatch pattern) over synthetic data and splits
each epoch's wall time into

  wait_s     consumer time blocked waiting for the next staged window
             (host staging + host->device transfer NOT hidden by prefetch),
  step_s     time in dispatch + the device fence (device execution).

If wait_s ~= 0 the feed fully overlaps and the end-to-end gap is
device-side; if wait_s dominates, the host path (numpy gather + stack +
host->device transfer) is the bottleneck and deeper prefetch cannot help
past CPU saturation. Run with --prefetch 0 for
the no-overlap baseline.

--feed resident (or both) additionally measures the device-resident path
(`DataPipeline.index_windows` -> `make_multi_step_resident`, the
production default): the dataset is staged in HBM once and each window
ships only int32 indices, so wait_s should collapse to ~0 regardless of
host speed — the designed fix for the end-to-end gap (VERDICT r4
next-steps #3).

Prints one JSON line per (feed, prefetch, epoch).

  python tools/bench_feed_overlap.py                    # longrun shape, TPU
  python tools/bench_feed_overlap.py --platform cpu --train-size 2048 \
      --per-chip-batch 256 --window 4                   # harness smoke
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-chip-batch", type=int, default=2048)
    ap.add_argument("--window", type=int, default=24,
                    help="steps per dispatch (longrun_r3: 24 = one epoch)")
    ap.add_argument("--train-size", type=int, default=50000)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--prefetch", default="0,2,4",
                    help="comma-separated prefetch depths to compare")
    ap.add_argument("--platform", default=None, choices=["cpu"],
                    help="force cpu (harness smoke test)")
    ap.add_argument("--feed", default="both",
                    choices=["streaming", "resident", "both"],
                    help="which feed path(s) to measure")
    args = ap.parse_args()

    import jax

    kernels = contextlib.nullcontext()
    if args.platform == "cpu":
        from tpu_dp.ops import interpret_kernels

        jax.config.update("jax_platforms", "cpu")
        kernels = interpret_kernels()
    with kernels:
        _run(args)


def _run(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dp.data.cifar import make_synthetic
    from tpu_dp.data.pipeline import DataPipeline
    from tpu_dp.models import build_model
    from tpu_dp.parallel import dist
    from tpu_dp.train import SGD, cosine_lr, create_train_state, make_multi_step

    mesh = dist.data_mesh()
    gb = args.per_chip_batch * int(mesh.devices.size)
    ds = make_synthetic(args.train_size, 10, seed=0, name="overlap-bench")
    model = build_model("resnet18", num_classes=10, dtype=jnp.bfloat16)
    opt = SGD(momentum=0.9, weight_decay=5e-4)
    state0 = create_train_state(model, jax.random.PRNGKey(0),
                                np.zeros((1, 32, 32, 3), np.float32), opt)
    steps = (args.train_size // gb // args.window) * args.window
    # One schedule and one pipeline recipe shared by both feeds: the tool's
    # whole point is an apples-to-apples comparison.
    sched = cosine_lr(0.4, max(steps, 1) * args.epochs, 1)

    def make_pipe(pf):
        return DataPipeline(ds, gb, mesh, shuffle=True, seed=0,
                            drop_remainder=True, prefetch=pf)

    loop = make_multi_step(model, opt, mesh, sched, num_steps=args.window)

    def run(feed, pf, pipe, step_fn):
        # The scanned loop donates its input state; each run needs a
        # fresh copy or run 2 would step on run 1's deleted buffers.
        state = jax.tree_util.tree_map(jnp.copy, state0)
        for epoch in range(args.epochs):
            pipe.set_epoch(epoch)
            wait_s = step_s = 0.0
            n_imgs = 0
            t_epoch = time.perf_counter()
            it = (pipe.index_windows(args.window) if feed == "resident"
                  else pipe.windows(args.window))
            while True:
                t0 = time.perf_counter()
                try:
                    n, item = next(it)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                if n == 1:
                    continue  # trailing singles: not the measured path
                state, m = step_fn(state, item)
                jax.block_until_ready(m)  # fence
                t2 = time.perf_counter()
                wait_s += t1 - t0
                step_s += t2 - t1
                n_imgs += n * gb
            total = time.perf_counter() - t_epoch
            rec = {"feed": feed, "prefetch": pf, "epoch": epoch,
                   "img_per_s": round(n_imgs / total, 1),
                   "total_s": round(total, 3),
                   "wait_s": round(wait_s, 3),
                   "step_s": round(step_s, 3),
                   "wait_frac": round(wait_s / total, 3),
                   "window": args.window, "global_batch": gb,
                   "backend": jax.default_backend(),
                   "device": jax.devices()[0].device_kind}
            print(json.dumps(rec), flush=True)
            # epoch 0 of each run includes compile (cached after the
            # first) — compare epochs >= 1.

    if args.feed in ("streaming", "both"):
        for pf in [int(p) for p in args.prefetch.split(",")]:
            run("streaming", pf, make_pipe(pf), loop)

    if args.feed in ("resident", "both"):
        from tpu_dp.train.step import make_multi_step_resident

        pipe = make_pipe(0)
        rdata = pipe.resident_data()
        rloop = make_multi_step_resident(model, opt, mesh, sched,
                                         num_steps=args.window,
                                         sample_shapes=pipe.sample_shapes)
        run("resident", 0, pipe,
            lambda state, idx: rloop(state, rdata, idx))


if __name__ == "__main__":
    main()
