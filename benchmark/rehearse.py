#!/usr/bin/env python3
"""CPU rehearsal of `run.py`, end to end at a tiny size.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload r18-b4096-resident
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 benchmark/rehearse.py --workload r18-dp4-b4096 --trace 1

It checks paths, files, control flow and the last line's shape: the same
`run.main`, with the look for the chip skipped and the batch and data set
cut (the widths stay, so the step is slow here). The line it prints carries
no metric: a number from the CPU is never written under a device metric's
name. `run.py` itself has no such switch and refuses any backend but the
cell's TPUs.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--steps-per-epoch", type=int, default=4)
    args = ap.parse_args(argv)
    chips = run.load_cell(args.workload)["chips"]
    rehearsal = {
        "batch_per_chip": args.batch_per_chip,
        "train_size": args.batch_per_chip * chips * args.steps_per_epoch,
    }
    return run.main(
        ["--workload", args.workload, "--seed", str(args.seed),
         "--seconds", str(args.seconds), "--trace", str(args.trace)],
        rehearsal=rehearsal)


if __name__ == "__main__":
    raise SystemExit(main())
