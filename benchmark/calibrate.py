#!/usr/bin/env python3
"""Read a cell's control and faults on the chip, at the cell's own size.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11 12 13 \
        [--variants program float8 float8_operands bfloat16 half_batch no_exchange]

Not part of a benchmark run. For each seed it follows the first steps with
the plain reference (float32, highest), then with the reference put in the
program's place (or, for ``program``, with the program itself, driven
through its first epoch as a run's set-up drives it: a dozen seeds' lower
readings in one process) and either computed in a lower precision (``float8``, the
control for a configuration that states bfloat16; ``bfloat16``, a second
witness of what the stated precision costs) or with a fault planted
(``half_batch``, ``no_exchange``), and prints the numbers `compare.py`
would compare, one JSON line a seed and variant. The limits in
``cells/<cell>.json`` were set between the program's readings (every run of
`run.py` prints them) and these.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from compare import compare, observed  # noqa: E402

NO_LIMITS = {"loss": math.inf, "grad1": math.inf, "delta": math.inf,
             "grad1_median": math.inf}


def program_readings(cell, seed, rehearsal) -> dict:
    """The program's own first steps, through the run's session and hook."""
    ses = run.Session(cell, seed, False, rehearsal)
    try:
        ses.warm_up()
        ses.hook.finish()
        got = ses.hook.first_steps()
        ses.release()
    finally:
        ses.close()
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["float8", "bfloat16", "half_batch"])
    ap.add_argument("--batch-per-chip", type=int, default=None,
                    help="cut the batch (CPU tests only)")
    ap.add_argument("--steps-per-epoch", type=int, default=None)
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from tpu_dp.utils import place_compile_cache

    place_compile_cache()
    cell = run.load_cell(args.workload)
    chips = int(cell["chips"])
    traffic = cell["traffic_file"]
    batch = (args.batch_per_chip or int(traffic["batch_per_chip"])) * chips
    train_size = int(traffic["train_size"])
    if args.steps_per_epoch:
        train_size = batch * args.steps_per_epoch
    spe = train_size // batch
    augmented = "augment" in cell["config_file"]["input"]
    rehearsal = None
    if args.batch_per_chip or args.steps_per_epoch:
        rehearsal = {"batch_per_chip": batch // chips,
                     "train_size": train_size}
    for seed in args.seeds:
        read = lambda **kw: run.reference_readings(  # noqa: E731
            cell, seed, chips, batch, train_size, spe, augmented, **kw)
        ref = read()
        for variant in args.variants:
            if variant == "program":
                got = program_readings(cell, seed, rehearsal)
            elif variant in ("float8", "float8_operands", "bfloat16"):
                got = read(precision=variant)
            else:
                got = read(fault=variant)
            _, rows = compare(got, ref, NO_LIMITS)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "variant": variant,
                "numbers": {r["name"]: r["value"] for r in rows},
                "observed": observed(got, ref),
                "leaves": {r["name"]: r["leaf"] for r in rows if "leaf" in r},
                "reference_loss": ref["loss"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
