#!/usr/bin/env python3
"""Read a cell's control and faults on the chip, at the cell's own size.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11 12 13 \
        [--variants program <a precision or a fault of the family's> ...]

Not part of a benchmark run. For each seed it follows the first steps with
the configuration's plain reference as the family computes it, then with
the reference put in the program's place (or, for ``program``, with the
program itself, driven through its first epoch as a run's set-up drives
it: a dozen seeds' lower readings in one process) and either computed in
one of the lower precisions the family's ``variants`` lists (the first is
the control, the nearest below the stated one; the others are further
witnesses) or with one of its faults planted, and prints the numbers
`compare.py` would compare, one JSON line a seed and variant. Without
``--variants``: the control and the faults the cell can have. The limits in
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
from loop_hook import FOLLOWED_STEPS  # noqa: E402

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
    ap.add_argument("--variants", nargs="+", default=None)
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
    family = cell["family"]
    rehearsal = None
    if args.batch_per_chip or args.steps_per_epoch:
        batch = args.batch_per_chip or int(
            cell["traffic_file"]["batch_per_chip"])
        rehearsal = {"batch_per_chip": batch}
        if args.steps_per_epoch:
            rehearsal["train_size"] = (batch * int(cell["chips"])
                                       * args.steps_per_epoch)
    known = family.variants(run.job_of(cell, args.seeds[0], rehearsal))
    variants = args.variants or [known["precisions"][0],
                                 *known["faults"]]
    for seed in args.seeds:
        job = run.job_of(cell, seed, rehearsal)
        ref = family.reference_readings(job, FOLLOWED_STEPS)
        for variant in variants:
            if variant == "program":
                got = program_readings(cell, seed, rehearsal)
            elif variant in known["precisions"]:
                got = family.reference_readings(job, FOLLOWED_STEPS,
                                                precision=variant)
            elif variant in known["faults"]:
                got = family.reference_readings(job, FOLLOWED_STEPS,
                                                fault=variant)
            else:
                raise SystemExit(
                    f"unknown variant {variant!r}: the family knows "
                    f"{list(known['precisions']) + list(known['faults'])}")
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
