#!/usr/bin/env python
"""Train entry point — one script, one code path, any mesh size.

Replaces the reference's forked pair (`/root/reference/cifar_example.py` run
directly vs `cifar_example_ddp.py` under `torchrun --nproc_per_node=N`): the
same command runs single-chip or across a full slice; parallelism comes from
the visible devices (and, multi-host, from `--parallel.*` / the standard JAX
coordination env), not from a launcher fork.

Usage:
    python train.py                                  # reference parity: Net, batch 4, 2 epochs
    python train.py --preset=resnet18_cifar10
    python train.py --preset=bf16_cosine_gb4096 --train.epochs=5
    python train.py --data.dataset=synthetic --train.log_every=50
    python train.py --config=checkpoints/step_0000000042/meta.json \
        --train.ckpt_dir=./repro   # reproduce into a fresh checkpoint dir
    python train.py --resume=auto  # continue from the newest checkpoint or
                                   # snapshot if one exists, else start fresh

Any config field is overridable as `--section.field=value` (see
`tpu_dp/config.py`). Preemption (SIGTERM/SIGINT) snapshots and exits with
code 143; an auto-restarting supervisor that relaunches with
`--resume=auto` loses no steps (docs/RESILIENCE.md).
"""

import json
import sys

from tpu_dp.config import parse_cli
from tpu_dp.resilience import DivergedError, PreemptedError
from tpu_dp.train.trainer import Trainer, run_elastic
from tpu_dp.utils import place_compile_cache, print0


def main(argv=None) -> int:
    cfg = parse_cli(sys.argv[1:] if argv is None else argv)
    place_compile_cache()
    try:
        if cfg.resilience.elastic:
            # The relaunch-aware driver: identical to Trainer(cfg).fit()
            # except that a fired `relaunch:` fault rejoins the run
            # in-process instead of exiting 143 (docs/RESILIENCE.md
            # "Fault-injection spec"); it also lets a relaunched process
            # JOIN a live run via resilience.elastic_join.
            trainer, result = run_elastic(cfg)
        else:
            trainer = Trainer(cfg)
            result = trainer.fit()
    except PreemptedError as e:
        # Clean preemption: the final snapshot is committed; exit with the
        # conventional terminated-by-SIGTERM status so supervisors restart
        # (with --resume=auto) instead of flagging a failure.
        print0(f"preempted: {e}")
        return PreemptedError.exit_code
    except DivergedError as e:
        # Guardrail halt: training is mathematically compromised (NaN
        # storm, unrecoverable divergence, SDC). Exit 65 (EX_DATAERR) —
        # deliberately NOT 143 — so a supervisor does not auto-restart
        # into the same divergence (docs/RESILIENCE.md "Guardrails").
        print0(f"diverged: {e}")
        return DivergedError.exit_code
    summary = {
        "model": cfg.model.name,
        "dataset": trainer.train_ds.name,
        "synthetic": trainer.train_ds.synthetic,
        "devices": trainer.num_devices,
        **{k: round(result[k], 1)
           for k in ("images_per_sec", "items_per_sec") if k in result},
        "wall_time_s": round(result["wall_time_s"], 1),
        "final_train_loss": round(result["history"][-1]["loss"], 4)
        if result["history"] else None,
        "eval": result.get("eval"),
    }
    if trainer.guard_enabled:
        # Guardrail rollup: quarantines/rollbacks/audits must be visible
        # in the one-line summary, not only in quarantine.jsonl.
        from tpu_dp.obs.counters import counters as obs_counters

        summary["guard"] = {
            "quarantined": int(obs_counters.get("guard.quarantined")),
            "spikes": int(obs_counters.get("guard.spike")),
            "rollbacks": int(obs_counters.get("guard.rollbacks")),
            "sdc_audits": int(obs_counters.get("guard.sdc_audits")),
            "sdc_mismatches": int(obs_counters.get("guard.sdc_mismatches")),
            "quarantine_log": str(trainer.quarantine_path),
        }
    obs = trainer.obs_summary()
    if obs is not None:
        # Telemetry rollup (train.obs=basic|full): span percentiles +
        # counters in the same summary line the run already emits.
        summary["obs"] = obs
        if "efficiency" in obs:
            # MFU/goodput get headline placement: hardware utilization is
            # the first-class fleet health signal (arXiv:2204.06514), not
            # a nested detail — and this is the block `obsctl diff`
            # cross-checks against BENCH baselines.
            summary["efficiency"] = obs["efficiency"]
    if trainer.elastic is not None:
        # Elastic rollup: a shrink must be visible in the one-line summary,
        # not only in the membership ledger (docs/RESILIENCE.md).
        from tpu_dp.obs.counters import counters as obs_counters

        rec = trainer.elastic.record
        summary["elastic"] = {
            "membership_epoch": rec.epoch,
            "world": rec.world,
            "members": list(rec.members),
            "regroups": int(obs_counters.get("elastic.regroups")),
            "lost_ranks": int(obs_counters.get("elastic.lost_ranks")),
            "joined_ranks": int(obs_counters.get("elastic.joined_ranks")),
            "regroup_s": round(obs_counters.get("elastic.regroup_s"), 3),
        }
    print0(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
