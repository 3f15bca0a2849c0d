"""`python -m tpu_dp.serve` — the synthetic-load serving smoke + chaos
scenario driver.

Drives a freshly-initialized (or checkpointed) model through the full
serve pipeline on the current backend — on CPU it forces the 8-virtual-
device mesh, the same harness the tests use — and prints the audited
report JSON. With ``--replicas N`` the run goes through the self-healing
tier (`ServeCluster`): N replicas over disjoint device subsets, failover,
elastic drain/rejoin, hot swap and SLO classes, all scriptable mid-load:

    --fault "delay:step=3,ms=500,rank=0;leave:step=5,rank=1"
    --drain-at 40:1 --rejoin-at 160:1 --swap-at 120
    --class-mix 0.6,0.4 --class-slo-ms 250,800 --floors 0:0.9
    --run-dir DIR        # heartbeats + membership ledger + flightrec dump
                         # → `obsctl timeline DIR` rebuilds the story

SIGTERM during the run means drain-then-leave for ``--sigterm-drains SID``
(default: the whole tier stops admitting and drains out — typed `closed`
sheds, never dropped requests).

Exit code is the verdict:

- 0: every request accounted for, loadgen ground truth == serve counters
  exactly (per class included), zero post-warmup retraces, and every
  ``--floors`` class met its attainment floor;
- 1: the run completed but the audit failed (inconsistent books, a
  retrace, or a class below its floor — a serving-robustness regression);
- 2: usage error.

`tools/run_tier1.sh --serve` runs the single-replica smoke at 200
requests (artifacts/serve_report.json); ``--serve-elastic`` runs the
2-replica chaos matrix (artifacts/serve_elastic_report.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_at_sid(spec: str, flag: str) -> tuple[int, int]:
    try:
        at, _, sid = spec.partition(":")
        return int(at), int(sid)
    except ValueError:
        raise ValueError(f"{flag} takes INDEX:SID, got {spec!r}") from None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tpu_dp.serve",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--pattern", default="poisson",
                    choices=["poisson", "burst", "diurnal"])
    ap.add_argument("--rate-rps", type=float, default=400.0)
    ap.add_argument("--burst", type=int, default=8)
    ap.add_argument("--sizes", default="1,2,3,4",
                    help="request image-count choices (mixed-size traffic)")
    ap.add_argument("--buckets", default="1,2,4,8,16,32",
                    help="padded batch-size ladder")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--slo-ms", type=float, default=250.0,
                    help="per-request latency target (generous on CPU)")
    ap.add_argument("--model", default="net")
    ap.add_argument("--ckpt", default=None,
                    help="serve params from this checkpoint dir "
                         "(from_checkpoint, params-only) instead of a "
                         "fresh init")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="also write the report JSON here")
    # -- the self-healing tier (docs/SERVING.md "Replica fan-out") -------
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--run-dir", default=None,
                    help="serving artifact root: heartbeats, membership "
                         "ledger, flight-recorder dump (obsctl's input)")
    ap.add_argument("--fault", default="",
                    help="';'-separated deterministic fault specs, rank = "
                         "replica sid (e.g. 'delay:step=3,ms=500,rank=0;"
                         "leave:step=5,rank=1')")
    ap.add_argument("--stale-after-s", type=float, default=2.0)
    ap.add_argument("--max-retries", type=int, default=1)
    ap.add_argument("--class-mix", default=None,
                    help="SLO-class probability mix, class 0 first "
                         "(e.g. '0.6,0.3,0.1')")
    ap.add_argument("--class-slo-ms", default="",
                    help="per-class latency targets, class 0 first")
    ap.add_argument("--floors", default="",
                    help="per-class attainment floors 'cls:frac,...' — "
                         "exit 1 when missed")
    ap.add_argument("--swap-at", type=int, default=None,
                    help="hot-swap the model weights before this request "
                         "index (a fresh seed+1 init, or --swap-ckpt)")
    ap.add_argument("--swap-ckpt", default=None,
                    help="checkpoint dir the --swap-at swap loads "
                         "(params-only)")
    ap.add_argument("--drain-at", default=None, metavar="INDEX:SID",
                    help="drain-then-leave replica SID before request INDEX")
    ap.add_argument("--rejoin-at", default=None, metavar="INDEX:SID",
                    help="rejoin replica SID before request INDEX (waits "
                         "briefly for its drain to finish)")
    ap.add_argument("--sigterm-drains", type=int, default=None,
                    help="SIGTERM drains this replica sid instead of the "
                         "whole tier")
    ap.add_argument("--profile", default=None,
                    help="apply a tpu_dp.tune tuned.json: fills the "
                         "serving ladder knobs (--buckets, --max-wait-ms) "
                         "and the model (from the profile key's workload) "
                         "that were NOT given explicitly — explicit flags "
                         "win; a (workload, devices, backend) key mismatch "
                         "is a refusal (exit 2), never a silent fallback")
    args = ap.parse_args(argv)

    profile = None
    if args.profile is not None:
        from tpu_dp.tune.profile import ProfileError, load_profile

        try:
            profile = load_profile(args.profile)
        except ProfileError as e:
            print(f"serve: {e}", file=sys.stderr)
            return 2
        explicit = {a.split("=", 1)[0]
                    for a in (sys.argv[1:] if argv is None else argv)
                    if a.startswith("--")}
        knobs = profile["config"]
        if "--buckets" not in explicit and knobs.get("serve.buckets"):
            args.buckets = str(knobs["serve.buckets"])
        if "--max-wait-ms" not in explicit and "serve.max_wait_ms" in knobs:
            args.max_wait_ms = float(knobs["serve.max_wait_ms"])
        if "--model" not in explicit:
            args.model = str(profile["key"]["workload"])

    # Backend pinning BEFORE jax imports: the smoke must exercise the
    # multi-replica fan-out, so on CPU expose 8 virtual devices (the
    # tests' harness, tests/conftest.py).
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    from tpu_dp.utils import place_compile_cache

    place_compile_cache()

    if profile is not None:
        # The ladder was tuned for a (workload, mesh, backend); serving a
        # different one under its numbers is the lie --profile refuses.
        from tpu_dp.tune.profile import ProfileMismatchError, check_key

        try:
            check_key(profile, workload=args.model,
                      devices=len(jax.devices()),
                      backend=jax.default_backend(),
                      where="this serve run")
        except ProfileMismatchError as e:
            print(f"serve: --profile {args.profile}: {e}", file=sys.stderr)
            return 2

    import numpy as np

    from tpu_dp.config import parse_class_floors, parse_class_slo_ms
    from tpu_dp.models import build_model
    from tpu_dp.serve import (
        InferenceEngine, ServeCluster, parse_buckets, run_load,
    )

    try:
        buckets = parse_buckets(args.buckets)
        sizes = tuple(int(s) for s in args.sizes.split(",") if s.strip())
        class_slo_ms = parse_class_slo_ms(args.class_slo_ms)
        floors = parse_class_floors(args.floors)
        class_mix = (
            None if args.class_mix is None
            else tuple(float(m) for m in args.class_mix.split(","))
        )
        drain_at = (None if args.drain_at is None
                    else _parse_at_sid(args.drain_at, "--drain-at"))
        rejoin_at = (None if args.rejoin_at is None
                     else _parse_at_sid(args.rejoin_at, "--rejoin-at"))
        if args.replicas < 1:
            raise ValueError(f"--replicas must be >= 1, got {args.replicas}")
        cluster_only = [
            name for name, val in (
                ("--drain-at", drain_at), ("--rejoin-at", rejoin_at),
                ("--run-dir", args.run_dir),
                ("--sigterm-drains", args.sigterm_drains),
            ) if val is not None
        ]
        if args.replicas == 1 and cluster_only:
            raise ValueError(
                f"{', '.join(cluster_only)} need --replicas >= 2"
            )
    except ValueError as e:
        print(f"serve: {e}", file=sys.stderr)
        return 2

    common = dict(
        buckets=buckets,
        max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue,
        slo_ms=args.slo_ms,
        class_slo_ms=class_slo_ms,
    )
    cluster_kw = dict(
        replicas=args.replicas,
        run_dir=args.run_dir,
        fault=args.fault,
        stale_after_s=args.stale_after_s,
        max_retries=args.max_retries,
    )
    multi = args.replicas > 1
    if args.ckpt:
        if multi:
            engine = ServeCluster.from_checkpoint(
                args.ckpt, **common, **cluster_kw
            )
        else:
            engine = InferenceEngine.from_checkpoint(
                args.ckpt, fault=args.fault, **common
            )
    else:
        model = build_model(args.model)
        variables = model.init(
            jax.random.PRNGKey(args.seed),
            np.zeros((1, 32, 32, 3), np.float32),
            train=False,
        )
        init_kw = dict(
            batch_stats=variables.get("batch_stats") or None,
            model_name=args.model,
        )
        if multi:
            engine = ServeCluster(model, variables["params"],
                                  **init_kw, **common, **cluster_kw)
        else:
            engine = InferenceEngine(model, variables["params"],
                                     fault=args.fault, **init_kw, **common)

    # The flight recorder + final dump are CLI-owned (not the cluster's):
    # a library embedder may share the process-wide recorder with a
    # trainer, and redirecting its dump dir behind their back would
    # misfile the trainer's black box.
    recorder = None
    if args.run_dir:
        from tpu_dp.obs import flightrec

        recorder = flightrec.recorder
        recorder.configure(
            rank=0, dump_dir=os.path.join(args.run_dir, "obs"), fresh=True,
            run={"kind": "serve", "replicas": args.replicas,
                 "model": args.model},
        )

    def _swap():
        if args.swap_ckpt:
            engine.swap_from_checkpoint(args.swap_ckpt)
            return
        fresh = build_model(args.model).init(
            jax.random.PRNGKey(args.seed + 1),
            np.zeros((1, 32, 32, 3), np.float32),
            train=False,
        )
        engine.swap_model(fresh["params"],
                          fresh.get("batch_stats") or None)

    def _rejoin(sid):
        # Wait briefly for the drain (scripted or fault-injected) to
        # land: rejoining a still-running replica is a scenario bug.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if engine.replicas[sid].status in ("left", "stopped"):
                engine.rejoin(sid)
                return
            time.sleep(0.02)
        print(f"serve: replica {sid} never drained; rejoin skipped",
              file=sys.stderr)

    events = []
    if drain_at is not None:
        at, sid = drain_at
        events.append((at, f"drain:{sid}", lambda s=sid: engine.drain(s)))
    if rejoin_at is not None:
        at, sid = rejoin_at
        events.append((at, f"rejoin:{sid}", lambda s=sid: _rejoin(s)))
    if args.swap_at is not None:
        events.append((args.swap_at, "swap", _swap))

    if multi:
        engine.install_sigterm_drain(args.sigterm_drains)
    engine.start()
    try:
        report = run_load(
            engine,
            n_requests=args.requests,
            pattern=args.pattern,
            rate_rps=args.rate_rps,
            sizes=sizes,
            burst=args.burst,
            seed=args.seed,
            class_mix=class_mix,
            class_slo_ms=class_slo_ms,
            events=events,
        )
    finally:
        engine.stop()
        if recorder is not None:
            recorder.dump(reason="serve_exit")

    floor_misses = []
    for cls, floor in sorted(floors.items()):
        got = (report["classes"].get(str(cls)) or {}).get("attainment")
        if got is None or got < floor:
            floor_misses.append(
                {"class": cls, "floor": floor, "attainment": got}
            )
    ok = (report["consistent"] and report["retraces"] == 0
          and not floor_misses)
    report["verdict"] = {
        "ok": bool(ok),
        "consistent": report["consistent"],
        "retraces": report["retraces"],
        "floors": {str(c): f for c, f in sorted(floors.items())},
        "floor_misses": floor_misses,
    }

    payload = json.dumps(report, indent=2, sort_keys=True)
    print(payload)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload + "\n")

    if not ok:
        print(
            f"serve: AUDIT FAILED — consistent={report['consistent']} "
            f"retraces={report['retraces']} floor_misses={floor_misses}",
            file=sys.stderr,
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
