#!/usr/bin/env python
"""Headline benchmark: CIFAR ResNet-18 DP training throughput per chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Metric = BASELINE.json's north star, "CIFAR-10 images/sec/chip", measured on
the compiled DP train step (forward + backward + gradient all-reduce + SGD
update — the reference's entire hot loop, `cifar_example_ddp.py:94-107`, as
one XLA program) for ResNet-18 at the config-5 operating point (bfloat16
compute, large per-chip batch). Also reports **MFU** (model FLOPs
utilization) from XLA's compiled-program cost analysis against the chip's
bf16 peak.

vs_baseline: the reference publishes no numbers (`BASELINE.md`), so the
comparison point is the BASELINE.json north-star bar — the "8×V100 NCCL
baseline" — taken as 2,500 images/sec/chip for ResNet-18/CIFAR-10 DDP
training (a generous per-V100 figure for this workload at large batch;
documented assumption, not a measured artifact). vs_baseline = value / 2500.

One process per chip: the parent never imports JAX. It probes the device
with a tiny matmul in a subprocess under a timeout, then runs each
measurement in a subprocess of its own, one at a time. Every successful
measurement is appended to `benchmarks/results.jsonl`. A run that finds no
accelerator prints a structured failure line (`"value": null, "error":
...`) and exits non-zero: it never answers with an archived number.

Modes:
    python bench.py                 # headline point (batch/chip 2048, 30-step windows)
    python bench.py --sweep         # batch {1024,2048,4096} x {jnp,pallas} x window {1,30}
    python bench.py --platform cpu  # smoke-test the harness off-TPU (not archived as headline)

Measurement: one dispatch of the device-side scanned training loop
(`make_train_step(feed="window")`): N steps compiled into a single XLA program cycling a
4-slot pool of pre-staged device-resident synthetic batches, so neither the
(single-core) host nor per-step launch latency can bottleneck the
measurement. One full window runs first as compile+warmup, then a second
identical window is timed. `steps_per_call=1` points instead dispatch the
production per-step function (`make_train_step`) back-to-back — the
dispatch-bound comparison. The steady-state feed path on a real pod host
overlaps via the pipeline's prefetch instead.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

V100_BASELINE_IMG_PER_SEC_PER_CHIP = 2500.0
METRIC = "cifar10_resnet18_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"
RESULTS_PATH = Path(__file__).resolve().parent / "benchmarks" / "results.jsonl"

# The MFU math — peak FLOP/s table, analytic per-model trained-image
# FLOPs, and the scan-cost-ambiguity resolver with its analytic sanity
# check — is hoisted to `tpu_dp.obs.costs` (PR 9): the trainer's live
# `obs.mfu` gauges and the serve engine's per-bucket utilization compute
# from the SAME registry this bench publishes from, so the two can never
# drift. The names below stay importable from bench for compatibility.
# Analytic derivation (kept with its first user): CIFAR ResNet-18
# (`tpu_dp/models/resnet.py`: 3x3 stem, stages [2,2,2,2] at widths
# 64/128/256/512 on feature maps 32/16/8/4): stem 1.77M + stage1 151.0M +
# stages2-4 134.2M each + fc 5.1K = 555.4M MACs = 1.11 GFLOP forward;
# training ~= 3x forward (grad wrt weights + wrt activations) = ~3.3
# GFLOP, minus the stem's unneeded input-grad and whatever XLA folds away
# => ~2.9-3.3e9 (XLA's compiled count measures 0.875x the 3x-forward
# figure). CIFAR ResNet-50 (bottleneck, [3,4,6,3]): 1297.8M MACs forward
# by the same per-layer count => 7.79 GFLOP trained, x0.875 => ~7.0e9.
from tpu_dp.obs.costs import (  # noqa: E402  (re-exported; single source)
    FLOPS_CHECK_RTOL,
    MODEL_TRAIN_FLOPS_PER_IMAGE,
    PEAK_FLOPS_BY_KIND,
    cost_analysis_flops,
    peak_flops,
    resolve_flops_per_step,
    serve_flops_per_image,
)
from tpu_dp.obs.costs import goodput as goodput_of  # noqa: E402

RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE = MODEL_TRAIN_FLOPS_PER_IMAGE["resnet18"]
# (model name -> (analytic trained FLOPs/image, default num_classes))
MODEL_SPECS = {
    "resnet18": (MODEL_TRAIN_FLOPS_PER_IMAGE["resnet18"], 10),
    "resnet50": (MODEL_TRAIN_FLOPS_PER_IMAGE["resnet50"], 100),
}


def metric_for(model: str, num_classes: int) -> str:
    return f"cifar{num_classes}_{model}_train_images_per_sec_per_chip"


def headline_metric(model: str) -> str:
    """The metric name a given model's headline records under."""
    return metric_for(model, MODEL_SPECS[model][1])

# --------------------------------------------------------------------------
# Subprocess plumbing: nothing in the parent ever touches the accelerator
# (a chip belongs to one process at a time), and a child that hangs can
# only ever cost a timeout.
# --------------------------------------------------------------------------

def _run_sub(argv: list[str], timeout_s: float, env: dict | None = None):
    """Run a subprocess; (rc, stdout, stderr), rc=124 on timeout.

    SIGTERM with a grace period before SIGKILL, so the child can release
    the chip cleanly.
    """
    proc = subprocess.Popen(
        argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
    )
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            out, err = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
        return 124, out or "", err or ""


PROBE_SRC = """
import jax
import jax.numpy as jnp
x = jnp.ones((256, 256), jnp.bfloat16)
v = float((x @ x)[0, 0])
assert v == 256.0, v
d = jax.devices()[0]
print("PROBE_OK", jax.default_backend(), len(jax.devices()), d.device_kind, sep="\\t")
"""


def probe_device(timeout_s: float, env: dict | None = None):
    """(info dict | None, failure string). One tiny matmul in a subprocess."""
    rc, out, err = _run_sub([sys.executable, "-c", PROBE_SRC], timeout_s,
                            env=env)
    for line in out.splitlines():
        if line.startswith("PROBE_OK"):
            _, backend, n, kind = line.split("\t")
            return {"backend": backend, "n_devices": int(n),
                    "device_kind": kind}, ""
    if rc == 124:
        failure = f"probe timeout after {timeout_s:.0f}s"
    else:
        tail = (err.strip().splitlines() or ["no stderr"])[-1]
        failure = f"probe rc={rc}: {tail[:300]}"
    print(f"bench: device probe failed: {failure}", file=sys.stderr)
    return None, failure


# --------------------------------------------------------------------------
# Child: one measurement point.
# --------------------------------------------------------------------------

def compile_with_flops(jitted, *eg_args):
    """AOT-compile once; (executable, program FLOPs or None, compile stats).

    The stats block is what lands in the BENCH json under "compile":
    lowering/compile wall times plus the compiled module's collective-op
    histogram (`tpu_dp.analysis.hlo.count_collectives` — the same Level-3
    classifier dplint DP301 runs), so a PartitionSpec regression that
    sneaks an all-gather into the hot loop shows up next to the throughput
    number it explains.
    """
    t0 = time.perf_counter()
    lowered = jitted.lower(*eg_args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = {
        "lowering_ms": round((t1 - t0) * 1e3, 1),
        "compile_ms": round((t2 - t1) * 1e3, 1),
    }
    try:
        from tpu_dp.analysis.hlo import count_collectives

        stats["hlo_collectives"] = count_collectives(compiled.as_text())
    except Exception as e:  # never fail a measurement over a report stat
        stats["hlo_collectives"] = None
        print(f"bench: collective count failed ({e!r})", file=sys.stderr)
    flops = cost_analysis_flops(compiled)
    return compiled, flops, stats


def _make_step(model, opt, mesh, sched, use_pallas, update_sharding,
               sentinel=False, collective_dtype=None, quant_block=None,
               bucket_mb=0.0):
    """The production per-step program for the requested update mode
    (`make_train_step`): GSPMD's inferred all-reduce for replicated,
    explicit collectives for the sharded weight update (optionally with
    the bf16/int8 compressed wire — `--collective-dtype` — and/or the
    bucketed overlap schedule — `--bucket-mb`).
    ``sentinel=True`` builds the guardrail variant (`--guard-overhead`)."""
    from tpu_dp.train import make_train_step

    return make_train_step(
        model, opt, mesh, sched, use_pallas_xent=use_pallas,
        update_sharding=update_sharding, sentinel=sentinel,
        collective_dtype=collective_dtype or None,
        quant_block_size=quant_block,
        bucket_mb=bucket_mb,
    )


def measure_point(cfg: dict) -> dict:
    """Measure one (batch/chip, xent impl, window) point; return a record.

    Runs in a subprocess; the parent enforces the timeout.
    """
    if cfg.get("platform") == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dp.utils import place_compile_cache

    place_compile_cache()

    from tpu_dp.data.cifar import make_synthetic
    from tpu_dp.models import build_model
    from tpu_dp.parallel import dist
    from tpu_dp.parallel.sharding import (
        batch_sharding, scan_batch_sharding, shard_batch,
    )
    from tpu_dp.train import (
        SGD, cosine_lr, create_train_state, make_train_step,
    )

    from tpu_dp.parallel import bucketing as bucketing_mod
    from tpu_dp.parallel import quant as quant_mod

    per_chip = int(cfg["per_chip_batch"])
    window = int(cfg["steps_per_call"])
    measure_steps = int(cfg["measure_steps"])
    use_pallas = bool(cfg["pallas_xent"])
    fused_stages = str(cfg.get("fused_stages", "") or "")
    update_sharding = str(cfg.get("update_sharding", "replicated"))
    collective_dtype = str(cfg.get("collective_dtype", "") or "")
    quant_block = int(cfg.get("quant_block_size", 256))
    bucket_mb = float(cfg.get("bucket_mb", 0) or 0)
    model_name = cfg.get("model", "resnet18")
    flops_per_image, num_classes = MODEL_SPECS[model_name]
    metric = metric_for(model_name, num_classes)

    mesh = dist.data_mesh()
    n_chips = int(mesh.devices.size)
    global_batch = per_chip * n_chips

    from tpu_dp.models import parse_fused_stages

    model = build_model(model_name, num_classes=num_classes,
                        dtype=jnp.bfloat16,
                        fused_stages=parse_fused_stages(fused_stages),
                        fused_block_b=int(cfg.get("fused_block_b", 0)),
                        fused_bwd=bool(cfg.get("fused_bwd", False)))
    opt = SGD(momentum=0.9, weight_decay=5e-4)
    if update_sharding == "sharded":
        # Cross-replica sharded weight update (docs/PERF.md): reduce-scatter
        # grads, step 1/n_chips of params+momentum per chip, all-gather.
        from tpu_dp.train import shard_optimizer

        opt = shard_optimizer(opt, n_chips)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    if collective_dtype in ("int8", "i8"):
        state = state.replace(residuals=quant_mod.init_residuals(
            state.params, n_chips, quant_block,
            bucket_bytes=bucketing_mod.parse_bucket_mb(bucket_mb)))
    # Two windows execute (compile+warmup, then measured): schedule horizon
    # covers both so the measured steps run at real cosine LRs.
    sched = cosine_lr(0.4, 2 * measure_steps, 2)

    # 4-slot pool of device-resident uint8 batches (normalize fuses into the
    # step on device, matching the production pipeline's host->HBM format).
    host_pool = [make_synthetic(global_batch, num_classes, seed=i, name="bench")
                 for i in range(4)]

    # Timing fence: `jax.block_until_ready` on the window's metrics (JAX
    # returns before the device finishes).
    if window > 1:
        loop = make_train_step(model, opt, mesh, sched, feed="window",
                               num_steps=window,
                               use_pallas_xent=use_pallas,
                               update_sharding=update_sharding,
                               collective_dtype=collective_dtype or None,
                               quant_block_size=quant_block,
                               bucket_mb=bucket_mb)
        stacked = {
            "image": np.stack([d.images for d in host_pool]),
            "label": np.stack([d.labels for d in host_pool]),
        }
        pool = shard_batch(stacked, mesh, spec=scan_batch_sharding(mesh))
        loop_exe, program_flops, compile_stats = compile_with_flops(
            loop, state, pool)

        state, metrics = loop_exe(state, pool)  # warmup window
        jax.block_until_ready(metrics)
        t0 = time.perf_counter()
        state, metrics = loop_exe(state, pool)
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - t0
        n_steps_timed = window
        step_flops = None  # resolved below, after the provisional record
    else:
        step = _make_step(model, opt, mesh, sched, use_pallas,
                          update_sharding,
                          collective_dtype=collective_dtype,
                          quant_block=quant_block,
                          bucket_mb=bucket_mb)
        batches = [
            shard_batch({"image": d.images, "label": d.labels}, mesh,
                        spec=batch_sharding(mesh))
            for d in host_pool
        ]
        step_exe, step_flops, compile_stats = compile_with_flops(
            step, state, batches[0])
        program_flops = None  # no scan program on this path

        state, metrics = step_exe(state, batches[0])  # warmup
        jax.block_until_ready(metrics)
        t0 = time.perf_counter()
        for i in range(measure_steps):
            state, metrics = step_exe(state, batches[i % len(batches)])
        # One fence; steps chain through donated state.
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - t0
        n_steps_timed = measure_steps

    # Per-step latency percentiles (tpu_dp.obs.spans): the headline number
    # above is a MEAN over an unfenced back-to-back run — a tail regression
    # (one slow step in 20: a recompile, an allocator stall) hides inside it. This pass dispatches with a fence per
    # dispatch and rolls up p50/p95/p99, so BENCH_r*.json can tell a tail
    # regression from a mean regression. Windowed points fence per window
    # and attribute evenly (per-step tails inside one compiled scan are
    # not host-observable); the fence cost makes these latency numbers —
    # the throughput headline stays the unfenced measurement.
    latency_rec = None
    quant_overflow = quant_clip = quant_steps = 0
    lat_steps = int(cfg.get("latency_steps", 20))
    if lat_steps > 0:
        from tpu_dp.obs.spans import SpanRecorder

        rec = SpanRecorder(capacity=max(16, lat_steps * 2))
        if window > 1:
            exe, fence = loop_exe, lambda m: float(m["loss"][-1])
        else:
            exe, fence = step_exe, lambda m: float(m["loss"])
        dispatches = max(2, -(-lat_steps // window)) if window > 1 else lat_steps
        step_i = 0
        for i in range(dispatches):
            t0 = time.perf_counter()
            if window > 1:
                state, m = exe(state, pool)
            else:
                state, m = exe(state, batches[i % len(batches)])
            fence(m)
            dt_ms = (time.perf_counter() - t0) * 1e3
            rec.record_window(step_i, max(1, window), {"step": dt_ms})
            step_i += max(1, window)
            if "quant_overflow" in m:
                # Codec-health totals ride the fenced pass (the fetch is
                # already paid): overflow/clip block counts per step.
                quant_overflow += int(np.asarray(m["quant_overflow"]).sum())
                quant_clip += int(np.asarray(m["quant_clip"]).sum())
                quant_steps += max(1, window)
        roll = rec.rollup()["step"]
        latency_rec = {
            "p50_ms": roll["p50"], "p95_ms": roll["p95"],
            "p99_ms": roll["p99"], "mean_ms": roll["mean"],
            "max_ms": roll["max"], "n_steps": roll["n"],
            "fence": "per_dispatch", "window": window,
        }

    snap_every = int(cfg.get("snapshot_every", 0))
    snapshot_rec = None
    if snap_every > 0:
        # Async-snapshot overhead (docs/RESILIENCE.md "<2% at cadence 50"):
        # time the identical loop twice — plain, then with a SnapshotManager
        # consulted at every host step boundary — over enough steps for at
        # least two snapshots to fire, so the device→host double-buffer copy
        # AND the overlapped background write are both in steady state.
        import tempfile

        from tpu_dp.resilience import SnapshotManager

        if window > 1:
            reps = max(2, -(-2 * snap_every // window))

            def timed(hook):
                nonlocal state
                hs = 0
                t0 = time.perf_counter()
                for _ in range(reps):
                    state, m = loop_exe(state, pool)
                    hs += window
                    hook(state, hs)
                    float(m["loss"][-1])  # per-window fence (both runs)
                return (time.perf_counter() - t0) / (reps * window)
        else:
            reps = max(measure_steps, 2 * snap_every)

            def timed(hook):
                nonlocal state
                t0 = time.perf_counter()
                for i in range(reps):
                    state, m = step_exe(state, batches[i % len(batches)])
                    hook(state, i + 1)
                float(m["loss"])
                return (time.perf_counter() - t0) / reps

        plain_s = timed(lambda s, n: None)
        with tempfile.TemporaryDirectory() as snap_dir:
            snap = SnapshotManager(snap_dir, every_steps=snap_every, keep=2)
            snap_s = timed(lambda s, n: snap.maybe(s, n, {"bench": True}))
            snap.close()
        snapshot_rec = {
            "every_steps": snap_every,
            "ms_per_step_plain": round(plain_s * 1e3, 3),
            "ms_per_step_snapshot": round(snap_s * 1e3, 3),
            "overhead_pct": round((snap_s / plain_s - 1.0) * 100, 2),
        }

    guard_rec = None
    guard_steps = int(cfg.get("guard_overhead_steps", 0))
    if guard_steps > 0 and window == 1:
        # Guardrail-sentinel overhead (docs/RESILIENCE.md "Guardrails"):
        # time the identical per-step loop twice — the plain program, then
        # the sentinel program (on-device health summary + guarded update
        # + guard_in input) INCLUDING the guard's per-window host fetch of
        # the three health scalars, which is its real steady-state cost.
        # Measured, not assumed: this block is what the "cheap on-device
        # summary" claim is made of.
        from tpu_dp.train.step import default_guard_in

        sentinel_step = _make_step(model, opt, mesh, sched, use_pallas,
                                   update_sharding, sentinel=True)
        gstate = create_train_state(
            model, jax.random.PRNGKey(0),
            np.zeros((1, 32, 32, 3), np.float32), opt
        )
        gi = default_guard_in()
        gstate, gm = sentinel_step(gstate, batches[0], gi)  # compile+warmup
        float(gm["loss"])
        gstate, gm = sentinel_step(gstate, batches[1 % len(batches)], gi)
        float(gm["loss"])

        t0 = time.perf_counter()
        for i in range(guard_steps):
            state, m = step_exe(state, batches[i % len(batches)])
            float(m["loss"])  # same per-step fence on both runs
        plain_s = (time.perf_counter() - t0) / guard_steps

        t0 = time.perf_counter()
        for i in range(guard_steps):
            gstate, gm = sentinel_step(gstate, batches[i % len(batches)], gi)
            # The guard hook's per-window fetch: loss_raw/grad_norm/applied.
            float(gm["loss_raw"]), float(gm["grad_norm"]), int(gm["applied"])
        sentinel_s = (time.perf_counter() - t0) / guard_steps
        guard_rec = {
            "n_steps": guard_steps,
            "ms_per_step_plain": round(plain_s * 1e3, 3),
            "ms_per_step_sentinel": round(sentinel_s * 1e3, 3),
            "overhead_pct": round((sentinel_s / plain_s - 1.0) * 100, 2),
        }

    serve_rec = None
    n_serve = int(cfg.get("serve_requests", 0))
    if n_serve > 0:
        # Serve-latency percentile block (tpu_dp.serve, docs/SERVING.md):
        # the trained params go through the full queue → dynamic batcher →
        # per-bucket compiled forward pipeline under a synthetic Poisson
        # load, so the BENCH json carries request-level p50/p95/p99 and
        # shed/SLO accounting next to the training throughput the same
        # hardware sustains. The ladder always includes world-divisible
        # buckets so the replica fan-out path is exercised on any mesh.
        from tpu_dp.serve import InferenceEngine, run_load

        buckets = tuple(sorted(
            {1, 2, 4, 8, 16, 32} | {n_chips, 2 * n_chips, 4 * n_chips}
        ))
        engine = InferenceEngine(
            model, state.params,
            batch_stats=state.batch_stats or None,
            mesh=mesh,
            buckets=buckets,
            slo_ms=float(cfg.get("serve_slo_ms", 50.0)),
            model_name=model_name,
        )
        engine.start()
        try:
            srep = run_load(
                engine, n_requests=n_serve, pattern="poisson",
                rate_rps=float(cfg.get("serve_rate_rps", 500.0)), seed=0,
            )
        finally:
            engine.stop()
        serve_rec = {
            "n_requests": n_serve,
            "rate_rps": float(cfg.get("serve_rate_rps", 500.0)),
            "latency_ms": srep["latency_ms"],
            "slo": srep["slo"],
            "shed": srep["ground_truth"]["shed"],
            "deadline_missed": srep["ground_truth"]["deadline_missed"],
            "consistent": srep["consistent"],
            "retraces": srep["retraces"],
            "occupancy": srep["occupancy"],
            "bucket_counts": srep["bucket_counts"],
        }

    quant_rec = None
    if collective_dtype:
        # The wire-accounting block (docs/PERF.md "Quantized collectives"):
        # bytes each wire format puts on the gradient reduce-scatter per
        # step, plus the codec's measured overflow/clip totals over the
        # fenced latency steps. Present for bf16 too (the byte math is the
        # point of the knob); overflow/clip only exist on the int8 path.
        quant_rec = quant_mod.wire_report(
            state.params, n_chips, quant_block,
            bucket_bytes=bucketing_mod.parse_bucket_mb(bucket_mb))
        quant_rec["collective_dtype"] = collective_dtype
        if collective_dtype in ("int8", "i8"):
            quant_rec["overflow"] = quant_overflow
            quant_rec["clip_blocks"] = quant_clip
            quant_rec["stats_steps"] = quant_steps

    comm_rec = None
    if cfg.get("comm_profile"):
        # Comm/compute attribution block (tpu_dp.obs.commprof,
        # docs/OBSERVABILITY.md "Comm/compute attribution"): capture one
        # profiled window of the already-compiled program, parse the
        # xplane trace, and attach the comm_ms / exposed_comm_ms /
        # overlap_frac headline (reconciled against the program's own
        # static collective schedule) so `obsctl diff` can gate a live
        # run's comm attribution against this BENCH record.
        import tempfile

        from tpu_dp.obs import chips as chips_mod
        from tpu_dp.obs import commprof as commprof_mod
        from tpu_dp.obs import xplane as xplane_mod

        trace_dir = tempfile.mkdtemp(prefix="tpu_dp_bench_comm_")
        try:
            if window > 1:
                with jax.profiler.trace(trace_dir):
                    state, m = loop_exe(state, pool)
                    float(m["loss"][-1])
                comm_exe, comm_steps = loop_exe, window
            else:
                with jax.profiler.trace(trace_dir):
                    state, m = step_exe(state, batches[0])
                    float(m["loss"])
                comm_exe, comm_steps = step_exe, 1
            summary = xplane_mod.summarize_robust(trace_dir)
            expected = commprof_mod.expected_from_hlo_text(
                comm_exe.as_text())
            wire_rep = None
            if collective_dtype or update_sharding == "sharded":
                wire_rep = quant_mod.wire_report(
                    state.params, n_chips, quant_block,
                    bucket_bytes=bucketing_mod.parse_bucket_mb(bucket_mb))
            rep = commprof_mod.breakdown(
                summary, steps=comm_steps,
                devices=n_chips if summary.get("source") == "host" else 1,
                expected_total={k: v * comm_steps
                                for k, v in expected["counts"].items()},
                collectives=expected["collectives"],
                world=n_chips,
                wire_report=wire_rep,
                wire_dtype=collective_dtype,
                ici_gbs=chips_mod.ici_gbs(jax.devices()[0].device_kind),
            )
            comm_rec = {
                "comm_ms": rep["comm_ms"],
                "exposed_comm_ms": rep["exposed_comm_ms"],
                "overlap_frac": rep["overlap_frac"],
                "compute_ms": rep["compute_ms"],
                "reconciled": (rep.get("reconciliation") or {}).get("ok"),
                "by_kind": {k: v["per_step"]
                            for k, v in rep["by_kind"].items()},
                "steps": comm_steps,
                "source": rep["source"],
            }
            if bucket_mb and wire_rep is not None and "buckets" in wire_rep:
                # The overlap sweep's per-config layout: K and the
                # per-bucket wire assignments, from the SAME plan the
                # compiled schedule derives (docs/PERF.md).
                comm_rec["bucket_mb"] = bucket_mb
                comm_rec["buckets"] = len(wire_rep["buckets"])
        except Exception as e:  # never fail a measurement over a report stat
            print(f"bench: comm profile failed ({e!r})", file=sys.stderr)
            comm_rec = {"error": str(e)[:300]}

    images_per_sec = n_steps_timed * global_batch / elapsed
    per_chip_ips = images_per_sec / n_chips
    device_kind = jax.devices()[0].device_kind
    peak = peak_flops(device_kind)

    def build(flops_per_step, flops_source, flops_check):
        mfu = None
        if flops_per_step and peak:
            # cost_analysis reports the per-device SPMD module's FLOPs.
            mfu = round(flops_per_step * n_steps_timed / elapsed / peak, 4)
        rec = {
            "metric": metric,
            "value": round(per_chip_ips, 1),
            "unit": UNIT,
            # The 2,500 img/s/V100 bar is a ResNet-18 figure; comparing a
            # ResNet-50 run against it would overstate the baseline.
            "vs_baseline": (
                round(per_chip_ips / V100_BASELINE_IMG_PER_SEC_PER_CHIP, 3)
                if model_name == "resnet18" else None),
            "mfu": mfu,
            # Goodput rides along with MFU (arXiv:2204.06514 treats both
            # as first-class): bench's feed is a pre-staged device-
            # resident pool, so data_wait is zero by construction and
            # this is the upper bound a production pipeline's live
            # obs.goodput gauge is compared against (`obsctl diff`).
            "goodput": round(goodput_of(0.0, elapsed * 1e3), 4),
            "ms_per_step": round(elapsed / n_steps_timed * 1e3, 3),
            "flops_per_step_per_chip": flops_per_step,
            "flops_source": flops_source,
            "flops_check": flops_check,
            # Lowering/compile wall times + the compiled module's
            # collective histogram (dplint Level-3 classifier).
            "compile": compile_stats,
            "backend": jax.default_backend(),
            "device_kind": device_kind,
            "n_chips": n_chips,
            "config": {
                "model": model_name, "dtype": "bfloat16",
                "per_chip_batch": per_chip, "steps_per_call": window,
                "measured_steps": n_steps_timed,
                "xent": "pallas" if use_pallas else "jnp",
                "fused_stages": fused_stages,
                "fused_bwd": bool(cfg.get("fused_bwd", False)),
                "update_sharding": update_sharding,
                "collective_dtype": collective_dtype,
                "quant_block_size": quant_block,
                "bucket_mb": bucket_mb,
            },
        }
        if latency_rec is not None:
            rec["latency"] = latency_rec
        if comm_rec is not None:
            rec["comm"] = comm_rec
        if quant_rec is not None:
            rec["quant"] = quant_rec
        if snapshot_rec is not None:
            rec["snapshot"] = snapshot_rec
        if guard_rec is not None:
            rec["guard"] = guard_rec
        if serve_rec is not None:
            rec["serve"] = serve_rec
        return rec

    if window > 1:
        # FLOPs truth comes from the loop-free w1 step (compiled for cost
        # analysis only) — scan cost semantics are ambiguous; see
        # resolve_flops_per_step. First BANK the measurement: emit a
        # provisional record (scan/analytic FLOPs reading) that
        # run_point's last-JSON-line parse will pick up even if the extra
        # compile outlasts the parent's timeout; a clean finish overprints
        # it below.
        emit(build(*resolve_flops_per_step(
            program_flops, None, window, per_chip, flops_per_image)))
        try:
            step = _make_step(model, opt, mesh, sched, use_pallas,
                              update_sharding)
            single = shard_batch(
                {"image": host_pool[0].images, "label": host_pool[0].labels},
                mesh, spec=batch_sharding(mesh))
            _, step_flops, _ = compile_with_flops(step, state, single)
        except Exception as e:
            print(f"bench: w1 cost-analysis compile failed ({e!r}); "
                  f"keeping scan/analytic FLOPs reading", file=sys.stderr)

    return build(*resolve_flops_per_step(
        program_flops, step_flops, window, per_chip, flops_per_image))


# --------------------------------------------------------------------------
# Parent: orchestration, archive, headline emission.
# --------------------------------------------------------------------------

#: results.jsonl row layout version. 1 (implicit, untagged) = pre-tune
#: rows; 2 adds the `schema` tag itself and `config_hash` — the stable
#: join key between archived rows, tune-trial ledger entries, and
#: tuned.json profiles.
ARCHIVE_SCHEMA = 2


def archive(record: dict) -> None:
    # CPU-backend rows are harness smoke tests, not measurements of the
    # TPU metric their name carries: tag them so no consumer of the
    # archive has to know the backend convention.
    if record.get("backend") == "cpu":
        record = dict(record, smoke=True)
    record.setdefault("schema", ARCHIVE_SCHEMA)
    if "config_hash" not in record:
        # Canonical digest of the row's own config block (stdlib-only
        # import; shared with tpu_dp.tune so trial rows and profiles
        # hash identical configs identically).
        from tpu_dp.tune.profile import config_hash

        record = dict(record,
                      config_hash=config_hash(record.get("config") or {}))
    RESULTS_PATH.parent.mkdir(parents=True, exist_ok=True)
    with open(RESULTS_PATH, "a") as f:
        f.write(json.dumps(record) + "\n")


def run_point(cfg: dict, timeout_s: float) -> dict:
    """Run one measurement subprocess; returns the record (or error record)."""
    argv = [sys.executable, os.path.abspath(__file__),
            "--_measure", json.dumps(cfg)]
    rc, out, err = _run_sub(argv, timeout_s)
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                break
    tail = (err.strip().splitlines() or ["no stderr"])[-1]
    cause = (f"measurement timeout after {timeout_s:.0f}s" if rc == 124
             else f"measurement rc={rc}: {tail[:300]}")
    return {"metric": headline_metric(cfg.get("model", "resnet18")),
            "value": None, "unit": UNIT,
            "vs_baseline": None, "error": cause, "config": cfg}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--sweep", action="store_true",
                    help="sweep batch x xent-impl x window instead of the "
                         "single headline point")
    ap.add_argument("--sweep-fused", action="store_true",
                    help="sweep the fused Pallas conv-path variants "
                         "(fused_stages x fused_bwd) at the headline "
                         "batch, windows {1,30}")
    ap.add_argument("--platform", default=None, choices=["cpu"],
                    help="force the cpu backend (harness smoke test)")
    ap.add_argument("--model", default="resnet18", choices=sorted(MODEL_SPECS),
                    help="resnet18 = the north-star metric; resnet50 = "
                         "BASELINE config 3 (100-way head), archived under "
                         "its own metric name")
    ap.add_argument("--per-chip-batch", type=int, default=2048)
    ap.add_argument("--fused-stages", default="",
                    help="ResNet stages on the fused Pallas conv path "
                         "('', '0', 'all'; tpu_dp/ops/conv_block.py)")
    ap.add_argument("--fused-block-b", type=int, default=0,
                    help="images per Pallas grid step (0 = auto from VMEM budget)")
    ap.add_argument("--fused-bwd", action="store_true",
                    help="route the backward input-grad conv through the "
                         "fused kernel too")
    ap.add_argument("--measure-steps", type=int, default=30,
                    help="timed optimizer steps on the per-step (window=1) "
                         "path; also the schedule horizon")
    ap.add_argument("--steps-per-call", type=int, default=30,
                    help="scan-window length of the headline point")
    ap.add_argument("--update-sharding", default="replicated",
                    choices=["replicated", "sharded"],
                    help="weight-update mode (train.update_sharding): "
                         "'sharded' reduce-scatters grads, updates 1/N of "
                         "params+momentum per chip, all-gathers updated "
                         "params (docs/PERF.md); recorded in the BENCH "
                         "json config block")
    ap.add_argument("--collective-dtype", default="",
                    choices=["", "bf16", "int8"],
                    help="wire format of the sharded update's gradient "
                         "reduce-scatter (train.collective_dtype): bf16 "
                         "casts the payload, int8 is the blockwise-scaled "
                         "codec with error feedback; requires "
                         "--update-sharding sharded. The record gains a "
                         "'quant' block (wire bytes per step f32/bf16/"
                         "int8, overflow/clip counts)")
    ap.add_argument("--quant-block-size", type=int, default=256,
                    help="scaling-block length of the int8 wire codec "
                         "(train.quant_block_size)")
    ap.add_argument("--bucket-mb", default="",
                    help="bucketed overlap-scheduled gradient collectives "
                         "(train.bucket_mb, docs/PERF.md 'Overlapped "
                         "collectives'): target MB per gradient bucket; "
                         "requires --update-sharding sharded. A comma list "
                         "('0,0.25,1,4') sweeps bucket sizes — one "
                         "measured point each, --comm-profile forced on — "
                         "and attaches an 'overlap' block (buckets, "
                         "comm_ms, exposed_comm_ms, overlap_frac per "
                         "config) to the emitted record, gateable via the "
                         "existing obsctl diff comm signals")
    ap.add_argument("--comm-profile", action="store_true",
                    help="capture one jax.profiler window of the measured "
                         "program, parse it (tpu_dp.obs.xplane) and attach "
                         "a 'comm' block — comm_ms / exposed_comm_ms / "
                         "overlap_frac, reconciled against the program's "
                         "static collective schedule — gateable by "
                         "`obsctl diff` like mfu")
    ap.add_argument("--latency-steps", type=int, default=20,
                    help="fenced per-step latency sample size for the "
                         "p50/p95/p99 'latency' block (tpu_dp.obs.spans; "
                         "0 disables). Fenced per dispatch — these are "
                         "latency numbers, the headline mean stays the "
                         "unfenced throughput measurement")
    ap.add_argument("--serve", action="store_true",
                    help="also run a synthetic serving load over the "
                         "trained params (tpu_dp.serve: queue → dynamic "
                         "batcher → per-bucket compiled forward) and "
                         "record a 'serve' latency-percentile block "
                         "(request-level p50/p95/p99, SLO attainment, "
                         "shed counts) in the BENCH json")
    ap.add_argument("--serve-requests", type=int, default=200,
                    help="requests in the --serve load")
    ap.add_argument("--serve-rate", type=float, default=500.0,
                    help="--serve Poisson arrival rate (requests/sec)")
    ap.add_argument("--serve-slo-ms", type=float, default=50.0,
                    help="--serve per-request latency target")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="also measure async-snapshot overhead at this step "
                         "cadence (tpu_dp.resilience.SnapshotManager; the "
                         "record gains a 'snapshot' block with overhead_pct)")
    ap.add_argument("--guard-overhead", type=int, default=0, metavar="N",
                    help="also measure the guardrail sentinel's overhead "
                         "over N fenced steps (plain vs sentinel program + "
                         "the guard's per-window health fetch; the record "
                         "gains a 'guard' block with overhead_pct — "
                         "per-step path only, docs/RESILIENCE.md)")
    ap.add_argument("--probe-timeout", type=float, default=120.0,
                    help="timeout of the one device probe (seconds)")
    ap.add_argument("--point-timeout", type=float, default=900.0)
    ap.add_argument("--profile", default=None,
                    help="apply a tpu_dp.tune tuned.json: fills the "
                         "update-sharding / collective-dtype / "
                         "quant-block-size / bucket-mb knobs (and the "
                         "model, from the profile key's workload) that "
                         "were NOT given explicitly — explicit flags win. "
                         "The profile's (workload, devices, backend) key "
                         "must match the measured device or bench refuses "
                         "(exit 2), never silently measuring a different "
                         "topology under tuned numbers")
    ap.add_argument("--_measure", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    profile = None
    if args.profile is not None:
        from tpu_dp.tune.profile import (ProfileError,
                                         ProfileMismatchError,
                                         check_key, load_profile)
        try:
            profile = load_profile(args.profile)
        except ProfileError as e:
            ap.error(str(e))
        explicit = {a.split("=", 1)[0]
                    for a in sys.argv[1:] if a.startswith("--")}
        knobs = profile["config"]
        if "--model" not in explicit:
            workload = str(profile["key"]["workload"])
            if workload not in MODEL_SPECS:
                ap.error(f"profile {args.profile} is keyed for workload "
                         f"{workload!r}, which this bench cannot measure "
                         f"(known models: {', '.join(sorted(MODEL_SPECS))})")
            args.model = workload
        if ("--update-sharding" not in explicit
                and "train.update_sharding" in knobs):
            args.update_sharding = str(knobs["train.update_sharding"])
        if ("--collective-dtype" not in explicit
                and "train.collective_dtype" in knobs):
            args.collective_dtype = str(knobs["train.collective_dtype"])
        if ("--quant-block-size" not in explicit
                and "train.quant_block_size" in knobs):
            args.quant_block_size = int(knobs["train.quant_block_size"])
        if "--bucket-mb" not in explicit and knobs.get("train.bucket_mb"):
            args.bucket_mb = str(knobs["train.bucket_mb"])
    if args.sweep and args.sweep_fused:
        ap.error("--sweep and --sweep-fused are mutually exclusive; "
                 "run them as two invocations (both archive)")
    if args.collective_dtype and args.update_sharding != "sharded":
        ap.error("--collective-dtype requires --update-sharding sharded "
                 "(the wire format lives on the reduce-scatter)")
    bucket_sweep = []
    if args.bucket_mb:
        try:
            bucket_sweep = [float(x) for x in args.bucket_mb.split(",")]
        except ValueError:
            ap.error(f"--bucket-mb must be a float or comma list of "
                     f"floats, got {args.bucket_mb!r}")
        if any(v < 0 for v in bucket_sweep):
            ap.error("--bucket-mb values must be >= 0")
        if any(bucket_sweep) and args.update_sharding != "sharded":
            # 0 arms nothing — only a real bucket size needs the
            # explicit-collectives path.
            ap.error("--bucket-mb requires --update-sharding sharded "
                     "(bucketing restructures the explicit reduce-scatter)")
        if args.sweep or args.sweep_fused:
            ap.error("--bucket-mb cannot combine with --sweep/--sweep-fused")
        if len(bucket_sweep) > 1:
            # The overlap SWEEP's whole point is the exposed-comm
            # before/after: without comm attribution the table would
            # record nothing. A single --bucket-mb value profiles only
            # if the user asked (the documented contract).
            args.comm_profile = True

    if args._measure is not None:
        cfg = json.loads(args._measure)
        kernels = contextlib.nullcontext()
        if cfg.get("platform") == "cpu":
            # A harness smoke test off the TPU: the Pallas kernels run in
            # the interpreter, asked for here and nowhere else.
            from tpu_dp.ops import interpret_kernels

            kernels = interpret_kernels()
        with kernels:
            emit(measure_point(cfg))
        return

    env = None
    if args.platform == "cpu":
        env = dict(os.environ, JAX_PLATFORMS="cpu")

    hmetric = headline_metric(args.model)
    info, failure = probe_device(args.probe_timeout, env=env)
    cpu_requested = (args.platform == "cpu"
                     or os.environ.get("JAX_PLATFORMS") == "cpu")
    if info is not None and info["backend"] == "cpu" and not cpu_requested:
        # The probe "succeeded" on the wrong backend: measuring the TPU
        # headline metric there would either time out (b2048 ResNet-18 on
        # host cores) or, worse, emit a cpu number under the accelerator
        # metric's name. The device is unavailable.
        failure = (f"probe reached only the cpu backend "
                   f"({info['n_devices']} device(s)) — no accelerator in "
                   f"this environment")
        info = None
    if info is None and profile is not None:
        # A --profile run is a claim about a SPECIFIC topology; with the
        # profile's backend absent there is nothing to measure (the "typed
        # error, not silent CPU fallback" contract).
        print(f"bench: --profile {args.profile} is keyed for backend "
              f"{profile['key'].get('backend')!r} but no usable device "
              f"was reached ({failure}) — refusing to fall back",
              file=sys.stderr)
        sys.exit(2)
    if info is None:
        # A measurement path that finds no chip fails.
        emit({"metric": hmetric, "value": None, "unit": UNIT,
              "vs_baseline": None,
              "error": f"device unavailable: {failure}"})
        sys.exit(1)
    print(f"bench: device ok — {info['n_devices']}x {info['device_kind']} "
          f"({info['backend']})", file=sys.stderr)
    if profile is not None:
        try:
            check_key(profile, workload=args.model,
                      devices=info["n_devices"], backend=info["backend"],
                      where="this bench run")
        except ProfileMismatchError as e:
            print(f"bench: --profile {args.profile}: {e}", file=sys.stderr)
            sys.exit(2)
        print(f"bench: profile {args.profile} key ok "
              f"(config_hash {profile['config_hash']})", file=sys.stderr)

    base = {"measure_steps": args.measure_steps, "platform": args.platform,
            "model": args.model, "fused_stages": args.fused_stages,
            "fused_block_b": args.fused_block_b, "fused_bwd": args.fused_bwd,
            "snapshot_every": args.snapshot_every,
            "guard_overhead_steps": args.guard_overhead,
            "latency_steps": args.latency_steps,
            "comm_profile": args.comm_profile,
            "update_sharding": args.update_sharding,
            "collective_dtype": args.collective_dtype,
            "quant_block_size": args.quant_block_size,
            "serve_requests": args.serve_requests if args.serve else 0,
            "serve_rate_rps": args.serve_rate,
            "serve_slo_ms": args.serve_slo_ms}
    if args.sweep:
        grid = [
            dict(base, per_chip_batch=b, pallas_xent=px, steps_per_call=w)
            for b in (1024, 2048, 4096)
            for px in (False, True)
            for w in (1, 30)
        ]
    elif args.sweep_fused:
        # Both window lengths: w1 isolates per-dispatch kernel cost; w30 is
        # the headline operating point (scanned windows), where variant
        # costs amortize differently (e.g. the emit outputs' bandwidth) —
        # a verdict from w1 alone could mis-rank variants.
        variants = [("", False), ("0", False), ("all", False),
                    ("0", True), ("all", True)]
        grid = [
            dict(base, per_chip_batch=args.per_chip_batch, pallas_xent=False,
                 steps_per_call=w, fused_stages=fs, fused_bwd=fb)
            for w in (1, 30)
            for fs, fb in variants
        ]
    elif len(bucket_sweep) > 1:
        # The --bucket-mb overlap sweep: one measured point per bucket
        # size (0 = the monolithic baseline), same batch/window; the
        # emitted record gains the per-config 'overlap' table.
        grid = [
            dict(base, per_chip_batch=args.per_chip_batch,
                 pallas_xent=False, steps_per_call=args.steps_per_call,
                 bucket_mb=v)
            for v in bucket_sweep
        ]
    else:
        grid = [dict(base, per_chip_batch=args.per_chip_batch,
                     pallas_xent=False, steps_per_call=args.steps_per_call,
                     bucket_mb=bucket_sweep[0] if bucket_sweep else 0.0)]

    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    results = []
    for i, cfg in enumerate(grid):
        rec = run_point(cfg, args.point_timeout)
        rec["ts"] = ts
        archive(rec)
        results.append(rec)
        tag = (f"b{cfg['per_chip_batch']}/"
               f"{'pallas' if cfg['pallas_xent'] else 'jnp'}/"
               f"w{cfg['steps_per_call']}"
               + (f"/fused[{cfg['fused_stages']}"
                  f"{'+bwd' if cfg.get('fused_bwd') else ''}]"
                  if cfg.get("fused_stages") else "")
               + ("/sharded-update"
                  if cfg.get("update_sharding") == "sharded" else "")
               + (f"/bucket{cfg['bucket_mb']}mb"
                  if cfg.get("bucket_mb") else ""))
        got = (f"{rec['value']} {UNIT}, mfu={rec.get('mfu')}"
               if rec.get("value") else rec.get("error"))
        print(f"bench: [{i + 1}/{len(grid)}] {tag}: {got}", file=sys.stderr)

    good = [r for r in results if r.get("value")]
    if not good:
        emit({"metric": hmetric, "value": None, "unit": UNIT,
              "vs_baseline": None,
              "error": results[0].get("error", "all points failed")})
        sys.exit(0)
    best = max(good, key=lambda r: r["value"])
    best = dict(best, n_points=len(good))
    if len(bucket_sweep) > 1:
        # BENCH 'overlap' block: the bucket-size sweep table (docs/PERF.md
        # "Overlapped collectives"). Each config's comm numbers come from
        # its own profiled window; exposed_comm_ms / overlap_frac are the
        # signals `obsctl diff` already gates, so a live bucketed run can
        # be held to this record.
        def _overlap_row(r: dict) -> dict:
            comm = r.get("comm") or {}
            failed = "error" in comm or not comm
            row = {
                "bucket_mb": r.get("config", {}).get("bucket_mb"),
                # A failed capture is NOT a monolithic schedule: buckets
                # defaults to 1 only when the profile succeeded without a
                # bucket layout (bucket_mb=0); a failed row says so.
                "buckets": None if failed else comm.get("buckets", 1),
                "comm_ms": comm.get("comm_ms"),
                "exposed_comm_ms": comm.get("exposed_comm_ms"),
                "overlap_frac": comm.get("overlap_frac"),
                "img_per_sec_per_chip": r.get("value"),
            }
            if "error" in comm:
                row["error"] = comm["error"]
            return row

        best["overlap"] = {
            "swept": "bucket_mb",
            "configs": [_overlap_row(r) for r in results],
        }
    emit(best)


if __name__ == "__main__":
    main()
