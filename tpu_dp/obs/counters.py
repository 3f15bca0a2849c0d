"""Process-wide counter/gauge registry — the numbers every subsystem emits.

The stack already *generates* operational signals nobody collects: retry
attempts (`resilience/retry.py`), silent-recompile retraces
(`analysis/recompile.py`), snapshot write/wait seconds
(`resilience/snapshot.py`), preemption signals (`resilience/preempt.py`).
This module is the single sink those subsystems publish into, and the
single source the trainer snapshots into `metrics.jsonl` and the Perfetto
export (docs/OBSERVABILITY.md "Counter registry").

Design constraints, in order:

- **Signal-safe**: `PreemptionHandler._handle` increments from a signal
  handler, where taking a `threading.Lock` the interrupted main thread
  might hold would deadlock the process at the worst possible moment.
  `inc`/`gauge` therefore use plain dict ops under the GIL — a concurrent
  read-modify-write can lose an increment, which is an acceptable
  telemetry error and the price of never deadlocking.
- **Import-light**: imported by `resilience/*` and `analysis/recompile.py`
  at module load; must not import jax (the device-memory gauges import it
  lazily) or anything from `tpu_dp`.
- **Always-on**: publishing is unconditional (an `inc` is one dict write;
  gating every call site on `train.obs` would couple four subsystems to
  the trainer's config). What the *trainer* does with the registry —
  snapshot it into records, or ignore it — is what `train.obs` gates.

Names are dotted, `subsystem.metric[_unit]`: `retry.attempts`,
`snapshot.write_s`, `recompile.retraces`, `device.mem_in_use_bytes`.
Counters accumulate; gauges hold the last written value.
"""

from __future__ import annotations

from typing import Any


class Counters:
    """A flat registry of monotonic counters and last-value gauges."""

    def __init__(self):
        self._counts: dict[str, float] = {}
        self._gauges: dict[str, float] = {}

    def inc(self, name: str, value: float = 1.0) -> None:
        """Add ``value`` to counter ``name`` (creating it at 0).

        Lock-free on purpose — see the module docstring; safe to call from
        signal handlers and background writer threads.
        """
        self._counts[name] = self._counts.get(name, 0.0) + value

    def gauge(self, name: str, value: float) -> None:
        """Set gauge ``name`` to ``value`` (last write wins)."""
        self._gauges[name] = float(value)

    def get(self, name: str, default: float = 0.0) -> float:
        if name in self._counts:
            return self._counts[name]
        return self._gauges.get(name, default)

    def snapshot(self) -> dict[str, float]:
        """One flat point-in-time dict of every counter and gauge.

        Values are rounded to 6 decimals — these land in JSON records, and
        15-digit float seconds are noise there.
        """
        out = {}
        for src in (self._counts, self._gauges):
            for k, v in list(src.items()):
                out[k] = round(v, 6)
        return out

    def snapshot_typed(self) -> tuple[dict[str, float], dict[str, float]]:
        """(counters, gauges) as two dicts — the Prometheus exporter needs
        the type split (`# TYPE ... counter|gauge`) that the flat
        `snapshot` deliberately erases."""
        return (
            {k: round(v, 6) for k, v in list(self._counts.items())},
            {k: round(v, 6) for k, v in list(self._gauges.items())},
        )

    def reset(self) -> None:
        """Drop everything — test isolation only."""
        self._counts.clear()
        self._gauges.clear()


#: Single-source metric-name registry: every literal name at an
#: ``.inc(...)``/``.gauge(...)`` site must appear here (exact) or match a
#: `METRIC_FAMILIES` prefix (dynamic-suffix families like per-replica
#: health). dplint DP405 (`tpu_dp.analysis.hostproto`) enforces it, so an
#: obsctl diff/watch signal can never silently name a counter nothing
#: publishes. Registration stays a plain dict (import-light, no enum) —
#: emit sites keep using bare strings; this table is the audit surface.
METRICS: dict[str, str] = {
    # retry machinery (resilience/retry.py)
    "retry.attempts": "IO attempts made under retry_call",
    "retry.retries": "attempts beyond the first (transient failures)",
    "retry.exhausted": "retry budgets exhausted (error surfaced)",
    # checkpoint protocol (checkpoint.py, resilience/preempt.py)
    "ckpt.write_errors": "checkpoint writes failed after retries",
    "ckpt.corrupt_candidates": "resume candidates failing verification",
    "ckpt.verified_loads": "checkpoint loads with checksum verified",
    "ckpt.unverified_loads": "loads of pre-checksum-era checkpoints",
    "ckpt.checksum_failures": "per-file checksum mismatches seen",
    "ckpt.skipped_candidates": "quarantined/partial steps skipped",
    # snapshot engine (resilience/snapshot.py)
    "snapshot.writes": "rollback snapshots taken",
    "snapshot.write_s": "seconds spent writing snapshots",
    "snapshot.write_errors": "async snapshot spills failed",
    "snapshot.wait_s": "seconds steps waited on snapshot drains",
    # elastic membership (resilience/elastic.py, trainer)
    "elastic.departures": "peer departures detected",
    "elastic.regroups": "membership regroups committed",
    "elastic.regroup_s": "seconds spent inside regroups",
    "elastic.lost_ranks": "ranks lost across regroups",
    "elastic.joined_ranks": "ranks admitted by grow paths",
    "elastic.joins": "join requests this rank has made",
    "elastic.membership_epoch": "current membership epoch (gauge)",
    # divergence guard (resilience/guard.py, train/hooks.py)
    "guard.rollbacks": "guard-initiated rollbacks",
    "guard.quarantined": "ranks quarantined",
    "guard.halts": "guard halts (budget exhausted)",
    "guard.sdc_audits": "SDC audit windows executed",
    "guard.sdc_mismatches": "SDC audits that mismatched",
    # preemption (resilience/preempt.py)
    "preempt.signals": "preemption signals received",
    # serving fleet (serve/)
    "serve.shed": "requests shed at admission",
    "serve.accepted": "requests admitted to the queue",
    "serve.batches": "batches dispatched",
    "serve.completed": "requests completed",
    "serve.deadline_missed": "requests completed past their SLO deadline",
    "serve.batch_occupancy": "last dispatched batch occupancy (gauge)",
    "serve.device_util": "device-utilization proxy (gauge)",
    "serve.replicas_live": "replicas currently live (gauge)",
    "serve.replica_quarantine_events": "replica quarantine transitions",
    "serve.failover.retried": "requests retried on another replica",
    "serve.model_version": "model version a replica serves (gauge)",
    "serve.membership_epoch": "serve-fleet membership epoch (gauge)",
    # a model's own counters, summed on the device and published at the
    # epoch's fence (the model says which: `counter_names`; train/trainer.py)
    "moe.assignments": "(position, expert) pairs the router made",
    "moe.assignments_held": "pairs whose expert this chip holds",
    "moe.assignments_dropped": "held pairs not computed (always 0)",
    "moe.load_max_sum": "a layer's largest pair count over the held experts, summed over steps and layers",
    "moe.load_mean_sum": "a layer's mean pair count over the held experts, summed likewise",
    "diffusion.tokens": "clean tokens trained on",
    "diffusion.masked_tokens": "tokens the noise masked (the loss's positions)",
    # observability derived rates (obs/, train/trainer.py)
    "throughput.images_per_sec": "global training throughput (gauge)",
    "throughput.items_per_sec": "the same where a batch row holds many items (tokens)",
    "obs.comm_ms": "per-window collective time (gauge, ms)",
    "obs.exposed_comm_ms": "per-window exposed (unoverlapped) comm ms",
    "obs.overlap_frac": "fraction of comm overlapped with compute",
    "obs.flops_per_step_per_chip": "model FLOPs per step per chip",
    "obs.step_time_ms": "smoothed step time (gauge, ms)",
    "obs.goodput": "examples/s across the slice (gauge)",
    "obs.mfu": "model FLOPs utilization (gauge)",
    # the trainer's dispatch boundary (obs/spans.py InflightSteps)
    "loop.dispatches": "dispatches counted (an epoch's first left out)",
    "loop.inflight_sum": "steps enqueued and unfinished, summed at dispatch",
    "loop.inflight_steps": "steps enqueued and unfinished (gauge)",
    "loop.dispatch_onto_idle": "dispatches that found the device drained",
    # set-up, once a construction (obs/spans.py setup_span, train/trainer.py)
    "setup.before_trainer_s": "process start (the OS's) to the first trainer's construction (gauge)",
    "setup.trainer_s": "Trainer.__init__, whole (gauge)",
    "setup.init_state_s": "the weights and optimizer state drawn from train.seed, inside it (gauge)",
    "setup.caller_s": "the return of __init__ to the first train_epoch (gauge)",
    "setup.first_epoch_s": "the first train_epoch, entry to the return of its fence (gauge)",
    "setup.trace_s": "tracing and lowering in set-up, frozen at the first epoch's fence (gauge)",
    "setup.compile_s": "backend compiles and cache loads in set-up, frozen likewise (gauge)",
    "setup.compiled_anew": "programs compiled in set-up that the cache did not serve (gauge)",
    "setup.step_entries": "jit cache entries the train programs hold at the first epoch's fence: one a program used (gauge)",
    # program making, from jax.monitoring (obs/compiles.py)
    "compile.trace_s": "seconds tracing functions to jaxprs (each stage its own)",
    "compile.lower_s": "seconds lowering jaxprs to modules",
    "compile.backend_s": "seconds compiling, or loading from the persistent cache",
    "compile.programs": "programs compiled or loaded",
    "compile.cache_hits": "programs the persistent cache served",
    "compile.compiled_anew": "programs compiled that the cache did not serve",
    # fleet aggregation (obs/fleet.py — derived cross-rank signals)
    "fleet.step_skew_ms": "max-min step-boundary arrival skew (gauge, ms)",
    "fleet.skew_ratio": "slowest rank vs leave-one-out median (gauge)",
    "fleet.slowest_rank": "rank currently setting the step clock (gauge)",
    "fleet.slowest_streak": "consecutive steps same rank slowest (gauge)",
    "fleet.step_time_p50_ms": "fleet step-clock p50 over window (gauge)",
    "fleet.step_time_p95_ms": "fleet step-clock p95 over window (gauge)",
    "fleet.goodput": "fleet-wide goodput re-export (gauge)",
    "fleet.mfu": "fleet-wide MFU re-export (gauge)",
    "fleet.queue_depth": "serve queue depth across the tier (gauge)",
    "fleet.attainment": "worst per-class SLO attainment (gauge)",
    "fleet.publish_errors": "fleet stream publishes swallowed",
    # quantized-collective codec (parallel/compress.py)
    "quant.overflow": "int8 blocks clipped at the absmax scale",
    "quant.clip_blocks": "blocks whose scale clipped the payload",
    # analyzer / compile cache (analysis/recompile.py)
    "recompile.retraces": "jit retraces observed past warmup",
    # chaos storage-fault injection (chaos/storage.py)
    "chaos.storage_armed": "storage-fault seams armed",
    "chaos.storage_faults": "injected storage faults fired",
    "chaos.storage_slow_reads": "injected slow-read stalls served",
    # device memory (update_device_memory_gauges)
    "device.mem_in_use_bytes": "max HBM in use across local devices",
}

#: Dynamic-suffix families: a literal (or f-string prefix) matching one of
#: these prefixes is registered as a family member — the suffix is data
#: (rank, replica sid, SLO class, bucket index, device ordinal).
METRIC_FAMILIES: dict[str, str] = {
    "serve.shed.": "sheds by reason / SLO class",
    "serve.accepted.c": "admissions by SLO class",
    "serve.completed.c": "completions by SLO class",
    "serve.deadline_missed.c": "SLO misses by class",
    "serve.replica_health.": "per-replica health gauge by sid",
    "serve.replica_batches.": "batches served by replica sid",
    "serve.device_util.b": "device-utilization proxy by bucket",
    "guard.": "guard trigger counts by verdict kind",
    "device.mem_in_use_bytes.": "HBM in use by local device ordinal",
    "device.mem_limit_bytes.": "HBM limit by local device ordinal",
}


#: The process-wide registry every subsystem publishes into.
counters = Counters()


def update_device_memory_gauges(registry: Counters | None = None) -> dict[str, float]:
    """Publish per-device HBM gauges from `jax.local_devices()[i].memory_stats()`.

    Gauges: ``device.mem_in_use_bytes.<i>`` and ``device.mem_limit_bytes.<i>``
    per local device, plus the cross-device max ``device.mem_in_use_bytes``.
    Backends without memory stats (CPU, some PJRT plugins return None or
    raise) publish nothing — absence of the gauge means "not measured",
    never a fake zero. Returns the gauges written (for tests/logging).
    """
    reg = counters if registry is None else registry
    import jax  # lazy: keep this module importable without a backend

    written: dict[str, float] = {}
    try:
        devices = jax.local_devices()
    except Exception:
        return written
    in_use_max = None
    for i, dev in enumerate(devices):
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        in_use = stats.get("bytes_in_use")
        limit = stats.get("bytes_limit")
        if in_use is not None:
            written[f"device.mem_in_use_bytes.{i}"] = float(in_use)
            in_use_max = max(in_use_max or 0.0, float(in_use))
        if limit is not None:
            written[f"device.mem_limit_bytes.{i}"] = float(limit)
    if in_use_max is not None:
        written["device.mem_in_use_bytes"] = in_use_max
    for name, value in written.items():
        reg.gauge(name, value)
    return written
