"""tpu_dp.obs — unified runtime telemetry (docs/OBSERVABILITY.md).

Three layers, host-side throughout:

**Live** (config-gated by ``train.obs``):

- `spans`    — per-step span recording (data_wait / h2d / dispatch /
  device) in a ring buffer with p50/p95/p99 rollups;
- `counters` — the process-wide counter/gauge registry the existing
  subsystems (resilience retries, snapshots, RecompileGuard, preemption,
  guardrails, elastic, serve) publish into unconditionally;
- `costs`    — per-compiled-program FLOP costs and the rolling
  MFU/goodput accounting the trainer and serve engine publish from
  (the single source bench.py's MFU math now imports);
- `health`   — file-based cross-rank heartbeats, straggler attribution
  and hang detection (now with the flight-recorder hang-dump trigger);
- `promfile` — atomic Prometheus-text-format export for node scrapers
  (no HTTP server, no new deps);
- `chips`    — the unified chip-spec registry (bf16 peak + HBM + ICI
  GB/s per device kind) behind MFU and the wire-bandwidth gauges;
- `compiles` — a `jax.monitoring` listener: every program traced, lowered,
  compiled or loaded, by name, and the set-up's share of it, frozen at
  the first epoch's fence beside the set-up spans of `spans` (every
  ``train.obs``);
- `commprof` + `xplane` — in-run comm/compute attribution: step-ranged
  capture windows auto-parsed into per-collective device time, wire
  GB/s, and the ``obs.comm_ms`` / ``obs.exposed_comm_ms`` /
  ``obs.overlap_frac`` gauges, trace-reconciled against the DP304
  fingerprint schedule.

**Crash forensics** (always-on):

- `flightrec` — a bounded ring of structured events dumped atomically on
  every `Trainer.fit` exit path, so a dead rank always leaves a black
  box.

**Post-hoc**:

- `export`   — Perfetto / Chrome-trace JSON (rollback generations as
  separate track groups, instant-event markers) so a run renders in
  chrome://tracing without TensorBoard;
- ``python -m tpu_dp.obs`` (`obsctl`) — merges every per-rank artifact
  into one generation-aware forensic timeline, plus straggler
  attribution, cross-rank trace merging, baseline regression diffs, and
  ``watch``: declarative alert rules over a live (or replayed) run,
  exit-coded on trip.

The package imports no jax at module load (the device-memory gauges load
it lazily): heartbeat monitors and trace tooling must work in watcher
processes with no accelerator attached.
"""

from tpu_dp.obs.counters import (
    Counters,
    counters,
    update_device_memory_gauges,
)
from tpu_dp.obs.costs import (
    CostRegistry,
    EfficiencyMeter,
    goodput,
    peak_flops,
    resolve_flops_per_step,
)
from tpu_dp.obs.costs import registry as cost_registry
from tpu_dp.obs.export import (
    export_perfetto,
    instant_event,
    merge_traces,
    to_trace_events,
    validate_trace,
    write_trace,
)
from tpu_dp.obs.flightrec import FlightRecorder
from tpu_dp.obs.flightrec import recorder as flight_recorder
from tpu_dp.obs.health import (
    HealthError,
    HealthIssue,
    HealthMonitor,
    HeartbeatWriter,
)
from tpu_dp.obs.promfile import render_prom, write_promfile
from tpu_dp.obs.spans import STEP_SPANS, SpanRecorder, percentile

__all__ = [
    "CostRegistry",
    "Counters",
    "EfficiencyMeter",
    "FlightRecorder",
    "HealthError",
    "HealthIssue",
    "HealthMonitor",
    "HeartbeatWriter",
    "STEP_SPANS",
    "SpanRecorder",
    "cost_registry",
    "counters",
    "export_perfetto",
    "flight_recorder",
    "goodput",
    "instant_event",
    "merge_traces",
    "peak_flops",
    "percentile",
    "render_prom",
    "resolve_flops_per_step",
    "to_trace_events",
    "update_device_memory_gauges",
    "validate_trace",
    "write_promfile",
    "write_trace",
]
