"""tpu_dp.obs — spans, counters, heartbeats/straggler detection, export.

Unit coverage for each obs piece plus Trainer integration on the
8-virtual-device CPU mesh: the acceptance contract is that a
``train.obs=full`` run produces schema-2 per-step `metrics.jsonl` records
carrying all four span fields and a counter snapshot, a Perfetto JSON
that validates against the trace-event schema, and heartbeat files a
`HealthMonitor` can attribute stragglers from — while ``obs=off`` leaves
the metrics log per-epoch-only and creates no telemetry dir at all.
The cross-process straggler test lives in `test_multiprocess.py`.
"""

import json
import signal
import time
from datetime import datetime

import pytest

from tpu_dp.obs import (
    STEP_SPANS,
    Counters,
    HealthError,
    HealthMonitor,
    HeartbeatWriter,
    SpanRecorder,
    counters as global_counters,
    export_perfetto,
    merge_traces,
    percentile,
    to_trace_events,
    validate_trace,
)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _isolate_global_counters():
    """Tests share the process-wide registry; snapshot/restore around each."""
    saved_counts = dict(global_counters._counts)
    saved_gauges = dict(global_counters._gauges)
    global_counters.reset()
    yield
    global_counters._counts.clear()
    global_counters._counts.update(saved_counts)
    global_counters._gauges.clear()
    global_counters._gauges.update(saved_gauges)


# ---------------------------------------------------------------- spans --

def test_percentile_interpolates():
    vals = sorted(float(v) for v in range(1, 101))  # 1..100
    assert percentile(vals, 50) == pytest.approx(50.5)
    assert percentile(vals, 95) == pytest.approx(95.05)
    assert percentile(vals, 0) == 1.0
    assert percentile(vals, 100) == 100.0
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_span_recorder_ring_and_rollup():
    rec = SpanRecorder(capacity=50)
    for step in range(1, 101):  # 100 records into a 50-slot ring
        rec.record(step, {"dispatch": float(step)}, ts=1000.0 + step)
    assert len(rec) == 50 and rec.total_recorded == 100
    records = rec.records()
    # Ring keeps the newest 50 (steps 51..100), oldest first.
    assert records[0]["step"] == 51 and records[-1]["step"] == 100
    roll = rec.rollup()["dispatch"]
    assert roll["n"] == 50 and roll["max"] == 100.0
    assert roll["p50"] == pytest.approx(75.5)
    assert roll["mean"] == pytest.approx(75.5)
    assert roll["p99"] == pytest.approx(percentile(
        [float(v) for v in range(51, 101)], 99), abs=1e-3)


def test_span_recorder_window_attribution():
    rec = SpanRecorder()
    recs = rec.record_window(11, 4, {"dispatch": 40.0, "device": 8.0},
                             ts=500.0)
    assert [r["step"] for r in recs] == [11, 12, 13, 14]
    assert all(r["spans"] == {"dispatch": 10.0, "device": 2.0} for r in recs)
    # Per-step start times advance by the window's per-step share.
    assert recs[1]["ts"] - recs[0]["ts"] == pytest.approx(0.012)


def test_span_recorder_rejects_bad_capacity():
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)


# -------------------------------------------------------------- counters --

def test_counters_inc_gauge_snapshot_reset():
    c = Counters()
    c.inc("a")
    c.inc("a", 2.5)
    c.gauge("g", 7.0)
    c.gauge("g", 9.0)  # last write wins
    assert c.get("a") == 3.5 and c.get("g") == 9.0
    assert c.get("absent", -1.0) == -1.0
    assert c.snapshot() == {"a": 3.5, "g": 9.0}
    c.reset()
    assert c.snapshot() == {}


def test_retry_call_publishes_counters():
    from tpu_dp.resilience.retry import retry_call

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_call(flaky, retries=3, sleep=lambda s: None) == "ok"
    snap = global_counters.snapshot()
    assert snap["retry.attempts"] == 3.0
    assert snap["retry.retries"] == 2.0
    assert "retry.exhausted" not in snap

    def doomed():
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        retry_call(doomed, retries=1, sleep=lambda s: None)
    assert global_counters.get("retry.exhausted") == 1.0


def test_recompile_guard_publishes_retraces():
    from tpu_dp.analysis.recompile import RecompileGuard

    cache = {"size": 1}

    def fake_step():
        return None

    fake_step._cache_size = lambda: cache["size"]
    guard = RecompileGuard(fake_step, name="t", warmup_calls=1,
                           on_retrace="warn", logger=lambda m: None)
    guard()
    guard()             # baseline stable
    cache["size"] = 3   # two retraces
    guard()
    assert guard.retraces == 2
    assert global_counters.get("recompile.retraces") == 2.0


def test_snapshot_manager_publishes_seconds(tmp_path):
    import numpy as np

    from tpu_dp.resilience import SnapshotManager

    state = {"w": np.ones((4, 4), np.float32), "b": np.zeros(3, np.float32)}
    snap = SnapshotManager(tmp_path / "snaps", every_steps=0, keep=2)
    assert snap.snapshot(state, 7, {"t": 1}) is not None
    snap.wait()
    snap.close()
    s = global_counters.snapshot()
    assert s["snapshot.writes"] == 1.0
    assert s["snapshot.write_s"] > 0.0
    assert s["snapshot.wait_s"] >= 0.0


def test_preemption_handler_counts_signals():
    from tpu_dp.resilience import PreemptionHandler

    h = PreemptionHandler()
    h._handle(signal.SIGTERM, None)  # direct: no real signal needed
    h._handle(signal.SIGTERM, None)
    assert h.requested
    assert global_counters.get("preempt.signals") == 2.0


def test_device_memory_gauges_absent_is_not_zero():
    # The CPU backend has no memory_stats: the gauge must be ABSENT (never
    # a fake 0 that reads as "no memory in use").
    from tpu_dp.obs import update_device_memory_gauges

    reg = Counters()
    written = update_device_memory_gauges(reg)
    snap = reg.snapshot()
    for name in snap:
        assert snap[name] > 0.0
    assert set(written) == set(snap)


# ---------------------------------------------------------------- health --

def _write_beats(run_dir, rank, beats):
    with HeartbeatWriter(run_dir, rank=rank) as hb:
        for step, step_ms, ts in beats:
            hb.beat(step, step_ms, ts=ts)


def test_heartbeat_writer_throttles_by_crossing(tmp_path):
    hb = HeartbeatWriter(tmp_path, rank=0, every_steps=5)
    # Window boundaries 3, 6, 9, 12: crossings of 5 are at 6 and 12 —
    # equality never happens, crossing must still beat.
    accepted = [hb.beat(s, 1.0) for s in (3, 6, 9, 12)]
    hb.close()
    assert accepted == [True, True, False, True]
    lines = hb.path.read_text().splitlines()
    assert [json.loads(l)["step"] for l in lines] == [3, 6, 12]


def test_health_monitor_flags_straggler_and_reports(tmp_path):
    now = time.time()
    _write_beats(tmp_path, 0, [(5, 10.0, now)])
    _write_beats(tmp_path, 1, [(5, 11.0, now)])
    _write_beats(tmp_path, 2, [(5, 50.0, now)])  # 5x the median
    _write_beats(tmp_path, 3, [(5, 9.0, now)])
    mon = HealthMonitor(tmp_path, world=4, straggler_factor=3.0,
                        stale_after_s=60.0)
    issues = mon.check(now=now)
    assert [(i.kind, i.rank) for i in issues] == [("straggler", 2)]
    # Leave-one-out median: rank 2 is judged against median(10, 11, 9).
    assert issues[0].ratio >= 3.0 and issues[0].median_ms == 10.0
    # warn mode logs through the injected logger and returns the issues.
    logged = []
    warn_mon = HealthMonitor(tmp_path, world=4, logger=logged.append)
    assert warn_mon.report(warn_mon.check(now=now)) == issues
    assert len(logged) == 1 and "rank 2" in logged[0]


def test_health_monitor_stale_and_missing(tmp_path):
    now = time.time()
    _write_beats(tmp_path, 0, [(8, 10.0, now)])
    _write_beats(tmp_path, 1, [(8, 10.0, now - 120.0)])  # went quiet
    mon = HealthMonitor(tmp_path, world=3, stale_after_s=60.0)
    # Startup grace: immediately after construction a rank with no file
    # yet is NOT "missing" (the first check can precede any rank's first
    # compile-heavy window) — only the genuinely stale rank flags.
    assert {(i.kind, i.rank) for i in mon.check(now=now)} == {("stale", 1)}
    mon._start = now - 120.0  # grace elapsed: rank 2 never appeared
    issues = mon.check(now=now)
    kinds = {(i.kind, i.rank) for i in issues}
    assert ("stale", 1) in kinds and ("missing", 2) in kinds
    stale = next(i for i in issues if i.kind == "stale")
    assert stale.age_s == pytest.approx(120.0, abs=1.0)
    # raise mode: HealthError carries the issues for the supervisor.
    strict = HealthMonitor(tmp_path, world=3, stale_after_s=60.0,
                           on_flag="raise")
    with pytest.raises(HealthError) as exc_info:
        strict.report(strict.check(now=now))
    assert any(i.kind == "stale" and i.rank == 1
               for i in exc_info.value.issues)


def test_health_monitor_joiner_admission_grace(tmp_path):
    """A freshly admitted rank (elastic grow) has no heartbeat history
    and must not be flagged "missing" against the MONITOR's start time —
    `admit` restarts its grace from the admission moment (ISSUE 12
    satellite; regression for the joiner-compiles-first-window gap)."""
    now = time.time()
    _write_beats(tmp_path, 0, [(8, 10.0, now)])
    _write_beats(tmp_path, 1, [(8, 10.0, now)])
    mon = HealthMonitor(tmp_path, world=3, stale_after_s=60.0)
    mon._start = now - 300.0  # global startup grace long elapsed
    # Without admission bookkeeping, rank 2 flags missing...
    assert {(i.kind, i.rank) for i in mon.check(now=now)} == {("missing", 2)}
    # ...but an admission NOW restarts its personal grace window:
    mon.admit(2, ts=now - 5.0)
    assert mon.check(now=now) == []
    # The grace is per-rank and finite: once the joiner's own grace
    # elapses with still no beat, it flags again — with the age measured
    # from ADMISSION, not from the monitor's birth.
    issues = mon.check(now=now + 100.0)
    missing = [i for i in issues if i.kind == "missing"]
    assert [(i.kind, i.rank) for i in missing] == [("missing", 2)]
    assert missing[0].age_s == pytest.approx(105.0, abs=1.0)
    # A beat from the admitted rank clears it like any other.
    _write_beats(tmp_path, 2, [(9, 10.0, now + 100.0)])
    assert not [i for i in mon.check(now=now + 100.0)
                if i.kind == "missing"]


def test_health_monitor_stale_scales_with_window_duration(tmp_path):
    """Beats arrive once per dispatched window; a window longer than the
    fixed threshold must not mark a healthy, still-beating rank as hung.
    Staleness is judged against STALE_INTERVAL_FACTOR x the rank's own
    observed inter-beat interval when that exceeds stale_after_s."""
    now = time.time()
    # 70s windows (beats 70s apart), checked 80s after the last beat:
    # within 3 x 70s — healthy, not stale — despite stale_after_s=60.
    _write_beats(tmp_path, 0, [(8, 70_000.0, now - 150.0),
                               (16, 70_000.0, now - 80.0)])
    _write_beats(tmp_path, 1, [(8, 70_000.0, now - 150.0),
                               (16, 70_000.0, now - 80.0)])
    mon = HealthMonitor(tmp_path, world=2, stale_after_s=60.0)
    assert mon.check(now=now) == []
    # Past 3x the interval the rank really is gone.
    assert {(i.kind, i.rank) for i in mon.check(now=now + 200.0)} == {
        ("stale", 0), ("stale", 1)}


def test_health_monitor_scan_attributes_past_steps(tmp_path):
    now = time.time()
    # Rank 1 was slow at step 3 only; latest beats look healthy — check()
    # sees nothing, scan() still attributes the historical straggle.
    _write_beats(tmp_path, 0, [(s, 10.0, now) for s in (1, 2, 3, 4)])
    _write_beats(tmp_path, 1, [(1, 10.0, now), (2, 10.0, now),
                               (3, 400.0, now), (4, 10.0, now)])
    mon = HealthMonitor(tmp_path, world=2, straggler_factor=3.0,
                        stale_after_s=3600.0)
    assert mon.check(now=now) == []
    issues = mon.scan()
    assert [(i.kind, i.rank, i.step) for i in issues] == [("straggler", 1, 3)]
    assert issues[0].ratio >= 3.0


def test_health_monitor_min_step_ms_floor(tmp_path):
    # µs-scale steps: 3x jitter on a 0.2ms median must not flag.
    now = time.time()
    _write_beats(tmp_path, 0, [(1, 0.2, now)])
    _write_beats(tmp_path, 1, [(1, 0.7, now)])
    mon = HealthMonitor(tmp_path, world=2, straggler_factor=3.0,
                        min_step_ms=1.0, stale_after_s=60.0)
    assert mon.check(now=now) == []


def test_health_monitor_latest_reads_only_the_tail(tmp_path, monkeypatch):
    """The live check is O(world), not O(history): latest() must find the
    newest beat through a bounded tail read even when the heartbeat file
    has grown far past the tail window."""
    now = time.time()
    _write_beats(tmp_path, 0, [(s, 10.0, now) for s in range(1, 2001)])
    monkeypatch.setattr(HealthMonitor, "TAIL_BYTES", 512)
    mon = HealthMonitor(tmp_path, world=1)
    assert mon.latest()[0]["step"] == 2000
    # scan() deliberately keeps the full history (post-hoc attribution).
    assert len(mon.read_beats()[0]) == 2000


def test_health_monitor_skips_torn_lines(tmp_path):
    _write_beats(tmp_path, 0, [(1, 10.0, time.time())])
    with open(tmp_path / "heartbeat_r00000.jsonl", "a") as f:
        f.write('{"rank": 0, "step"')  # torn mid-write by a dying host
    mon = HealthMonitor(tmp_path, world=1)
    assert mon.latest()[0]["step"] == 1


def test_health_monitor_validates_config(tmp_path):
    with pytest.raises(ValueError):
        HealthMonitor(tmp_path, world=2, on_flag="explode")
    with pytest.raises(ValueError):
        HealthMonitor(tmp_path, world=2, straggler_factor=1.0)


def test_straggler_detection_via_fault_injector(tmp_path, monkeypatch):
    """The deterministic delay fault drives the detector single-process:
    two simulated ranks share a run dir, rank 1 carries
    ``delay:step=3,rank=1`` — scan() must name exactly that rank/step."""
    from tpu_dp.resilience.faultinject import FaultInjector

    monkeypatch.setenv("TPU_DP_FAULT", "delay:step=3,rank=1,ms=200")
    for rank in (0, 1):
        inj = FaultInjector.from_spec("", rank=rank)
        with HeartbeatWriter(tmp_path, rank=rank) as hb:
            for step in range(1, 6):
                t0 = time.perf_counter()
                time.sleep(0.02)
                inj.on_step(step)
                hb.beat(step, (time.perf_counter() - t0) * 1e3)
    mon = HealthMonitor(tmp_path, world=2, straggler_factor=3.0,
                        stale_after_s=3600.0)
    stragglers = [i for i in mon.scan() if i.kind == "straggler"]
    assert stragglers, "injected delay not flagged"
    worst = max(stragglers, key=lambda i: i.ratio)
    # The worst offender is the injected rank at the injected step,
    # carrying the measured lag factor and the delay itself.
    assert (worst.rank, worst.step) == (1, 3)
    assert worst.ratio >= 3.0
    assert worst.step_ms >= 200.0


# ---------------------------------------------------------------- export --

def _sample_records():
    return [
        {"step": 1, "ts": 100.0,
         "spans": {"data_wait": 2.0, "h2d": 0.5, "dispatch": 1.0,
                   "device": 8.0}},
        {"step": 2, "ts": 100.02,
         "spans": {"data_wait": 1.0, "h2d": 0.4, "dispatch": 0.9,
                   "device": 7.5}},
    ]


def test_to_trace_events_schema_and_layout():
    trace = to_trace_events(
        _sample_records(), rank=3,
        counter_points=[{"ts": 101.0, "counters": {"retry.attempts": 2.0,
                                                   "note": "skipped"}}],
    )
    assert validate_trace(trace) == []
    events = trace["traceEvents"]
    slices = [e for e in events if e["ph"] == "X"]
    assert len(slices) == 8 and all(e["pid"] == 3 for e in slices)
    # Spans lay out back-to-back from the step's start.
    s1 = [e for e in slices if e["args"]["step"] == 1]
    assert s1[0]["name"] == "data_wait" and s1[0]["ts"] == 100.0 * 1e6
    assert s1[1]["ts"] == pytest.approx(s1[0]["ts"] + s1[0]["dur"])
    # Metadata names the rank process and each span track.
    meta = {(e["name"], e["args"]["name"]) for e in events if e["ph"] == "M"}
    assert ("process_name", "tpu_dp rank 3") in meta
    assert ("thread_name", "device") in meta
    # Counter events carry numeric values only.
    cs = [e for e in events if e["ph"] == "C"]
    assert [c["name"] for c in cs] == ["retry.attempts"]


def test_export_perfetto_writes_valid_json(tmp_path):
    out = export_perfetto(tmp_path / "nested" / "trace.json",
                          _sample_records(), rank=0)
    trace = json.loads(out.read_text())
    assert validate_trace(trace) == []
    assert not list(tmp_path.glob("**/*.tmp"))  # atomic rename, no residue


def test_merge_traces_keeps_all_events():
    a = to_trace_events(_sample_records(), rank=0)
    b = to_trace_events(_sample_records(), rank=1)
    merged = merge_traces([a, b])
    assert validate_trace(merged) == []
    assert len(merged["traceEvents"]) == (
        len(a["traceEvents"]) + len(b["traceEvents"])
    )


def test_validate_trace_catches_malformed():
    assert validate_trace([]) != []
    assert validate_trace({"traceEvents": "nope"}) != []
    bad_ph = {"traceEvents": [{"ph": "Z", "name": "x"}]}
    assert "unknown ph" in validate_trace(bad_ph)[0]
    missing = {"traceEvents": [{"ph": "X", "name": "x", "ts": 1.0}]}
    assert any("missing" in e for e in validate_trace(missing))
    negative = {"traceEvents": [
        {"ph": "X", "name": "x", "ts": -1.0, "dur": 1.0, "pid": 0, "tid": 0}
    ]}
    assert any("non-negative" in e for e in validate_trace(negative))


# ------------------------------------------------------------- profiling --

def test_parse_profile_steps():
    from tpu_dp.utils import parse_profile_steps

    assert parse_profile_steps("") is None
    assert parse_profile_steps(None) is None
    assert parse_profile_steps("400:450") == (400, 450)
    for bad in ("400", "400:", ":450", "5:5", "9:4", "-1:4", "a:b"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


def _drive_profiler(prof, windows):
    """Simulate the trainer's hooks over (first_step, n) windows."""
    for first, n in windows:
        prof.on_window_start(first, n)
        prof.on_step(first + n - 1)


def test_step_profiler_traces_exactly_the_requested_steps():
    from tpu_dp.utils import StepProfiler

    events = []
    prof = StepProfiler("/tmp/x", 10, 20,
                        start_fn=lambda d: events.append(("start", d)),
                        stop_fn=lambda: events.append(("stop",)))
    windows = [(s, 4) for s in (1, 5, 9, 13, 17, 21, 25)]  # w4 boundaries
    traced = []
    for first, n in windows:
        prof.on_window_start(first, n)
        if prof.active:
            traced.extend(range(first, first + n))
        prof.on_step(first + n - 1)
    assert events == [("start", "/tmp/x"), ("stop",)]
    assert prof.done and not prof.active
    # The realized trace covers the requested [10, 20) — snapped outward
    # to window boundaries, never shifted one window late.
    assert set(range(10, 20)) <= set(traced)
    prof.on_window_start(40, 4)  # one artifact per run: never re-arms
    prof.on_step(43)
    assert len(events) == 2


def test_step_profiler_single_step_and_in_window_ranges():
    from tpu_dp.utils import StepProfiler

    # profile_steps=3:4 at steps_per_call=1 must trace step 3 itself.
    events = []
    prof = StepProfiler("/tmp/x", 3, 4,
                        start_fn=lambda d: events.append("start"),
                        stop_fn=lambda: events.append("stop"))
    for step in (1, 2, 3, 4):
        prof.on_window_start(step, 1)
        armed_for = step if prof.active and len(events) == 1 else None
        if armed_for is not None:
            assert armed_for == 3  # armed BEFORE step 3 ran, not after
        prof.on_step(step)
    assert events == ["start", "stop"]
    # A range strictly inside one dispatch window still traces (snaps
    # outward) instead of being skipped.
    events2 = []
    prof2 = StepProfiler("/tmp/x", 2, 5,
                         start_fn=lambda d: events2.append("start"),
                         stop_fn=lambda: events2.append("stop"))
    _drive_profiler(prof2, [(1, 8), (9, 8)])
    assert events2 == ["start", "stop"] and prof2.done


def test_step_profiler_close_stops_open_trace():
    from tpu_dp.utils import StepProfiler

    events = []
    prof = StepProfiler("/tmp/x", 0, 100,
                        start_fn=lambda d: events.append("start"),
                        stop_fn=lambda: events.append("stop"))
    prof.on_window_start(1, 1)
    prof.on_step(1)
    prof.close()  # training ended inside the range
    assert events == ["start", "stop"]
    prof_skipped = StepProfiler("/tmp/x", 5, 6,
                                start_fn=lambda d: events.append("start2"),
                                stop_fn=lambda: events.append("stop2"))
    prof_skipped.on_window_start(50, 1)  # resumed past the range
    assert prof_skipped.done and "start2" not in events
    with pytest.raises(ValueError):
        StepProfiler("", 0, 10)


# ----------------------------------------------------------- integration --

def _obs_cfg(tmp_path, **overrides):
    from tpu_dp.config import Config

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 16
    c.data.prefetch = 1
    c.train.epochs = 1
    c.train.log_every = 2
    c.train.eval_at_end = False
    c.train.ckpt_dir = str(tmp_path / "ck")
    for k, v in overrides.items():
        section, field = k.split(".")
        setattr(getattr(c, section), field, v)
    return c


def _read_metrics(path):
    return [json.loads(l) for l in path.read_text().splitlines()]


def test_trainer_obs_full_end_to_end(tmp_path):
    """The acceptance contract: obs=full on the CPU mesh produces schema-2
    per-step records with all four spans + counter snapshots, heartbeats,
    and a Perfetto JSON that validates."""
    from tpu_dp.train.trainer import Trainer

    cfg = _obs_cfg(tmp_path, **{"train.obs": "full"})
    tr = Trainer(cfg)
    tr.fit()

    records = _read_metrics(tmp_path / "ck" / "metrics.jsonl")
    assert all(r["schema"] == 3 for r in records)
    for r in records:  # ts parses as ISO-8601
        datetime.fromisoformat(r["ts"])
    per_step = [r for r in records if "spans" in r and "epoch" not in r]
    assert [r["step"] for r in per_step] == [1, 2, 3, 4]
    for r in per_step:
        # The line is written inside `telemetry`: it holds the spans that
        # had ended by then; the ring (the rollup below) holds them all.
        assert set(r["spans"]) == {"data_wait", "pre_dispatch", "h2d",
                                   "inflight_wait", "dispatch", "device"}
        assert r["spans"]["device"] > 0.0  # full mode fences per window
        assert isinstance(r["counters"], dict)
    epoch_rec = next(r for r in records if "epoch" in r)
    assert set(epoch_rec["spans"]) == set(STEP_SPANS) | {"epoch_fence"}
    assert epoch_rec["spans"]["epoch_fence"]["n"] == 1
    assert {"p50", "p95", "p99", "mean", "max", "n"} <= set(
        epoch_rec["spans"]["dispatch"])

    # Heartbeats: one file for this rank, one line per step.
    beats = (tmp_path / "ck" / "obs" / "heartbeat_r00000.jsonl")
    assert len(beats.read_text().splitlines()) == 4

    # Perfetto export validates and covers the run's steps.
    trace = json.loads(
        (tmp_path / "ck" / "obs" / "trace.perfetto.json").read_text())
    assert validate_trace(trace) == []
    steps_in_trace = {e["args"]["step"] for e in trace["traceEvents"]
                      if e["ph"] == "X"}
    assert steps_in_trace == {1, 2, 3, 4}

    # The run summary block exists and rolls up the same spans.
    summary = tr.obs_summary()
    assert summary["mode"] == "full"
    assert summary["spans_ms"]["device"]["n"] == 4


def test_trainer_obs_off_is_untelemetered(tmp_path):
    from tpu_dp.train.trainer import Trainer

    tr = Trainer(_obs_cfg(tmp_path))
    tr.fit()
    records = _read_metrics(tmp_path / "ck" / "metrics.jsonl")
    # Schema stamps are unconditional (the satellite fix)…
    assert all(r["schema"] == 3 and "ts" in r and "step" in r
               for r in records)
    # …but there are no per-step records, no spans, and no live-telemetry
    # artifacts — the only obs-dir inhabitant at obs=off is the
    # always-on flight-recorder dump (crash forensics are deliberately
    # NOT gated by train.obs; docs/OBSERVABILITY.md "Flight recorder").
    assert [r for r in records if "spans" in r] == []
    assert [p.name for p in (tmp_path / "ck" / "obs").iterdir()] == [
        "flightrec_r00000.json"
    ]
    assert tr.obs_summary() is None


def test_trainer_obs_basic_spans_without_sync(tmp_path):
    from tpu_dp.train.trainer import Trainer

    cfg = _obs_cfg(tmp_path, **{"train.obs": "basic"})
    tr = Trainer(cfg)
    tr.fit()
    records = _read_metrics(tmp_path / "ck" / "metrics.jsonl")
    # Basic: no per-step records (those are full-mode), and the epoch
    # rollup OMITS h2d/device (unmeasured — basic adds no fence; absence,
    # never a fake zero) while data_wait/dispatch are real.
    assert [r for r in records if "spans" in r and "epoch" not in r] == []
    epoch_rec = next(r for r in records if "epoch" in r)
    assert set(epoch_rec["spans"]) == (
        set(STEP_SPANS) - {"h2d", "device"}) | {"epoch_fence"}
    assert epoch_rec["spans"]["dispatch"]["max"] > 0.0
    # Heartbeats + export still on.
    assert (tmp_path / "ck" / "obs" / "trace.perfetto.json").exists()
    assert (tmp_path / "ck" / "obs" / "heartbeat_r00000.jsonl").exists()


def test_trainer_metrics_path_configurable(tmp_path):
    from tpu_dp.train.trainer import Trainer

    sink = tmp_path / "elsewhere" / "m.jsonl"
    cfg = _obs_cfg(tmp_path, **{"train.metrics_path": str(sink)})
    Trainer(cfg).fit()
    assert sink.exists()
    assert not (tmp_path / "ck" / "metrics.jsonl").exists()
    assert any("epoch" in r for r in _read_metrics(sink))


def test_trainer_rejects_bad_obs_mode(tmp_path):
    from tpu_dp.train.trainer import Trainer

    with pytest.raises(ValueError, match="train.obs"):
        Trainer(_obs_cfg(tmp_path, **{"train.obs": "loud"}))


def test_trainer_profile_steps_requires_dir(tmp_path):
    from tpu_dp.train.trainer import Trainer

    with pytest.raises(ValueError, match="profile_dir"):
        Trainer(_obs_cfg(tmp_path, **{"train.profile_steps": "1:3"}))


def test_config_obs_roundtrip_and_cli():
    from tpu_dp.config import Config, parse_cli

    cfg = parse_cli(["--train.obs=full", "--obs.straggler_factor=4.5",
                     "--obs.on_straggler=raise", "--train.metrics_path=/x",
                     "--train.profile_steps=10:20"])
    assert cfg.train.obs == "full"
    assert cfg.obs.straggler_factor == 4.5
    assert cfg.obs.on_straggler == "raise"
    rebuilt = Config.from_dict(cfg.to_dict())
    assert rebuilt.obs.straggler_factor == 4.5
    assert rebuilt.train.profile_steps == "10:20"
