"""Comm/compute attribution suite (tpu_dp/obs/{chips,xplane,commprof}.py,
obsctl watch).

- the unified chip-spec registry is the single source the MFU math, the
  breakdown tool, and the wire-bandwidth gauges all read (cross-import
  pins so the old drift-prone copies cannot come back);
- the xplane parser against the checked-in tiny fixture (host-thunk
  layout, infra skipped, interval/overlap math) + typed refusals
  (unrecognized layouts, unknown comm-report schemas);
- the CommProfiler window scheduling (range + every-N cadence) with
  injected profiler fns;
- `obsctl watch` rule parsing and trip/no-trip against a synthetic
  metrics stream;
- the CPU-backend END-TO-END: an 8-device sharded-update training run
  with an in-run capture window whose parsed breakdown reconciles
  exactly — per-step collective kinds/counts vs the program's own static
  schedule, wire bytes vs quant.wire_report — and whose gauges land in
  metrics records, the flight recorder, obsctl diff, and obsctl watch.
"""

import json
from pathlib import Path

import pytest

pytestmark = [pytest.mark.obs, pytest.mark.commprof]

FIXTURE_DIR = Path(__file__).parent / "fixtures" / "xplane"


def _has_xplane_proto() -> bool:
    try:
        from tpu_dp.obs.xplane import import_xplane_pb2

        import_xplane_pb2()
        return True
    except Exception:
        return False


# --------------------------------------------------------------------------
# chips: one registry, no more drift-prone copies
# --------------------------------------------------------------------------

def test_chip_registry_is_the_single_source():
    from tpu_dp.obs import chips, costs

    # costs' table is DERIVED from the registry, and peak_flops delegates.
    assert costs.PEAK_FLOPS_BY_KIND == tuple(
        (sub, spec.peak_flops) for sub, spec in chips.CHIP_SPECS
    )
    for sub, spec in chips.CHIP_SPECS:
        assert costs.peak_flops(sub) == chips.peak_flops(sub)
    # The v5e's published peaks.
    v5e = chips.chip_spec("TPU v5 lite")
    assert v5e is not None
    assert v5e.peak_flops == 197e12
    assert v5e.hbm_gbs == 819.0
    assert v5e.ici_gbs is not None
    # Match-order discipline survives: "v5 lite" is v5e, bare "v5" is v5p.
    assert chips.chip_spec("tpu v5").name == "v5p"
    assert chips.chip_spec("unknown accelerator") is None
    assert chips.ici_gbs("v2") is None  # unknown field: absent, never 0


def test_collective_kinds_pinned_to_analyzer():
    from tpu_dp.analysis import hlo
    from tpu_dp.obs import xplane

    # The reconciliation compares trace events against the DP304 schedule;
    # both sides must classify collectives identically.
    assert tuple(xplane.COLLECTIVE_KINDS) == tuple(hlo._COLLECTIVE_KINDS)


# --------------------------------------------------------------------------
# xplane parser: fixture, refusals, interval math
# --------------------------------------------------------------------------

@pytest.mark.skipif(not _has_xplane_proto(),
                    reason="TF xplane proto unavailable")
def test_fixture_parses_host_layout():
    from tpu_dp.obs import xplane

    s = xplane.summarize(FIXTURE_DIR)
    assert s["source"] == "host"
    # Two thread lines x one all-reduce each; infra events skipped.
    assert s["collectives"]["counts"] == {"all-reduce": 2}
    names = {op["name"] for op in s["ops"]}
    assert names == {"all-reduce.1", "loop_fusion.2"}
    # Interval math: the two lines' identical spans merge — comm is the
    # 1 ms all-reduce, compute the 2 ms fusion starting at 0.5 ms, so
    # 0.5 ms of comm is exposed and overlap is 50%.
    assert s["comm_s"] == pytest.approx(1e-3, rel=1e-6)
    assert s["compute_s"] == pytest.approx(2e-3, rel=1e-6)
    assert s["exposed_comm_s"] == pytest.approx(0.5e-3, rel=1e-6)


@pytest.mark.skipif(not _has_xplane_proto(),
                    reason="TF xplane proto unavailable")
def test_unrecognized_layout_refused(tmp_path):
    from tpu_dp.obs import xplane

    # An empty XSpace (no device plane, no host thunk lines) must be a
    # typed refusal, not an empty breakdown.
    (tmp_path / "empty.xplane.pb").write_bytes(b"")
    with pytest.raises(xplane.XplaneError, match="unrecognized"):
        xplane.summarize(tmp_path)


def test_no_trace_dir_refused(tmp_path):
    from tpu_dp.obs import xplane

    with pytest.raises(xplane.XplaneError, match="no xplane.pb"):
        xplane.summarize(tmp_path)


def test_comm_report_schema_refusal(tmp_path):
    from tpu_dp.obs import commprof

    p = tmp_path / "comm_report.json"
    p.write_text(json.dumps({"schema": 99, "comm_ms": 1.0}))
    with pytest.raises(commprof.CommProfileError, match="schema"):
        commprof.read_comm_report(p)
    commprof.write_comm_report(p, {"schema": commprof.SCHEMA, "comm_ms": 1})
    assert commprof.read_comm_report(p)["comm_ms"] == 1


def test_exposed_interval_math():
    from tpu_dp.obs.xplane import exposed_seconds

    comm = [(0.0, 1.0), (2.0, 3.0), (2.5, 3.5)]   # union [0,1] + [2,3.5]
    compute = [(0.5, 2.2), (3.4, 4.0)]
    # exposed: [0,0.5) + [2.2,3.4) = 0.5 + 1.2
    assert exposed_seconds(comm, compute) == pytest.approx(1.7)
    assert exposed_seconds(comm, []) == pytest.approx(2.5)
    assert exposed_seconds([], compute) == 0.0


def test_base_op_name():
    from tpu_dp.obs.xplane import base_op_name

    assert base_op_name("all-reduce.12") == "all-reduce"
    assert base_op_name("%reduce-scatter.3 = f32[8]{0} ...") \
        == "reduce-scatter"
    assert base_op_name("all-gather-start.1") == "all-gather"
    assert base_op_name("all-gather-done.1") == "all-gather-done"
    assert base_op_name("loop_fusion.2") == "loop_fusion"
    # Instruction names derived from JAX primitives (JAX 0.9).
    assert base_op_name("all_gather_invariant.9") == "all-gather"
    assert base_op_name("reduce_scatter.14") == "reduce-scatter"
    assert base_op_name("psum_invariant.3") == "all-reduce"


# --------------------------------------------------------------------------
# wire bytes + reconciliation units
# --------------------------------------------------------------------------

def test_shape_bytes():
    from tpu_dp.obs.commprof import shape_bytes

    assert shape_bytes("f32[8,100]") == 3200
    assert shape_bytes("s8[16]") == 16
    assert shape_bytes("bf16[4]") == 8
    assert shape_bytes("(f32[2], s32[3])") == 20
    assert shape_bytes("f32[]") == 4
    assert shape_bytes("weird[10]") == 0  # unknown dtype: never a guess


def test_wire_bytes_rules():
    from tpu_dp.obs.commprof import wire_bytes_from_schedule

    colls = [
        {"kind": "reduce-scatter", "shape": "f32[25]"},   # 1/8 shard
        {"kind": "all-gather", "shape": "f32[200]"},
        {"kind": "all-reduce", "shape": "f32[]"},          # metric scalar
        {"kind": "all-to-all", "shape": "s8[800]"},
        {"kind": "all-to-all", "shape": "f32[8]"},         # scales
    ]
    w = wire_bytes_from_schedule(colls, world=8)
    assert w["grad_exchange"] == 25 * 4 * 8 + 800 + 8 * 4
    assert w["params_gather"] == 200 * 4
    assert w["grad_allreduce"] == 0  # scalar metric never counts


def test_reconcile_exact_and_mismatch():
    from tpu_dp.obs.commprof import reconcile

    exp = {"reduce-scatter": 20, "all-gather": 20, "all-reduce": 4}
    obs = {"reduce-scatter": 160, "all-gather": 160, "all-reduce": 32}
    r = reconcile(exp, obs, steps=2, devices=8)
    assert r["ok"]
    assert r["by_kind"]["reduce-scatter"]["per_step_observed"] == 10.0
    # One missing event -> mismatch; an unexpected kind -> mismatch.
    r = reconcile(exp, dict(obs, **{"all-gather": 159}), 2, 8)
    assert not r["ok"] and not r["by_kind"]["all-gather"]["ok"]
    r = reconcile(exp, dict(obs, **{"collective-permute": 8}), 2, 8)
    assert not r["ok"]


def test_parse_comm_profile_steps():
    from tpu_dp.obs.commprof import (
        CommProfileError,
        parse_comm_profile_steps,
    )

    assert parse_comm_profile_steps("") is None
    assert parse_comm_profile_steps(None) is None
    assert parse_comm_profile_steps("4:6") == ("range", 4, 6)
    assert parse_comm_profile_steps("every:100") == ("every", 100, 1)
    assert parse_comm_profile_steps("every:100:8") == ("every", 100, 8)
    for bad in ("nope", "6:4", "every:0", "every:4:8", "every:1:2:3"):
        with pytest.raises((CommProfileError, ValueError)):
            parse_comm_profile_steps(bad)


def test_comm_profiler_every_mode_scheduling(tmp_path, monkeypatch):
    from tpu_dp.obs import commprof

    monkeypatch.setattr(
        commprof.xplane, "summarize_robust",
        lambda d: {"source": "host", "comm_s": 8e-3, "compute_s": 1e-2,
                   "exposed_comm_s": 2e-3,
                   "collectives": {"counts": {"all-reduce": 8},
                                   "dur_s": {"all-reduce": 8e-3}}},
    )
    published = []
    cp = commprof.CommProfiler(
        tmp_path, ("every", 5, 1), devices=4, world=4,
        expected_fn=lambda: {"counts": {"all-reduce": 2}, "collectives": []},
        publish=lambda rep, s, e, d: published.append((s, e, rep)),
        start_fn=lambda d: None, stop_fn=lambda: None,
    )
    for step in range(1, 13):
        cp.on_window_start(step, 1)
        cp.on_step(step)
    # Windows at steps 5 and 10, one step each.
    assert [(s, e) for s, e, _ in published] == [(5, 6), (10, 11)]
    rep = published[0][2]
    assert rep["steps"] == 1 and rep["devices"] == 4
    # 8 raw events / 4 devices / 1 step == the expected 2 per step.
    assert rep["reconciliation"]["ok"]
    # comm 8ms over 4 devices = 2ms/step; exposed 0.5ms; overlap 0.75.
    assert rep["comm_ms"] == pytest.approx(2.0)
    assert rep["exposed_comm_ms"] == pytest.approx(0.5)
    assert rep["overlap_frac"] == pytest.approx(0.75)
    assert cp.reports == 2


def test_comm_profiler_every_mode_rearms_after_step_jump(tmp_path,
                                                         monkeypatch):
    """A step jump past a pending cadence window (resume, regroup) must
    arm the window THIS dispatch covers, not silently drop one capture."""
    from tpu_dp.obs import commprof

    monkeypatch.setattr(
        commprof.xplane, "summarize_robust",
        lambda d: {"source": "host", "comm_s": 0.0, "compute_s": 1e-2,
                   "exposed_comm_s": 0.0, "collectives": {}},
    )
    published = []
    cp = commprof.CommProfiler(
        tmp_path, ("every", 4, 1), devices=1, world=4,
        publish=lambda rep, s, e, d: published.append((s, e)),
        start_fn=lambda d: None, stop_fn=lambda: None,
    )
    cp.on_window_start(1, 1)   # pending window [4, 5)
    cp.on_step(1)
    # The step clock jumps: the next dispatch covers [11, 19). The stale
    # [4, 5) window retires AND [12, 13) arms within the same dispatch
    # (snapping outward to the window, like any StepProfiler range).
    cp.on_window_start(11, 8)
    cp.on_step(18)
    assert published == [(11, 19)]
    assert cp.reports == 1


def test_comm_profiler_every_mode_wide_window_covers_jump(tmp_path,
                                                          monkeypatch):
    """A step jump landing INSIDE a W>1 cadence window still captures
    that window (snapping outward), not the next cadence."""
    from tpu_dp.obs import commprof

    monkeypatch.setattr(
        commprof.xplane, "summarize_robust",
        lambda d: {"source": "host", "comm_s": 0.0, "compute_s": 1e-2,
                   "exposed_comm_s": 0.0, "collectives": {}},
    )
    published = []
    cp = commprof.CommProfiler(
        tmp_path, ("every", 10, 3), devices=1, world=4,
        publish=lambda rep, s, e, d: published.append((s, e, rep)),
        start_fn=lambda d: None, stop_fn=lambda: None,
    )
    cp.on_window_start(11, 1)  # resumed into [10, 13)
    cp.on_step(11)
    cp.on_window_start(12, 1)
    cp.on_step(12)             # window's last step (end - 1) ran
    assert [(s, e) for s, e, _ in published] == [(11, 13)]
    assert published[0][2]["steps"] == 2  # the partial capture, honest


def test_step_profiler_records_flightrec_events(tmp_path):
    from tpu_dp.obs import flightrec
    from tpu_dp.utils.profiling import StepProfiler

    flightrec.recorder.reset()
    prof = StepProfiler(str(tmp_path), 3, 5, start_fn=lambda d: None,
                        stop_fn=lambda: None, label="unit")
    prof.on_window_start(1, 1)
    prof.on_step(1)
    prof.on_window_start(3, 1)   # arms
    prof.on_step(3)
    prof.on_window_start(4, 1)
    prof.on_step(4)              # stops (end-1 == 4)
    evs = [e for e in flightrec.recorder.events()
           if e["kind"].startswith("profile_")]
    assert [e["kind"] for e in evs] == ["profile_start", "profile_stop"]
    assert evs[0]["trace_dir"] == str(tmp_path)
    assert evs[0]["label"] == "unit"
    assert (evs[0]["start_step"], evs[0]["end_step"]) == (3, 5)
    flightrec.recorder.reset()


# --------------------------------------------------------------------------
# obsctl watch: rules + trip/no-trip over a synthetic stream
# --------------------------------------------------------------------------

def _write_stream(run: Path, dip_step: int | None = None,
                  exposed_ms: float = 0.6) -> Path:
    recs = []
    for i in range(1, 11):
        mfu = 0.2 if i == dip_step else 0.5
        recs.append({"ts": f"2026-08-01T10:00:{i:02d}+00:00", "step": i,
                     "schema": 3, "mfu": mfu, "goodput": 0.95,
                     "spans": {"data_wait": 1.0, "dispatch": 2.0},
                     "counters": {"obs.step_time_ms": 10.0,
                                  "quant.overflow": 0.0}})
    recs.append({"ts": "2026-08-01T10:00:12+00:00", "step": 10,
                 "schema": 3, "event": "comm_profile", "comm_ms": 2.0,
                 "exposed_comm_ms": exposed_ms, "overlap_frac": 0.7})
    run.mkdir(parents=True, exist_ok=True)
    (run / "metrics.jsonl").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n")
    base = run / "base.json"
    base.write_text(json.dumps({"mfu": 0.5, "goodput": 0.95,
                                "p95_ms": 10.0, "exposed_comm_ms": 0.5}))
    return base


def test_watch_rule_parsing():
    from tpu_dp.obs.obsctl import WatchRule

    r = WatchRule("mfu<0.9*baseline")
    assert (r.signal, r.op, r.factor, r.const) == ("mfu", "<", 0.9, None)
    assert r.bound({"mfu": 0.5}) == pytest.approx(0.45)
    assert r.bound({}) is None  # baseline lacks the signal: no-data
    r = WatchRule("exposed_comm_ms>=5")
    assert (r.signal, r.const) == ("exposed_comm_ms", 5.0)
    assert WatchRule("goodput <= baseline*0.8").factor == 0.8
    assert WatchRule("heartbeat_age_s>baseline").factor == 1.0
    for bad in ("mfu!!3", "<0.5", "mfu<", "mfu<nope"):
        with pytest.raises(ValueError):
            WatchRule(bad)


def test_watch_rule_unknown_signal_rejected():
    """A typo'd signal must be a parse-time usage error — it would
    otherwise never evaluate, and a second healthy rule seeing data
    would mask the dead gate under exit 0."""
    from tpu_dp.obs.obsctl import WatchRule

    with pytest.raises(ValueError, match="unknown signal"):
        WatchRule("exposed_com_ms>1.5*baseline")


def test_health_scan_accepts_shared_beats(tmp_path):
    """`scan(beats=)` must match a fresh-read scan — `end_signals` shares
    one file pass between the straggler scan and the last-beat ages."""
    from tpu_dp.obs.health import HealthMonitor

    def beat(rank, step, step_ms):
        with open(tmp_path / f"heartbeat_r{rank:05d}.jsonl", "a") as f:
            f.write(json.dumps({"rank": rank, "step": step,
                                "ts": 100.0 + step,
                                "step_ms": step_ms}) + "\n")

    for step in range(1, 4):
        beat(0, step, 10.0)
        beat(1, step, 200.0 if step == 2 else 10.0)  # step-2 straggler
    mon = HealthMonitor(tmp_path, world=2)
    fresh = [(i.kind, i.rank, i.step) for i in mon.scan()]
    shared = [(i.kind, i.rank, i.step)
              for i in mon.scan(beats=mon.read_beats())]
    assert fresh == shared and ("straggler", 1, 2) in shared


def test_end_signals_ignore_departed_epochs(tmp_path):
    """heartbeat_age_s is a state-of-the-run signal: a rank that
    legitimately departed in an elastic shrink (its old epoch's stream
    stops forever) must not read as permanently stale."""
    from tpu_dp.obs.obsctl import RunArtifacts, end_signals

    def beat(d, rank, ts):
        with open(d / f"heartbeat_r{rank:05d}.jsonl", "a") as f:
            f.write(json.dumps({"rank": rank, "step": 1, "ts": ts,
                                "step_ms": 10.0}) + "\n")

    obs = tmp_path / "obs"
    me1 = obs / "me0001"
    me1.mkdir(parents=True)
    beat(obs, 0, 500.0)
    beat(obs, 2, 500.0)   # departs; its stream ends here
    beat(me1, 0, 999.0)   # survivors re-homed and healthy
    beat(me1, 1, 999.0)
    sig = end_signals(RunArtifacts(tmp_path), now=1000.0)
    assert sig["heartbeat_age_s"] == pytest.approx(1.0)


def test_metrics_tail_incremental(tmp_path):
    """The live-watch tail parses only appended bytes and defers a
    partial trailing line to the next tick (shared `tpu_dp.obs.tail`
    reader; obsctl's old private name must stay importable)."""
    from tpu_dp.obs.obsctl import _MetricsTail
    from tpu_dp.obs.tail import JsonlTail

    assert _MetricsTail is JsonlTail
    path = tmp_path / "metrics.jsonl"
    tail = _MetricsTail(path)
    assert tail.poll() == []  # absent file: no data, no error
    with open(path, "w") as f:
        f.write(json.dumps({"step": 1}) + "\n")
    assert [r["step"] for r in tail.poll()] == [1]
    assert tail.poll() == []
    with open(path, "a") as f:
        f.write(json.dumps({"step": 2}) + "\n")
        f.write('{"step": 3')  # sink mid-append
    assert [r["step"] for r in tail.poll()] == [2]
    with open(path, "a") as f:
        f.write(', "mfu": 0.5}\n')
    assert [r["step"] for r in tail.poll()] == [3]


def test_watch_trips_and_exit_codes(tmp_path):
    from tpu_dp.obs import obsctl

    base = _write_stream(tmp_path / "run", dip_step=7)
    run = str(tmp_path / "run")
    alerts = tmp_path / "run" / "alerts.jsonl"
    # Trip on the mid-run MFU dip, archiving the alert events.
    rc = obsctl.main(["watch", run, "--replay", "--baseline", str(base),
                      "--rule", "mfu<0.9*baseline",
                      "--alerts-out", str(alerts)])
    assert rc == 1
    ev = json.loads(alerts.read_text().splitlines()[0])
    assert ev["kind"] == "alert" and ev["step"] == 7
    assert ev["value"] == pytest.approx(0.2)
    # The archived alert merges into the forensic timeline as a marker.
    timeline = obsctl.build_timeline(obsctl.RunArtifacts(run))
    kinds = [e["kind"] for e in timeline["events"]]
    assert "alert" in kinds and "comm_profile" in kinds
    assert "alert" in obsctl.MARKER_KINDS

    # Clean rules on a clean stream exit 0.
    clean = _write_stream(tmp_path / "clean")
    rc = obsctl.main(["watch", str(tmp_path / "clean"), "--replay",
                      "--baseline", str(clean),
                      "--rule", "mfu<0.9*baseline",
                      "--rule", "goodput<0.8",
                      "--rule", "quant_overflow_per_step>0",
                      "--rule", "overlap_frac<0.5"])
    assert rc == 0
    # Exposed-comm regression vs the baseline trips.
    rc = obsctl.main(["watch", str(tmp_path / "clean"), "--replay",
                      "--baseline", str(clean),
                      "--rule", "exposed_comm_ms>1.1*baseline"])
    assert rc == 1
    # No rule ever saw data -> refuse to certify (exit 2, like diff).
    rc = obsctl.main(["watch", str(tmp_path / "clean"), "--replay",
                      "--rule", "straggler_ratio>3"])
    assert rc == 2
    # Usage errors: bad rule / baseline rule without --baseline / none.
    assert obsctl.main(["watch", run, "--replay", "--rule", "mfu!!3"]) == 2
    assert obsctl.main(["watch", run, "--replay",
                        "--rule", "mfu<0.9*baseline"]) == 2
    assert obsctl.main(["watch", run, "--replay"]) == 2


def test_diff_gates_comm_signals(tmp_path):
    from tpu_dp.obs import obsctl

    _write_stream(tmp_path / "run", exposed_ms=0.6)
    eff = obsctl.run_efficiency(obsctl.RunArtifacts(tmp_path / "run"))
    assert eff["comm_ms"] == 2.0
    assert eff["exposed_comm_ms"] == 0.6
    assert eff["overlap_frac"] == 0.7
    # BENCH-style baseline with a comm block: exposed regression trips.
    bench = {"mfu": 0.5, "goodput": 0.95, "p95_ms": 10.0,
             "comm": {"comm_ms": 2.0, "exposed_comm_ms": 0.4,
                      "overlap_frac": 0.8}}
    p = tmp_path / "bench.json"
    p.write_text(json.dumps(bench))
    verdict = obsctl.diff_verdict(eff, obsctl.load_baseline(p), 0.1)
    bad = {c["signal"] for c in verdict["checks"]
           if c["verdict"] == "regressed"}
    assert "exposed_comm_ms" in bad and "overlap_frac" in bad
    # A run with no comm data skips the comm signals, never "0".
    plain = tmp_path / "plain"
    plain.mkdir()
    (plain / "metrics.jsonl").write_text(json.dumps(
        {"ts": "2026-08-01T10:00:01+00:00", "step": 1, "schema": 3,
         "mfu": 0.5, "goodput": 0.9, "spans": {"dispatch": 1.0}}) + "\n")
    eff2 = obsctl.run_efficiency(obsctl.RunArtifacts(plain))
    assert "comm_ms" not in eff2
    v2 = obsctl.diff_verdict(eff2, obsctl.load_baseline(p), 0.1)
    comm_checks = {c["signal"]: c["verdict"] for c in v2["checks"]}
    assert comm_checks["exposed_comm_ms"] == "skipped"


# --------------------------------------------------------------------------
# the CPU-backend end-to-end: capture -> parse -> reconcile -> gate
# --------------------------------------------------------------------------

@pytest.mark.skipif(not _has_xplane_proto(),
                    reason="TF xplane proto unavailable")
def test_inrun_comm_profile_sharded_reconciles(tmp_path):
    """The acceptance run: 8-device sharded update, in-run window [4, 6).

    The parsed breakdown must reconcile exactly with the program's own
    static collective schedule (reduce-scatter + all-gather + metric
    all-reduces, once per step per device), the wire bytes with
    quant.wire_report, and the gauges must land in every downstream
    surface: metrics records, the flight recorder, comm_report.json,
    obsctl diff, and obsctl watch (trip on an injected regression, exit
    0 clean).
    """
    import jax

    from tpu_dp.config import Config
    from tpu_dp.obs import flightrec, obsctl
    from tpu_dp.obs.commprof import read_comm_report
    from tpu_dp.train.trainer import Trainer

    cfg = Config()
    cfg.data.dataset = "synthetic"
    cfg.data.synthetic_train_size = 80
    cfg.data.synthetic_test_size = 16
    cfg.data.batch_size = 8
    cfg.data.device_resident = "off"
    cfg.train.epochs = 1
    cfg.train.eval_at_end = False
    cfg.train.steps_per_call = 1
    cfg.train.obs = "full"
    cfg.train.update_sharding = "sharded"
    cfg.train.ckpt_dir = str(tmp_path / "ck")
    cfg.obs.comm_profile_steps = "4:6"
    tr = Trainer(cfg)
    tr.fit()

    world = len(jax.devices())
    rep = read_comm_report(tr.obs_dir / "comm_report.json")
    assert rep["start_step"] == 4 and rep["end_step"] == 6
    assert rep["steps"] == 2 and rep["devices"] == world
    recon = rep["reconciliation"]
    assert recon["ok"], recon
    # The sharded update's schedule: reduce-scatter + all-gather groups
    # plus the two metric scalar all-reduces, exactly once per step.
    kinds = set(recon["by_kind"])
    assert {"reduce-scatter", "all-gather", "all-reduce"} <= kinds
    assert recon["by_kind"]["all-reduce"]["per_step_observed"] == 2.0
    assert recon["by_kind"]["reduce-scatter"]["per_step_observed"] == \
        recon["by_kind"]["reduce-scatter"]["per_step_expected"]
    # Wire bytes: schedule-derived == quant.wire_report's layout math.
    assert rep["wire"]["reconciliation"]["ok"], rep["wire"]
    assert rep["comm_ms"] > 0 and rep["compute_ms"] > 0
    assert rep["overlap_frac"] is not None

    # Schema-3 surfaces: the comm_profile event + the gauges in counter
    # snapshots of records written after the window.
    metrics = [json.loads(line) for line in
               (tmp_path / "ck" / "metrics.jsonl").read_text().splitlines()]
    events = [r for r in metrics if r.get("event") == "comm_profile"]
    assert len(events) == 1
    assert events[0]["reconciled"] is True
    assert events[0]["comm_ms"] == rep["comm_ms"]
    assert any("obs.comm_ms" in (r.get("counters") or {}) for r in metrics)

    # Flight recorder: the capture window is discoverable from artifacts.
    dump = flightrec.read_dump(
        sorted(tr.obs_dir.glob("flightrec_r*.json"))[0])
    kinds = [e["kind"] for e in dump["events"]]
    assert "profile_start" in kinds and "profile_stop" in kinds
    assert "comm_profile" in kinds

    # obsctl diff reads the comm signals from the run.
    eff = obsctl.run_efficiency(obsctl.RunArtifacts(tmp_path / "ck"))
    assert eff["exposed_comm_ms"] == rep["exposed_comm_ms"]

    # obsctl watch: exit 0 on the clean run, 1 on an injected
    # exposed-comm regression (the acceptance gate).
    base = tmp_path / "base.json"
    rc = obsctl.main(["diff", str(tmp_path / "ck"),
                      "--write-baseline", str(base)])
    assert rc == 0
    rc = obsctl.main(["watch", str(tmp_path / "ck"), "--replay",
                      "--baseline", str(base),
                      "--rule", "exposed_comm_ms>1.5*baseline",
                      "--rule", "goodput<0.5*baseline"])
    assert rc == 0
    tampered = tmp_path / "tampered.json"
    payload = json.loads(base.read_text())
    payload["exposed_comm_ms"] = rep["exposed_comm_ms"] / 100.0
    tampered.write_text(json.dumps(payload))
    rc = obsctl.main(["watch", str(tmp_path / "ck"), "--replay",
                      "--baseline", str(tampered),
                      "--rule", "exposed_comm_ms>1.5*baseline"])
    assert rc == 1

    # The timeline shows the whole story from artifacts alone.
    timeline = obsctl.build_timeline(obsctl.RunArtifacts(tmp_path / "ck"))
    tkinds = [e["kind"] for e in timeline["events"]]
    assert "profile_start" in tkinds and "comm_profile" in tkinds


# --------------------------------------------------------------------------
# serving capture parity
# --------------------------------------------------------------------------

@pytest.mark.skipif(not _has_xplane_proto(),
                    reason="TF xplane proto unavailable")
def test_serve_batch_ranged_capture(tmp_path):
    """`serve.profile_batches` arms the same StepProfiler window over
    batch indices: the replica's capture lands an xplane trace under its
    per-sid subdir, parseable by the same library, with the flightrec
    profile_start/profile_stop discoverability. The range is 0-based
    half-open over the documented batch indices — 0:1 captures exactly
    the first batch (an off-by-one here captured nothing at all)."""
    import numpy as np

    import jax
    from tpu_dp.models import build_model
    from tpu_dp.obs import flightrec, xplane
    from tpu_dp.serve import InferenceEngine
    from tpu_dp.train.state import create_train_state
    from tpu_dp.train.optim import SGD

    flightrec.recorder.reset()
    model = build_model("net")
    state = create_train_state(model, jax.random.PRNGKey(0),
                               np.zeros((1, 32, 32, 3), np.float32),
                               SGD(momentum=0.0))
    engine = InferenceEngine(
        model, state.params, buckets=(1,), slo_ms=10_000.0,
        profile_dir=str(tmp_path / "prof"), profile_batches=(0, 1),
    )
    engine.start()
    try:
        handles = [engine.submit(np.zeros((32, 32, 3), np.uint8))
                   for _ in range(3)]
        for h in handles:
            assert h.wait(timeout=60.0)
    finally:
        engine.stop()
    trace_root = tmp_path / "prof" / "r0"
    assert xplane.find_xplane(trace_root) is not None
    s = xplane.summarize(trace_root)
    assert s["ops"], "capture window recorded no op events"
    kinds = [e["kind"] for e in flightrec.recorder.events()
             if e["kind"].startswith("profile_")]
    assert "profile_start" in kinds and "profile_stop" in kinds
    flightrec.recorder.reset()
