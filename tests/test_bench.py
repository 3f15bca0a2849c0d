"""bench.py harness logic — the parts that run without an accelerator.

The measurement itself needs a chip; what these tests pin down is the
harness around it: device-kind→peak mapping, FLOPs resolution, the archive
row format, subprocess output parsing, the sweep grids — and that a run
which finds no chip fails instead of answering with an archived number.
"""

import json

import pytest

import bench


def test_peak_flops_known_kinds():
    assert bench.peak_flops("TPU v5 lite") == 197e12
    assert bench.peak_flops("TPU v5e") == 197e12
    assert bench.peak_flops("TPU v5p") == 459e12
    assert bench.peak_flops("TPU v4") == 275e12
    assert bench.peak_flops("TPU v3") == 123e12
    assert bench.peak_flops("TPU v6 lite") == 918e12


def test_peak_flops_v5_lite_not_misread_as_v5p():
    # Substring order matters: "v5 lite" must match before bare "v5".
    assert bench.peak_flops("tpu v5 lite") == 197e12


def test_peak_flops_unknown_is_none():
    assert bench.peak_flops("cpu") is None
    assert bench.peak_flops("Graphcore IPU") is None


# ---- FLOPs resolution (the round-2 30x MFU bug, VERDICT r2 weak #1) ----
# At b2048 the true per-step figure is ~5.97e12 (measured w1 on the real
# chip); the buggy path divided the scan body's cost by the window again
# and published 1.99e11. These tests mock the cost-analysis inputs.

B2048_TRUE = bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE * 2048


def test_resolve_prefers_w1_step_cost():
    # When the loop-free step's cost is available it wins outright — the
    # scanned program's ambiguous number must not even be consulted.
    f, source, check = bench.resolve_flops_per_step(
        program_flops=B2048_TRUE, step_flops=5.97e12, window=30,
        per_chip_batch=2048,
        flops_per_image=bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE)
    assert f == 5.97e12 and source == "w1_step_cost_analysis" and check == "ok"


def test_resolve_scan_body_only_semantics_not_divided():
    # jaxlib reports the scan BODY once: dividing by window again is the
    # round-2 bug. Body reading is log-closer to analytic => keep as-is.
    f, source, check = bench.resolve_flops_per_step(
        program_flops=5.97e12, step_flops=None, window=30, per_chip_batch=2048,
        flops_per_image=bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE)
    assert f == 5.97e12
    assert source == "scan_cost_analysis_body" and check == "ok"


def test_resolve_scan_multiplied_semantics_divided():
    # A jaxlib that DOES multiply by trip count must be divided back down.
    f, source, check = bench.resolve_flops_per_step(
        program_flops=30 * 5.97e12, step_flops=None, window=30,
        per_chip_batch=2048,
        flops_per_image=bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE)
    assert f == 5.97e12
    assert source == "scan_cost_analysis_divided" and check == "ok"


def test_resolve_analytic_fallback():
    f, source, check = bench.resolve_flops_per_step(
        program_flops=None, step_flops=None, window=30, per_chip_batch=1024,
        flops_per_image=bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE)
    assert f == bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE * 1024
    assert source == "analytic" and check == "unverified"


def test_resolve_flags_mismatch_with_analytic():
    # A cost number 30x off analytic (the exact round-2 failure magnitude,
    # had it come from the step path) must be flagged, never silent.
    f, source, check = bench.resolve_flops_per_step(
        program_flops=None, step_flops=5.97e12 / 30, window=1,
        per_chip_batch=2048,
        flops_per_image=bench.RESNET18_CIFAR_TRAIN_FLOPS_PER_IMAGE)
    assert check.startswith("mismatch:")


def _write_archive(tmp_path, records):
    p = tmp_path / "results.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in records))
    return p


@pytest.mark.parametrize("probe", [
    (None, "probe timeout after 120s"),
    ({"n_devices": 8, "device_kind": "cpu", "backend": "cpu"}, ""),
], ids=["no-device", "cpu-only"])
def test_no_chip_exits_nonzero_and_never_answers_from_the_archive(
        tmp_path, monkeypatch, capsys, probe):
    """A measurement path that finds no chip fails: non-zero exit, one
    structured failure line, and no archived number in its place — even
    with a perfectly good accelerator row in the archive."""
    p = _write_archive(tmp_path, [
        {"metric": bench.METRIC, "value": 33000.0, "unit": bench.UNIT,
         "vs_baseline": 13.2, "backend": "tpu", "ts": "2026-07-31T00:00:00Z"},
    ])
    monkeypatch.setattr(bench, "RESULTS_PATH", p)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "probe_device", lambda *a, **k: probe)
    monkeypatch.setattr(bench, "run_point", lambda *a, **k: pytest.fail(
        "measured without a chip"))
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "stale" not in out and "33000" not in out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["value"] is None and "device unavailable" in rec["error"]


def test_bench_source_has_no_stale_path():
    src = open(bench.__file__).read()
    assert "stale" not in src and "last_good_archived" not in src


def test_metric_for_models():
    assert bench.metric_for("resnet18", 10) == bench.METRIC
    assert (bench.metric_for("resnet50", 100)
            == "cifar100_resnet50_train_images_per_sec_per_chip")
    # Each supported model carries a plausible analytic count (R50 does
    # ~2.3x the conv FLOPs of R18 on CIFAR shapes).
    r18, r50 = bench.MODEL_SPECS["resnet18"][0], bench.MODEL_SPECS["resnet50"][0]
    assert 2.0 < r50 / r18 < 2.7


def test_archive_appends_with_schema_and_config_hash(tmp_path, monkeypatch):
    from tpu_dp.tune.profile import config_hash

    p = tmp_path / "nested" / "results.jsonl"
    monkeypatch.setattr(bench, "RESULTS_PATH", p)
    bench.archive({"a": 1})
    bench.archive({"b": 2, "config": {"bucket_mb": 1.0}})
    rows = [json.loads(x) for x in p.read_text().splitlines()]
    assert [r["a" if "a" in r else "b"] for r in rows] == [1, 2]
    # Every archived row is stamped with the archive schema version and
    # the canonical digest of its own config block, so trial rows, BENCH
    # emissions, and tuned.json profiles join on one key.
    assert [r["schema"] for r in rows] == [bench.ARCHIVE_SCHEMA] * 2
    assert rows[0]["config_hash"] == config_hash({})
    assert rows[1]["config_hash"] == config_hash({"bucket_mb": 1.0})


def test_run_point_reports_child_failure(monkeypatch):
    # A child that dies without emitting JSON must yield a structured error
    # record, not an exception.
    monkeypatch.setattr(
        bench, "_run_sub", lambda argv, t, env=None: (1, "noise\n", "boom")
    )
    rec = bench.run_point({"per_chip_batch": 8}, timeout_s=5)
    assert rec["value"] is None
    assert "rc=1" in rec["error"] and "boom" in rec["error"]


def test_run_point_parses_last_json_line(monkeypatch):
    payload = {"metric": bench.METRIC, "value": 123.0, "unit": bench.UNIT,
               "vs_baseline": 0.05}
    out = "bench: chatter\n" + json.dumps(payload) + "\n"
    monkeypatch.setattr(
        bench, "_run_sub", lambda argv, t, env=None: (0, out, "")
    )
    assert bench.run_point({}, timeout_s=5) == payload


def test_run_point_timeout(monkeypatch):
    monkeypatch.setattr(
        bench, "_run_sub", lambda argv, t, env=None: (124, "", "")
    )
    rec = bench.run_point({}, timeout_s=7)
    assert rec["value"] is None and "timeout" in rec["error"]


def test_archive_tags_cpu_backend_as_smoke(tmp_path, monkeypatch):
    # CPU-backend rows are outage-time harness smoke tests under a TPU
    # metric name; archive() must tag them so archive consumers don't need
    # to know the backend convention (docs/DESIGN.md "Benchmarking
    # honestly"). Accelerator rows must stay untagged.
    p = tmp_path / "results.jsonl"
    monkeypatch.setattr(bench, "RESULTS_PATH", p)
    bench.archive({"value": 9.9, "backend": "cpu"})
    bench.archive({"value": 34000.0, "backend": "tpu"})
    rows = [json.loads(x) for x in p.read_text().splitlines()]
    assert rows[0]["smoke"] is True
    assert "smoke" not in rows[1]


def test_fused_sweep_grid_covers_both_windows(monkeypatch, capsys):
    # The fused-variant verdict must include the headline operating point
    # (w30 scanned windows), not just the w1 dispatch-bound comparison
    # (VERDICT r3 weak #4): 5 variants x 2 windows.
    grids = []

    def fake_run_point(cfg, timeout_s):
        grids.append(cfg)
        return {"value": 1.0, "unit": bench.UNIT, "vs_baseline": 0.0,
                "metric": bench.METRIC, "config": cfg}

    monkeypatch.setattr(bench, "probe_device", lambda *a, **k: (
        {"n_devices": 1, "device_kind": "x", "backend": "tpu"}, None))
    monkeypatch.setattr(bench, "run_point", fake_run_point)
    monkeypatch.setattr(bench, "archive", lambda r: None)
    monkeypatch.setattr(bench.sys, "argv", ["bench.py", "--sweep-fused"])
    bench.main()
    capsys.readouterr()  # swallow the emitted headline line
    pts = {(g["fused_stages"], g["fused_bwd"], g["steps_per_call"])
           for g in grids}
    variants = {("", False), ("0", False), ("all", False),
                ("0", True), ("all", True)}
    assert pts == {(fs, fb, w) for fs, fb in variants for w in (1, 30)}
    assert len(grids) == 10


def test_latency_steps_recorded_in_grid(monkeypatch, capsys):
    """--latency-steps flows into every grid point's config, so
    measure_point runs the fenced per-step latency pass (the 'latency'
    p50/p95/p99 block that distinguishes tail from mean regressions —
    docs/OBSERVABILITY.md)."""
    grids = []

    def fake_run_point(cfg, timeout_s):
        grids.append(cfg)
        return {"value": 1.0, "unit": bench.UNIT, "vs_baseline": 0.0,
                "metric": bench.METRIC, "config": cfg}

    monkeypatch.setattr(bench, "probe_device", lambda *a, **k: (
        {"n_devices": 1, "device_kind": "x", "backend": "tpu"}, None))
    monkeypatch.setattr(bench, "run_point", fake_run_point)
    monkeypatch.setattr(bench, "archive", lambda r: None)
    monkeypatch.setattr(bench.sys, "argv",
                        ["bench.py", "--latency-steps", "7"])
    bench.main()
    capsys.readouterr()
    assert grids and all(g["latency_steps"] == 7 for g in grids)


def test_update_sharding_recorded_in_grid(monkeypatch, capsys):
    """--update-sharding flows into every grid point's config (and from
    there into the BENCH json config block via measure_point)."""
    grids = []

    def fake_run_point(cfg, timeout_s):
        grids.append(cfg)
        return {"value": 1.0, "unit": bench.UNIT, "vs_baseline": 0.0,
                "metric": bench.METRIC, "config": cfg}

    monkeypatch.setattr(bench, "probe_device", lambda *a, **k: (
        {"n_devices": 1, "device_kind": "x", "backend": "tpu"}, None))
    monkeypatch.setattr(bench, "run_point", fake_run_point)
    monkeypatch.setattr(bench, "archive", lambda r: None)
    monkeypatch.setattr(bench.sys, "argv",
                        ["bench.py", "--update-sharding", "sharded"])
    bench.main()
    capsys.readouterr()
    assert grids and all(g["update_sharding"] == "sharded" for g in grids)
