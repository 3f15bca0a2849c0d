"""The loop's own metrics: the readers' arithmetic, what they give where the
program has nothing to read, and a traced rehearsal that reports them."""

import json

import pytest

import rehearse
import run
from tpu_dp.obs.counters import counters

NEW = ["dispatch_idle_share", "epoch_gap_ms", "host_step_ms",
       "inflight_steps"]


@pytest.fixture()
def registry():
    saved = dict(counters._counts), dict(counters._gauges)
    counters.reset()
    yield counters
    counters.reset()
    counters._counts.update(saved[0])
    counters._gauges.update(saved[1])


def step(**ms):
    base = {"data_wait": 1.0, "pre_dispatch": 0.1, "dispatch": 2.0,
            "telemetry": 0.2, "accumulate": 0.3, "hooks": 0.4}
    return {**base, **ms}


def test_span_sum_is_a_statistic_of_the_steps_sums():
    from metrics.readers import span_sum

    names = ["pre_dispatch", "telemetry", "accumulate", "hooks"]
    ctx = {"spans": [step(), step(hooks=1.4), step(epoch_gap=9.0),
                     step(hooks=9000.4)]}   # the harness's stop_trace
    assert span_sum.read(ctx, names, "mean") == pytest.approx(
        (1.0 + 2.0 + 1.0 + 9001.0) / 4)
    assert span_sum.read(ctx, names, "p50") == pytest.approx(1.5)
    assert span_sum.read({"spans": ctx["spans"][:3]}, names,
                         "p50") == pytest.approx(1.0)
    # The metric is the median: one step's outlier does not move it.
    assert run.read_metric("host_step_ms", ctx) == pytest.approx(1.5)
    with pytest.raises(ValueError):
        span_sum.read(ctx, names, "p17")


@pytest.mark.parametrize("spans", [
    [],
    [{"data_wait": 1.0, "dispatch": 2.0}] * 3,   # the parent's records
    [{k: v for k, v in step().items() if k != "telemetry"}],
])
def test_span_sum_without_its_spans_reads_nothing(spans):
    assert run.read_metric("host_step_ms", {"spans": spans}) is None


def test_span_sum_counts_only_the_steps_that_have_them_all():
    ctx = {"spans": [step(), {"guard_rollback": 0.0}]}
    assert run.read_metric("host_step_ms", ctx) == pytest.approx(1.0)


def test_epoch_gap_is_the_mean_over_the_epochs_that_have_one():
    ctx = {"spans": [step(), step(epoch_gap=6.0), step(),
                     step(epoch_gap=8.0)]}
    assert run.read_metric("epoch_gap_ms", ctx) == pytest.approx(7.0)
    assert run.read_metric("epoch_gap_ms", {"spans": [step()]}) is None


def test_counter_ratio_reads_the_programs_registry(registry):
    registry.inc("loop.dispatches", 40)
    registry.inc("loop.dispatch_onto_idle", 10)
    registry.inc("loop.inflight_sum", 100)
    assert run.read_metric("dispatch_idle_share", {}) == pytest.approx(25.0)
    assert run.read_metric("inflight_steps", {}) == pytest.approx(2.5)


def test_counter_ratio_never_idle_is_nought_not_nothing(registry):
    registry.inc("loop.dispatches", 40)
    registry.inc("loop.dispatch_onto_idle", 0)
    assert run.read_metric("dispatch_idle_share", {}) == 0.0


@pytest.mark.parametrize("counts", [
    {},                                                 # the parent
    {"loop.dispatches": 0, "loop.dispatch_onto_idle": 0},
    {"loop.dispatches": 5},                             # no numerator
])
def test_counter_ratio_without_its_counters_reads_nothing(registry, counts):
    for name, value in counts.items():
        registry.inc(name, value)
    assert run.read_metric("dispatch_idle_share", {}) is None


def test_new_metrics_are_declared_for_every_cell():
    bench = json.loads((run.REPO / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec = json.loads(
            (run.HERE / "metrics" / f"{name}.json").read_text())
        entry = declared[name]
        assert "workloads" not in entry and entry["layer"] == "loop"
        for key in ("unit", "better", "source", "moves", "layer"):
            assert entry[key] == spec[key], (name, key)
    assert [m["name"] for m in bench["per_layer"]][-4:] == [
        "host_step_ms", "epoch_gap_ms", "dispatch_idle_share",
        "inflight_steps"]


def test_traced_rehearsal_would_report_the_loops_metrics(capsys):
    """`rehearse.py --trace 1`, two epochs of four steps on the CPU: the
    line lists the four beside the metrics the cell reported before."""
    assert rehearse.main(["--workload", "r18-b4096-resident", "--trace", "1",
                          "--seconds", "0.01"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert set(NEW) <= set(line["would_report"]), line["would_report"]
    assert {"data_wait_ms", "data_wait_ms_p95"} <= set(line["would_report"])
