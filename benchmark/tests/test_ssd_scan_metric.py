"""`ssd_scan_roofline`: declared for the hybrid cell alone, read by the
benchmark's own `kernel_roofline` from a data file, over ranked operations
that hold the scan's kernel pair, and that do not."""

import json

import pytest

import run
import work_nemotron_h
from work import load_peaks

CELL = "nemotron-ep16-s8192-b2"
BENCH = json.loads((run.REPO / "BENCHMARK.json").read_text())


def least():
    """Four Mamba-2 layers over 2 x 8,192 tokens: x and y 4,096 wide, B and
    C 1,024 each and delta 64, at 2 bytes an element, five times over (in
    and out forward; in, dy and the gradients out backward, less the one y):
    3.51 GB, 4.29 ms at 819 GB/s, twelve times the products' time."""
    config = run.load_cell(CELL)["config_file"]
    seconds = work_nemotron_h.ssd_least_seconds(
        config, 8192, 2, load_peaks("TPU v5 lite"))
    ins, out = 4096 + 2 * 1024 + 64, 4096
    assert seconds == pytest.approx(
        2.0 * (3 * ins + 2 * out) * 16384 * 4 / 819e9)
    assert seconds == pytest.approx(4.29e-3, rel=2e-3)
    return seconds


def test_the_metric_is_declared_for_the_hybrid_cell_alone():
    entry = [m for m in BENCH["per_layer"]
             if m["name"] == "ssd_scan_roofline"]
    assert entry == [{
        "name": "ssd_scan_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "throughput_per_chip", "workloads": [CELL]}]
    names = [m["name"] for m in run.load_cell(CELL)["per_layer"]]
    assert "ssd_scan_roofline" in names
    for other in ("sdar-ep8-s4096-b4", "r18-b4096-resident"):
        assert "ssd_scan_roofline" not in [
            m["name"] for m in run.load_cell(other)["per_layer"]]
    # a data file on the reader the benchmark has: no reader code of its own
    spec = json.loads((run.REPO / "benchmark" / "metrics"
                       / "ssd_scan_roofline.json").read_text())
    assert spec["reader"] == "kernel_roofline"
    assert spec["args"] == {"kernels": ["ssd_scan"],
                            "work": "work_nemotron_h",
                            "function": "ssd_least_seconds"}


def ctx_with(ops, steps=11):
    cell = run.load_cell(CELL)
    return {"trace": {"devices": [{}],
                      "fullest": {"steps": steps, "device_ops": ops}},
            "items_per_step": 2 * 8192, "global_batch": 2,
            "batch_per_chip": 2, "config": cell["config_file"],
            "peaks": load_peaks("TPU v5 lite")}


@pytest.mark.parametrize("ops", [
    # the program's forward and backward kernels carry one name
    [["fusion", 2.4], ["ragged-dot-none", 1.38], ["ssd_scan_pair", 0.33],
     ["splash_mqa_causal_pair", 0.26]],
    # kernels named apart are summed by their common prefix
    [["fusion", 2.4], ["ssd_scan_bwd", 0.18], ["ragged-dot-none", 1.38],
     ["ssd_scan_fwd", 0.15]],
], ids=["one_name", "two_names"])
def test_the_reader_sums_the_kernels_over_the_traced_steps(ops):
    got = run.read_metric("ssd_scan_roofline", ctx_with(ops))
    assert got == pytest.approx(100 * least() * 11 / 0.33)
    assert 0 < got < 100


@pytest.mark.parametrize("trace", [
    # the parent's program: the scan is the compiler's fusions
    {"devices": [{}], "fullest": {"steps": 11, "device_ops": [
        ["fusion", 3.2], ["ragged-dot-none", 1.38],
        ["reduce_window_sum", 0.198], ["splash_mqa_causal_pair", 0.26]]}},
    {"devices": []},
    None,
], ids=["a_program_without_the_kernels", "no_device_plane", "no_trace"])
def test_nothing_where_the_kernels_are_not_ranked(trace):
    """The reader returns None and does not raise, and the line leaves the
    metric out."""
    ctx = ctx_with([])
    ctx["trace"] = trace
    assert run.read_metric("ssd_scan_roofline", ctx) is None
