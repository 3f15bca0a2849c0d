"""`work.py` against the counts the plan was made from."""

import json
from pathlib import Path

import pytest

import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name, macs, params, flops", [
    # 555.4M MACs and 3.33e9 FLOPs; 1,298.0M MACs (1,297.8M before the
    # 100-way head) and 7.78e9 FLOPs.
    ("resnet18-cifar", 555_422_720, 11_173_962, 3_328_997_376),
    ("resnet50-cifar", 1_298_014_208, 23_705_252, 7_784_546_304),
])
def test_counts(name, macs, params, flops):
    m = model(name)
    assert work.macs_per_item(m) == macs
    assert work.parameters(m) == params == m["parameters"]
    assert work.train_flops_per_item(m) == flops
    # forward 2 x MACs, backward twice that, less the stem's input gradient
    stem = work.contractions(m)[0]
    assert flops == 6 * macs - 2 * stem.macs


def test_least_time_is_a_lower_bound_on_both_sides():
    m = model("resnet18-cifar")
    least = work.least_step_seconds(m, 4096, "bfloat16", 197e12, 819e9)
    by_flops = work.train_flops_per_item(m) * 4096 / 197e12
    assert least["seconds"] >= by_flops
    assert 0.0 < least["bandwidth_bound_seconds"] < least["seconds"]
    # twice the batch, twice the time where nothing is bound by the weights
    twice = work.least_step_seconds(m, 8192, "bfloat16", 197e12, 819e9)
    assert twice["seconds"] <= 2 * least["seconds"]


def test_unknown_device_kind_raises():
    assert work.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        work.load_peaks("TPU v9 imaginary")
