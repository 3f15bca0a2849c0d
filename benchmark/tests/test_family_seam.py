"""The seam between the harness and a configuration's family.

`run.py`, `loop_hook.py` and `calibrate.py` know no model: what the data,
the weights, the first gradient, the reference and the required work are
comes from ``families/<family>.py``, found by the ``family`` key of the
configuration's file. Here a family the harness has never named goes
through `run.run` whole, the loader's errors name what is missing, the item
count of a batch row reaches the throughput and `step_mfu`, the hook reads
the first gradient through whatever function the family gives it, and the
data set reaches the trainer row-major with the bytes it always had.
"""

import hashlib
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import datagen
import run
from compare import compare
from loop_hook import FOLLOWED_STEPS, LoopHook
from tpu_dp.train.hooks import StepEvent
from work import load_peaks

TESTS = Path(__file__).resolve().parent
BENCH = json.loads((run.REPO / "BENCHMARK.json").read_text())
SEED = 2147483777


# ------------------------------------------------ (a) a family never named

def lenet_cell():
    """A cell as `load_cell` would hand it over, built here: the program's
    LeNet at its reference preset, float32, plain SGD with momentum."""
    family = run.check_family(
        run.load_module("bench_family_lenet", TESTS / "family_lenet.py"),
        TESTS / "family_lenet.py")
    return {
        "name": "lenet-b32", "config": "lenet-cifar", "traffic": "b32",
        "chips": 1, "family": family,
        "config_file": {
            "name": "lenet-cifar", "family": "lenet",
            "argv": ["--preset=reference", "--model.name=net"],
            "precision": {"compute": "float32"},
            "optimizer": {"name": "sgd", "lr": 0.001, "momentum": 0.9},
        },
        "traffic_file": {"batch_per_chip": 32, "train_size": 32 * 5,
                         "argv": []},
        # float32 on both sides: rounding alone lies between them
        "limits": {"loss": 1e-4, "grad1": 1e-3, "grad1_median": 1e-3,
                   "delta": 1e-3},
        "end_to_end": BENCH["end_to_end"],
        "per_layer": [m for m in BENCH["per_layer"] if "workloads" not in m],
    }


@pytest.mark.parametrize("trace, would_report", [
    (False, ["setup_s", "step_ms_p95", "throughput_per_chip"]),
    # what a traced ResNet rehearsal lists on the CPU, where no device
    # plane is traced (`test_loop_metrics.py`)
    (True, ["data_wait_ms", "data_wait_ms_p95", "dispatch_idle_share",
            "epoch_gap_ms", "host_step_ms", "inflight_steps"]),
])
def test_a_family_never_named_runs_whole(trace, would_report):
    result = run.run(lenet_cell(), SEED, 0.01, trace,
                     rehearsal={"batch_per_chip": 32})
    assert result["correct"] is True, result["compared"]
    assert result["would_report"] == would_report
    assert result["attempted"] == 5 and result["failed"] == 0
    assert [r["name"] for r in result["compared"]] == [
        "loss_step1", "loss_step2", "loss_step3", "grad1_worst_leaf",
        "grad1_median_leaf", "delta3_worst_leaf"]
    assert result["window"]["items_per_step"] == 32


def test_the_never_named_familys_fault_is_not_correct():
    cell = lenet_cell()
    job = run.job_of(cell, SEED)
    family = cell["family"]
    ref = family.reference_readings(job, FOLLOWED_STEPS)
    (fault,) = family.variants(job)["faults"]
    got = family.reference_readings(job, FOLLOWED_STEPS, fault=fault)
    correct, rows = compare(got, ref, cell["limits"])
    assert correct is False, rows


# ------------------------------------------------- (b) the loader's errors

def test_a_configuration_without_family_names_the_key():
    with pytest.raises(run.Refused, match="configs/x.json has no key 'family'"):
        run.load_family({"name": "x"}, "configs/x.json")


def test_a_family_without_a_file_names_the_path():
    with pytest.raises(run.Refused, match=r"benchmark/families/nope\.py"):
        run.load_family({"family": "nope"}, "configs/x.json")


def test_a_family_that_leaves_a_question_open_is_refused():
    half = types.SimpleNamespace(items_per_row=lambda job: 1)
    with pytest.raises(run.Refused, match="first_gradient"):
        run.check_family(half, "families/half.py")


def test_every_declared_cell_finds_its_family():
    for workload in BENCH["workloads"]:
        cell = run.load_cell(workload["name"])
        assert cell["family"].__name__ == "bench_family_resnet"
        assert cell["family"].items_per_row(run.job_of(cell, SEED)) == 1


# ------------------------------------------ (c) the items of a batch row

def test_items_per_row_reaches_throughput_and_step_mfu():
    family = types.SimpleNamespace(
        items_per_row=lambda job: 4096,
        train_flops_per_item=lambda job: 1.0e6,
        least_step_seconds=lambda job, peaks: {"seconds": 1e-3})
    job = run.Job(config={}, traffic={}, seed=1, chips=2, batch_per_chip=8,
                  train_size=64)
    peaks = load_peaks("TPU v5 lite")
    window = {"steps": 10, "seconds": 4.0, "gaps_ms": [400.0] * 10,
              "setup_s": 1.0}
    cell = {"end_to_end": BENCH["end_to_end"],
            "per_layer": [m for m in BENCH["per_layer"]
                          if m["name"] == "step_mfu"]}
    items, metrics = run.window_metrics(cell, family, job, peaks, window,
                                        trace=False)
    rows_rate = 10 * 16 / 4.0 / 2
    assert items == 16 * 4096
    assert metrics["throughput_per_chip"]["value"] == 4096 * rows_rate
    assert metrics["step_ms_p95"]["value"] == 400.0

    reduced = {"devices": [{}], "fullest": {"steps": 5, "window_s": 2.0}}
    _, traced = run.window_metrics(cell, family, job, peaks, window,
                                   trace=True, reduced=reduced)
    flops = 1.0e6 * (16 * 4096) * 5
    assert traced["step_mfu"]["value"] == pytest.approx(
        100.0 * flops / (2.0 * peaks["bf16_flops_per_s"] * 2))


# ------------------------------- (d) the first gradient, another form

def test_hook_reads_the_first_gradient_through_the_familys_function():
    """An optimizer of Adam's kind keeps ``m = (1 - beta1) * g`` after its
    first step: the family's function is ``m / (1 - beta1)``, and that is
    what the hook reports, whatever the state's tree looks like."""
    beta1 = 0.9
    grads = {"a": {"kernel": jnp.arange(6.0).reshape(2, 3)},
             "b": {"bias": jnp.asarray([3.0, 4.0])}}
    params = jax.tree_util.tree_map(jnp.ones_like, grads)
    state = types.SimpleNamespace(params=params, opt_state=None)
    trainer = types.SimpleNamespace(state=state)

    def first_gradient(opt_state, params0):
        return jax.tree_util.tree_map(lambda m: m / (1.0 - beta1),
                                      opt_state["m"])

    hook = LoopHook(trainer, first_gradient, steps_per_epoch=4)
    hook.capture_initial()
    hook.on_epoch_start(0)
    for k in range(FOLLOWED_STEPS):
        hook.on_window_start(k, 1)
        if k == 0:
            state.opt_state = {
                "count": jnp.int32(1),
                "m": jax.tree_util.tree_map(lambda g: (1.0 - beta1) * g,
                                            grads)}
        state.params = jax.tree_util.tree_map(lambda p: p - 0.5, state.params)
        hook.on_step_end(StepEvent(epoch=0, done=k + 1, n=1,
                                   window=({"loss": jnp.float32(2.0 - k)},)))
    hook.finish()
    got = hook.first_steps()
    assert got["loss"] == [2.0, 1.0, 0.0]
    assert got["grad1"] == pytest.approx(
        {"a/kernel": float(np.sqrt(55.0)), "b/bias": 5.0}, rel=1e-6)
    assert got["delta"] == pytest.approx(
        {"a/kernel": 1.5 * np.sqrt(6.0), "b/bias": 1.5 * np.sqrt(2.0)},
        rel=1e-6)


# ----------------------------------------- (e) the data set's layout

def test_make_dataset_is_row_major_with_the_bytes_it_always_had():
    n = 40000
    images, labels = datagen.make_dataset(SEED, n, 10)
    assert images.shape == (n, 32, 32, 3) and images.dtype == np.uint8
    assert images.flags.c_contiguous and labels.flags.c_contiguous
    on_device = datagen.device_dataset(SEED, n, 10)
    assert np.array_equal(images, np.asarray(on_device[0]))
    assert np.array_equal(labels, np.asarray(on_device[1]))
    # sha1 of the first chunk's bytes in row-major order, as the parent's
    # `make_dataset` (commit 66e344a) made them on the CPU backend
    first = hashlib.sha1(images[:datagen.CHUNK].tobytes()).hexdigest()
    assert first == "089065d2cf9acafb44ee2cd1c627e868b5a0d40f"
