"""The `sdar` family through the harness, at a tiny size on the CPU.

A cell built here as `test_family_seam.py` builds its LeNet's, with the
`sdar` family's own file and tiny shapes in the configuration: through
`run.run` whole (the program's trainer, the hook, the plain reference, the
comparison), every fault the family knows read outside a limit, the
declared cell's files found by name, and `work_sdar.py` against a count by
hand.
"""

import json

import pytest

import run
import work_sdar
from compare import compare
from loop_hook import FOLLOWED_STEPS
from work import load_peaks

BENCH = json.loads((run.REPO / "BENCHMARK.json").read_text())
SEED = 2147483777
LENGTH, ROWS = 32, 4

SHAPES = {"hidden_size": 64, "num_layers": 2, "num_heads": 4,
          "num_kv_heads": 2, "head_dim": 16, "expert_width": 32,
          "num_experts": 8, "experts_per_token": 2, "experts_held": 4,
          "num_classes": 64, "bf16": "false"}


def sdar_cell():
    """A cell as `load_cell` would hand it over: the family's file, the
    published config's keys at tiny values, float32 on both sides."""
    path = run.HERE / "families" / "sdar.py"
    family = run.check_family(run.load_module("bench_family_sdar", path), path)
    config = {
        "name": "sdar-tiny", "family": "sdar",
        "argv": ["--preset=sdar_30b_a3b_ep8", "--optim.lr=0.001"]
        + [f"--model.{k}={v}" for k, v in SHAPES.items()],
        "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 32, "router_experts": 8, "num_experts": 4,
        "num_experts_per_tok": 2, "norm_topk_prob": True, "rope_theta": 1e6,
        "rms_norm_eps": 1e-6, "vocab_size": 64, "block_length": 4,
        "mask_token_id": 63, "share_index": 0, "init_std": 0.02,
        "precision": {"compute": "float32"},
        "optimizer": {"name": "adamw", "lr": 0.001, "b1": 0.9, "b2": 0.95,
                      "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0},
    }
    return {
        "name": "sdar-tiny-s32-b4", "config": "sdar-tiny",
        "traffic": "s32-b4", "chips": 1, "family": family,
        "config_file": config,
        "traffic_file": {"batch_per_chip": ROWS, "train_size": ROWS * 4,
                         "seq_len": LENGTH,
                         "argv": [f"--data.seq_len={LENGTH}"]},
        # float32 on both sides: rounding alone lies between them
        "limits": {"loss": 1e-4, "grad1": 1e-3, "grad1_median": 1e-3,
                   "delta": 1e-3},
        "end_to_end": BENCH["end_to_end"],
        "per_layer": [m for m in BENCH["per_layer"]
                      if "workloads" not in m
                      or "sdar-ep8-s4096-b4" in m["workloads"]],
    }


@pytest.mark.parametrize("trace, would_report", [
    (False, ["setup_s", "step_ms_p95", "throughput_per_chip"]),
    # on the CPU no device plane is traced; what the loop and the
    # program's counters give is all there
    (True, ["data_wait_ms", "data_wait_ms_p95", "dispatch_idle_share",
            "epoch_gap_ms", "host_step_ms", "inflight_steps",
            "moe_held_share", "moe_load_imbalance"]),  # no kernel's share
])
def test_the_sdar_family_runs_whole(trace, would_report):
    from tpu_dp.obs.counters import counters

    counters.reset()
    result = run.run(sdar_cell(), SEED, 0.01, trace,
                     rehearsal={"batch_per_chip": ROWS})
    assert result["correct"] is True, result["compared"]
    assert result["would_report"] == would_report
    assert result["attempted"] == 4 and result["failed"] == 0
    assert [r["name"] for r in result["compared"]] == [
        "loss_step1", "loss_step2", "loss_step3", "grad1_worst_leaf",
        "grad1_median_leaf", "delta3_worst_leaf"]
    assert result["window"]["items_per_step"] == ROWS * LENGTH
    counts = counters.snapshot()
    assert counts["moe.assignments_dropped"] == 0
    assert counts["diffusion.tokens"] == 2 * 4 * ROWS * LENGTH  # two epochs


@pytest.fixture(scope="module")
def sound():
    cell = sdar_cell()
    job = run.job_of(cell, SEED)
    return cell, job, cell["family"].reference_readings(job, FOLLOWED_STEPS)


@pytest.mark.parametrize("variant", ["float8", "half_batch", "causal_mask",
                                     "no_topk_renorm", "capacity_drop"])
def test_the_control_and_every_fault_read_outside_a_limit(sound, variant):
    cell, job, ref = sound
    family = cell["family"]
    known = family.variants(job)
    assert known["precisions"][0] == "float8"
    assert variant in known["precisions"] + known["faults"]
    kind = "precision" if variant in known["precisions"] else "fault"
    got = family.reference_readings(job, FOLLOWED_STEPS, **{kind: variant})
    correct, rows = compare(got, ref, cell["limits"])
    assert correct is False, rows


def test_the_reference_read_twice_is_the_same(sound):
    cell, job, ref = sound
    again = cell["family"].reference_readings(job, FOLLOWED_STEPS)
    assert compare(again, ref, cell["limits"])[0] is True
    assert again["loss"] == ref["loss"]


def test_the_declared_cell_finds_its_files():
    cell = run.load_cell("sdar-ep8-s4096-b4")
    assert cell["family"].__name__ == "bench_family_sdar"
    job = run.job_of(cell, SEED)
    assert cell["family"].items_per_row(job) == 4096
    assert (job.global_batch, job.steps_per_epoch) == (4, 32)
    names = [m["name"] for m in cell["per_layer"]]
    assert "moe_load_imbalance" in names and "moe_held_share" in names
    assert "collective_ms" not in names and "step_mfu" in names
    config = cell["config_file"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["parameters"] == work_sdar.parameters(config)
    assert set(cell["limits"]) == {"loss", "grad1", "grad1_median", "delta"}


def test_required_work_against_a_count_by_hand():
    """The published widths, 4 layers, 16 of 128 experts, 18,992 rows of
    vocabulary, rows of 4,096 tokens in blocks of 4."""
    config = run.load_cell("sdar-ep8-s4096-b4")["config_file"]
    parts = work_sdar.forward_flops_per_position_layer(config, 4096)
    # q 2048x4096, k and v 2048x512, o 4096x2048, 2 x MACs
    assert parts["projections"] == 2 * (2048 * 4096 + 2 * 2048 * 512
                                        + 4096 * 2048) == 37_748_736
    # 1,024 blocks: a position sees 4 * 1,025 / 2 = 2,050 keys on average,
    # at two products of 128 for each of 32 heads
    assert parts["scores_and_values"] == 2050 * 2 * 2 * 128 * 32 == 33_587_200
    assert parts["router"] == 2 * 2048 * 128
    # 8 x 16 / 128 = one expert term a position: three products of 2048x768
    assert parts["experts"] == 3 * 2 * 2048 * 768 == 9_437_184
    layer = 37_748_736 + 33_587_200 + 524_288 + 9_437_184
    head = 2 * 2048 * 18_992
    per_item = 3 * (2 * 4 * layer + head)
    assert work_sdar.train_flops_per_item(config, 4096) == per_item
    # a layer outside its experts, its 16 experts, embedding + head, norm
    assert work_sdar.parameters(config) == (
        4 * (19_140_864 + 75_497_472) + 77_791_232 + 2_048) == 456_346_624
    peaks = load_peaks("TPU v5 lite")
    least = work_sdar.least_step_seconds(
        config, 4096, 4, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert least["compute_bound_seconds"] == pytest.approx(
        per_item * 16_384 / 197e12)
    assert least["compute_bound_seconds"] == pytest.approx(0.1817, rel=1e-3)
    assert least["bandwidth_bound_seconds"] == pytest.approx(
        28 * 456_346_624 / 819e9)
    assert least["seconds"] == pytest.approx(0.1973, rel=1e-3)


def test_the_kernels_roofline_reads_the_ranked_operations():
    """`splash_attention_roofline`: the attention's least time over the
    device time of the kernel's operations among the ten ranked; nothing
    where the program has no such kernel (the parent's, the ResNets')."""
    cell = run.load_cell("sdar-ep8-s4096-b4")
    assert "splash_attention_roofline" in [m["name"]
                                           for m in cell["per_layer"]]
    config, peaks = cell["config_file"], load_peaks("TPU v5 lite")
    least = work_sdar.attention_least_seconds(config, 4096, 4, peaks)
    # 3 x (2,050 keys x 2 products x 2 x 128 x 32 heads) x 32,768 positions
    # x 4 layers over 197e12
    assert least == pytest.approx(
        3 * 33_587_200 * 32_768 * 4 / 197e12) == pytest.approx(0.06704,
                                                               rel=1e-3)
    ops = [["splash_mqa_fwd_residuals", 0.6], ["fusion", 0.9],
           ["splash_mqa_dkv_no_residuals", 0.9], ["ragged-dot-none", 0.2]]
    ctx = {"trace": {"devices": [{}],
                     "fullest": {"steps": 10, "device_ops": ops}},
           "items_per_step": 4 * 4096, "global_batch": 4,
           "batch_per_chip": 4, "config": config, "peaks": peaks}
    assert run.read_metric("splash_attention_roofline", ctx) == pytest.approx(
        100 * least * 10 / 1.5)
    ctx["trace"]["fullest"]["device_ops"] = [["fusion", 0.9]]
    assert run.read_metric("splash_attention_roofline", ctx) is None
    ctx["trace"] = None
    assert run.read_metric("splash_attention_roofline", ctx) is None
