"""The `afmoe` family through the harness, at a tiny size on the CPU.

A cell built here as `test_nemotron_cell.py` builds its own, with the
`afmoe` family's file and tiny shapes in the configuration: through `run.run`
whole (the program's trainer, the hook, the plain reference, the
comparison), the control and every fault the family knows read outside a
limit (`no_gate` and `no_window` among them: the tiny cell's window of 8 is
shorter than its rows of 32, so a sliding layer that saw every earlier key
moves the numbers), the declared cell's files found by name, and
`work_afmoe.py` against the count written out by hand at the published
widths.
"""

import json

import pytest

import run
import work_afmoe
from compare import compare
from loop_hook import FOLLOWED_STEPS
from work import load_peaks

BENCH = json.loads((run.REPO / "BENCHMARK.json").read_text())
CELL = "trinity-ep8-s16384-b1"
SEED = 2147483777
LENGTH, ROWS, WINDOW = 32, 4, 8

SHAPES = {"hidden_size": 64, "layer_types": "SSSF", "dense_layers": 1,
          "sliding_window": WINDOW, "num_heads": 4, "num_kv_heads": 2,
          "head_dim": 16, "dense_width": 96, "expert_width": 32,
          "shared_expert_width": 32, "num_experts": 8,
          "experts_per_token": 2, "experts_held": 4, "num_classes": 64,
          "bf16": "false"}


def trinity_cell():
    """A cell as `load_cell` would hand it over: the family's file, the
    published config's keys at tiny values, float32 on both sides."""
    path = run.HERE / "families" / "afmoe.py"
    family = run.check_family(run.load_module("bench_family_afmoe", path),
                              path)
    config = {
        "name": "trinity-tiny", "family": "afmoe",
        "argv": ["--preset=trinity_mini_ep8", "--optim.lr=0.001"]
        + [f"--model.{k}={v}" for k, v in SHAPES.items()],
        "hidden_size": 64, "num_hidden_layers": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "num_dense_layers": 1, "sliding_window": WINDOW,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_shared_experts": 1, "num_experts": 4, "router_experts": 8,
        "num_experts_per_tok": 2, "route_scale": 2.826, "route_norm": True,
        "rms_norm_eps": 1e-5, "rope_theta": 10000, "vocab_size": 64,
        "share_index": 0, "init_std": 0.02,
        "precision": {"compute": "float32"},
        "optimizer": {"name": "adamw", "lr": 0.001, "b1": 0.9, "b2": 0.95,
                      "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0},
    }
    return {
        "name": "trinity-tiny-s32-b4", "config": "trinity-tiny",
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
                      if "workloads" not in m or CELL in m["workloads"]],
    }


@pytest.mark.parametrize("trace", [False, True])
def test_the_afmoe_family_runs_whole(trace):
    from tpu_dp.obs.counters import counters

    counters.reset()
    result = run.run(trinity_cell(), SEED, 0.01, trace,
                     rehearsal={"batch_per_chip": ROWS})
    assert result["correct"] is True, result["compared"]
    reported = set(result["would_report"])
    if trace:
        # on the CPU no device plane is traced: no kernel's share, and
        # what the loop and the set-up spans give is all there
        assert {"data_wait_ms", "host_step_ms", "inflight_steps",
                "dispatch_idle_share", "epoch_gap_ms"} <= reported
        assert not {"window_attention_roofline", "step_mfu"} & reported
    else:
        assert reported == {"setup_s", "step_ms_p95", "throughput_per_chip"}
    assert result["attempted"] == 4 and result["failed"] == 0
    assert [r["name"] for r in result["compared"]] == [
        "loss_step1", "loss_step2", "loss_step3", "grad1_worst_leaf",
        "grad1_median_leaf", "delta3_worst_leaf"]
    assert result["window"]["items_per_step"] == ROWS * LENGTH
    counts = counters.snapshot()
    assert counts["moe.assignments_dropped"] == 0
    assert counts["moe.rows_run"] >= counts["moe.assignments_held"] > 0
    assert counts["lm.tokens"] == 2 * 4 * ROWS * (LENGTH - 1)  # two epochs


@pytest.fixture(scope="module")
def sound():
    cell = trinity_cell()
    job = run.job_of(cell, SEED)
    return cell, job, cell["family"].reference_readings(job, FOLLOWED_STEPS)


@pytest.mark.parametrize("variant", [
    "float8", "no_window", "no_gate", "rope_on_full", "no_shared_expert",
    "softmax_router"])
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
    cell = run.load_cell(CELL)
    assert cell["family"].__name__ == "bench_family_afmoe"
    assert cell["chips"] == 1
    job = run.job_of(cell, SEED)
    assert cell["family"].items_per_row(job) == 16384
    assert (job.global_batch, job.steps_per_epoch) == (1, 32)
    names = [m["name"] for m in cell["per_layer"]]
    assert "window_attention_roofline" in names and "step_mfu" in names
    assert not {"collective_ms", "splash_attention_roofline",
                "causal_attention_roofline"} & set(names)
    config = cell["config_file"]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"]
    assert config["parameters"] == work_afmoe.parameters(config) \
        == 705_474_304
    assert set(cell["limits"]) == {"loss", "grad1", "grad1_median", "delta"}
    # the published widths, uncut
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128,
            "intermediate_size": 6144, "moe_intermediate_size": 1024,
            "router_experts": 128, "num_experts_per_tok": 8,
            "route_scale": 2.826, "sliding_window": 2048,
            "rope_theta": 10000, "rms_norm_eps": 1e-5}.items():
        assert config[key] == value, key
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]


def test_required_work_is_the_count_by_hand():
    """`work_afmoe.py` at the published widths against the count written
    out part by part: 813 MFLOP a token forward, 40.0 TFLOP a step with
    backward, 203 ms at the peak; the window pair's least time 31.4 ms."""
    config = run.load_cell(CELL)["config_file"]
    part = work_afmoe.forward_flops_per_token(config, 16384)
    attn = part["attention"]
    assert attn["projections"] == 2 * 2048 * 9216 + 2 * 4096 * 2048  # 54.5 M
    assert attn["sliding_scores_and_values"] == pytest.approx(31.5e6,
                                                              rel=2e-3)
    assert work_afmoe.window_keys(16384, 2048) == pytest.approx(1920.06,
                                                                abs=0.01)
    assert attn["full_scores_and_values"] == 4 * 128 * 32 * 8192.5
    assert part["dense"] == 2 * 3 * 2048 * 6144                      # 75.5 M
    assert part["experts"] == {"shared_expert": 2 * 3 * 2048 * 1024,
                               "router": 2 * 2048 * 128,
                               "routed_experts": 2 * 3 * 2048 * 1024}
    assert part["head"] == 2 * 2048 * 25024                          # 102.5 M
    per_token = work_afmoe.train_flops_per_item(config, 16384)
    assert per_token / 3 == pytest.approx(813.4e6, rel=1e-4)
    assert per_token * 16384 == pytest.approx(40.0e12, rel=1e-3)
    peaks = load_peaks("TPU v5 lite")
    least = work_afmoe.least_step_seconds(
        config, 16384, 1, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert least["compute_bound_seconds"] == pytest.approx(0.203, rel=5e-3)
    window = work_afmoe.window_attention_least_seconds(config, 16384, 1,
                                                       peaks)
    assert window == pytest.approx(0.0314, rel=5e-3)


def test_the_window_metric_reads_its_own_pair():
    """`window_attention_roofline` through the shipped reader: it reads the
    window pair's one name among the ranked operations, and neither the
    causal pair's (the full layer's) nor the block-diffusion pair's."""
    cell = run.load_cell(CELL)
    config, peaks = cell["config_file"], load_peaks("TPU v5 lite")
    ops = [["fusion", 3.0], ["splash_mqa_window_pair", 0.5],
           ["splash_mqa_causal_pair", 0.2],
           ["splash_mqa_block_diffusion_fwd", 5.0]]
    ctx = {"trace": {"devices": [0],
                     "fullest": {"device_ops": ops, "steps": 11}},
           "items_per_step": 16384, "global_batch": 1,
           "batch_per_chip": 1, "peaks": peaks, "config": config}
    least = work_afmoe.window_attention_least_seconds(config, 16384, 1, peaks)
    assert run.read_metric("window_attention_roofline", ctx) \
        == pytest.approx(100 * least * 11 / 0.5)
    ctx["trace"]["fullest"]["device_ops"] = [op for op in ops
                                             if "window" not in op[0]]
    assert run.read_metric("window_attention_roofline", ctx) is None
