"""The trace reducer: its interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on four v5e chips
(`small_dp4.xplane.pb`, written by `record_small_trace.py`)."""

from pathlib import Path

import pytest

import trace_reduce as T

FIXTURE = Path(__file__).resolve().parent / "small_dp4.xplane.pb"


def test_merge_total_subtract_complement():
    merged = T.merge([(3, 5), (0, 1), (0.5, 2), (5, 6)])
    assert merged == [(0, 2), (3, 6)]
    assert T.total(merged) == 5
    assert T.subtract_total(merged, [(1, 4)]) == 1 + 2
    assert T.subtract_total(merged, []) == 5
    assert T.complement(merged, -1, 7) == [(-1, 0), (2, 3), (6, 7)]
    assert T.clip([(0, 2), (3, 6)], 1, 4) == [(1, 2), (3, 4)]


def test_op_names_and_collectives():
    name = T.op_name("%all-reduce-start.12 = (f32[64]{0}) all-reduce-start(..)")
    assert name == "all-reduce-start" and T.is_collective(name)
    assert T.is_collective("all-gather-done")
    assert T.is_collective("reduce-scatter")
    assert not T.is_collective(T.op_name("%fusion.813 = s32[4]{0} fusion(..)"))
    assert T.op_name("%convert_reduce_fusion.3 = ...") == "convert_reduce_fusion"


def test_self_times_charge_a_while_less_its_body():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion"),
              (4.0, 6.0, "copy"), (12.0, 13.0, "fusion")]
    assert T.self_times(events) == {"while": 5.0, "fusion": 4.0, "copy": 2.0}
    assert [e[2] for e in T.leaves(events)] == ["fusion", "copy", "fusion"]


def test_reduce_device_on_made_up_steps():
    # Four executions of the step program, 10 s apart, each 8 s busy: a
    # 6 s fusion, then a 2 s all-reduce of which 1 s runs under a copy.
    modules, ops = [], []
    for k in range(4):
        t = 10.0 * k
        modules.append((t, t + 8.0, "jit_loop(1)"))
        modules.append((t + 8.5, t + 8.6, "jit_add(2)"))
        ops += [(t, t + 6.0, "%fusion.1 = f32[] fusion()"),
                (t + 6.0, t + 8.0, "%all-reduce.2 = f32[] all-reduce()"),
                (t + 6.0, t + 7.0, "%copy.3 = f32[] copy()")]
    host = [(7.0, 11.0, "data_wait"), (17.0, 21.0, "dispatch")]
    dev = T.reduce_device(modules, ops, [], host)
    assert dev["step_program"] == "jit_loop(1)"
    assert dev["steps"] == 2 and dev["window_s"] == 20.0
    assert dev["busy_s"] == 16.0
    assert dev["collective_s"] == 4.0 and dev["collective_exposed_s"] == 2.0
    assert dev["device_ops"][0] == ["fusion", 12.0]
    assert dev["idle_gaps"] == [["dispatch", 2.0], ["other", 2.0]]
    # fewer than three executions: nothing whole to measure
    assert T.reduce_device(modules[:4], ops, [], host) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace_from_four_chips():
    out = T.reduce_trace(FIXTURE)
    assert len(out["devices"]) == 4
    for dev in out["devices"]:
        # eight executions traced: six whole periods, one all-reduce each,
        # nothing to hide it under; the host slept 2 ms after each dispatch
        assert dev["steps"] == 6 and dev["collective_ops"] == 6
        assert dev["step_program"].startswith("jit_loop")
        assert dev["device_ops"][0][0] == "all-reduce"
        assert dev["collective_exposed_s"] == dev["collective_s"]
        assert dev["idle_gaps"][0][0] == "data_wait"
        assert 0.019 < dev["window_s"] < 0.022
        assert 0.0007 < dev["busy_s"] < 0.0010
        assert 0.0 < dev["busy_s"] <= dev["window_s"]
        assert dev["collective_ops"] > 0
        assert 0.0 <= dev["collective_exposed_s"] <= dev["collective_s"]
        assert dev["collective_s"] <= dev["busy_s"] + 1e-9
        ranked = [sec for _, sec in dev["device_ops"]]
        assert ranked == sorted(ranked, reverse=True)
        assert abs(sum(sec for _, sec in dev["idle_gaps"])
                   - (dev["window_s"] - dev["busy_s"])) <= dev["window_s"]
    assert out["fullest"]["busy_s"] == max(d["busy_s"] for d in out["devices"])
    assert 0.0 < out["busy_s"] <= out["window_s"]
