"""The loop accounts for its whole iteration (`obs/spans.py`, `train_epoch`).

The spans tile an iteration under an injected clock; the per-epoch spans
land once an epoch on the right record; the spans are host events of a
`jax.profiler` trace with the step number attached; ``obs=off`` touches
none of it; the in-flight counters say whether the device waited.
"""

from __future__ import annotations

import contextlib

import pytest

from tpu_dp.obs import Counters, SpanRecorder, to_trace_events, validate_trace
from tpu_dp.obs import counters as global_counters
from tpu_dp.obs.spans import (
    EPOCH_SPANS,
    STEP_SPANS,
    InflightSteps,
    tile_ms,
)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _isolate_global_counters():
    saved = dict(global_counters._counts), dict(global_counters._gauges)
    global_counters.reset()
    yield
    global_counters.reset()
    global_counters._counts.update(saved[0])
    global_counters._gauges.update(saved[1])


class TickClock:
    """Every reading is one millisecond after the last."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return self.reads * 1e-3


class Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: keeps what was opened
    and how deep the open ones were nested."""

    def __init__(self):
        self.seen: list[tuple[str, int]] = []
        self.depth = self.max_depth = 0

    def __call__(self, name, **kwargs):
        self.seen.append((name, kwargs["step"]))
        return self._scope()

    @contextlib.contextmanager
    def _scope(self):
        self.depth += 1
        self.max_depth = max(self.max_depth, self.depth)
        yield
        self.depth -= 1


def _cfg(tmp_path, **overrides):
    from tpu_dp.config import Config

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 16
    c.data.prefetch = 1
    c.train.log_every = 2
    c.train.ckpt_dir = str(tmp_path / "ck")
    for k, v in overrides.items():
        section, field = k.split(".")
        setattr(getattr(c, section), field, v)
    return c


def _trainer(tmp_path, clock=None, annotate=None, **overrides):
    from tpu_dp.train.trainer import Trainer

    tr = Trainer(_cfg(tmp_path, **overrides))
    if clock is not None:
        tr.spans = SpanRecorder(clock=clock, annotate=annotate)
    return tr


# ------------------------------------------------------------ the primitive

def test_begin_ends_the_open_span_on_the_same_reading():
    clock, notes = TickClock(), Annotations()
    rec = SpanRecorder(clock=clock, annotate=notes)
    t0 = rec.begin("data_wait", step=7)
    rec.begin("pre_dispatch")
    rec.begin("dispatch")
    t3 = rec.begin("telemetry")
    assert dict(rec.held) == pytest.approx(
        {"data_wait": 1.0, "pre_dispatch": 1.0, "dispatch": 1.0})
    (r,) = rec.open_window(1)
    rec.begin("accumulate")   # telemetry ends into the record it made
    rec.begin("hooks")
    t6 = rec.begin("data_wait", step=8)  # hooks end into step 7's record
    assert r["step"] == 7 and list(r["spans"]) == [
        "data_wait", "pre_dispatch", "dispatch", "telemetry", "accumulate",
        "hooks"]
    assert tile_ms(r["spans"]) == pytest.approx((t6 - t0) * 1e3)
    assert t3 - t0 == pytest.approx(3e-3)
    assert clock.reads == 7  # one reading a span, none to end one
    assert notes.max_depth == 1  # never two open at once
    assert notes.seen[:2] == [("tpu_dp.data_wait", 7),
                              ("tpu_dp.pre_dispatch", 7)]
    assert notes.seen[-1] == ("tpu_dp.data_wait", 8)
    rec.abandon()
    assert notes.depth == 0 and len(rec) == 1


def test_window_spans_split_evenly_and_keep_steps_back_to_back():
    rec = SpanRecorder(clock=TickClock(), annotate=Annotations())
    rec.begin("data_wait", step=11)
    rec.begin("dispatch")
    rec.begin("telemetry")
    recs = rec.open_window(4, gen=2)
    rec.begin("hooks")
    rec.end()
    assert [r["step"] for r in recs] == [11, 12, 13, 14]
    assert all(r["gen"] == 2 for r in recs)
    for r in recs:
        assert r["spans"] == pytest.approx(
            {"data_wait": 0.25, "dispatch": 0.25, "telemetry": 0.25,
             "hooks": 0.25})
    # A step starts where the one before it ends, late spans included.
    assert recs[1]["ts"] - recs[0]["ts"] == pytest.approx(1e-3, abs=1e-6)
    assert recs[3]["ts"] - recs[0]["ts"] == pytest.approx(3e-3, abs=1e-6)


def test_span_on_one_record_takes_what_the_abandoned_iteration_held():
    rec = SpanRecorder(clock=TickClock(), annotate=Annotations())
    rec.begin("data_wait", step=1)
    rec.begin("telemetry")
    first, last = rec.open_window(2)
    rec.begin("data_wait", step=3)     # the next() that ends the epoch
    rec.begin("epoch_fence", rec=last)
    rec.end()
    assert "epoch_fence" not in first["spans"]
    assert last["spans"]["epoch_fence"] == pytest.approx(2.0)
    assert dict(rec.held) == {}
    rec.begin("data_wait", step=3)     # the next epoch starts clean
    rec.begin("telemetry")
    (nxt,) = rec.open_window(1)
    assert nxt["spans"] == pytest.approx({"data_wait": 1.0})


# ------------------------------------------------------------- the trainer

@pytest.mark.parametrize("mode", ["basic", "full"])
def test_spans_tile_the_iteration(tmp_path, mode):
    """Under an injected clock the tiles of an epoch sum to the wall time
    from its first `data_wait` to its fence, no two are open at once, and
    ``basic`` leaves ``h2d``/``device`` out rather than writing zeros."""
    clock, notes = TickClock(), Annotations()
    tr = _trainer(tmp_path, clock, notes, **{"train.obs": mode})
    tr.train_epoch(0)
    records = tr.spans.records()
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    tiles = [s for s in STEP_SPANS
             if mode == "full" or s not in ("h2d", "device")]
    for r in records[:-1]:
        assert list(r["spans"]) == tiles
    assert list(records[-1]["spans"]) == tiles + ["epoch_fence"]
    # Every clock reading but the last opens a span; the last ends the
    # fence: the spans' sum is the time between the first and the last.
    total = sum(tile_ms(r["spans"]) for r in records)
    assert total == pytest.approx((clock.reads - 1) * 1.0)
    assert total == pytest.approx((tr._fence_t - 1e-3) * 1e3)
    assert all(v > 0.0 for r in records for v in r["spans"].values())
    assert notes.max_depth == 1 and notes.depth == 0
    # The annotations carry the global step, the fence its record's.
    assert {n for n, _ in notes.seen} == {
        "tpu_dp." + s for s in tiles + ["epoch_fence"]}
    assert ("tpu_dp.dispatch", 3) in notes.seen
    assert notes.seen[-1] == ("tpu_dp.epoch_fence", 4)
    trace = to_trace_events(records)
    assert validate_trace(trace) == []


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_epoch_spans_land_once_an_epoch(tmp_path, steps_per_call):
    """`epoch_gap` on the record of the epoch's first step, `epoch_fence`
    on its last's, whole and not spread over a window; the first epoch
    follows no fence and has no gap."""
    clock = TickClock()
    tr = _trainer(tmp_path, clock, Annotations(), **{
        "train.obs": "basic", "train.steps_per_call": steps_per_call,
        "data.device_resident": "off"})
    fences = []
    for epoch in range(3):
        tr.train_epoch(epoch)
        fences.append(tr._fence_t)
        clock()  # what the caller does between two epochs counts too
    by_step = {r["step"]: r["spans"] for r in tr.spans.records()}
    assert sorted(by_step) == list(range(1, 13))
    assert [s for s in by_step if "epoch_fence" in by_step[s]] == [4, 8, 12]
    assert [s for s in by_step if "epoch_gap" in by_step[s]] == [5, 9]
    for first, fence_t in ((5, fences[0]), (9, fences[1])):
        spans = by_step[first]
        # From the fence's return to the first dispatch's: the caller's
        # reading and the one that opens data_wait, then the window's
        # data_wait, pre_dispatch, inflight_wait and dispatch.
        assert spans["epoch_gap"] == pytest.approx(6.0)
        assert spans["epoch_gap"] == pytest.approx(2.0 + steps_per_call * (
            spans["data_wait"] + spans["pre_dispatch"]
            + spans["inflight_wait"] + spans["dispatch"]))
        assert fence_t is not None
    assert tr._fence_t == fences[2]
    # The Perfetto export ends the gap where the first dispatch ends.
    events = [e for e in to_trace_events(tr.spans.records())["traceEvents"]
              if e["ph"] == "X" and e["args"]["step"] == 5]
    gap = next(e for e in events if e["name"] == "epoch_gap")
    dispatch = next(e for e in events if e["name"] == "dispatch")
    assert gap["ts"] + gap["dur"] == pytest.approx(
        dispatch["ts"] + dispatch["dur"])
    rollup = tr.spans.rollup(EPOCH_SPANS)
    assert rollup["epoch_gap"]["n"] == 2 and rollup["epoch_fence"]["n"] == 3


def test_a_raised_hook_leaves_no_gap_and_no_open_span(tmp_path):
    from tpu_dp.train.hooks import StepHook

    class Boom(StepHook):
        def on_step_end(self, ev):
            if self.tr._host_step == 6:
                raise RuntimeError("boom")

    notes = Annotations()
    tr = _trainer(tmp_path, TickClock(), notes, **{"train.obs": "basic"})
    tr.add_hook(Boom(tr))
    tr.train_epoch(0)
    with pytest.raises(RuntimeError, match="boom"):
        tr.train_epoch(1)
    assert tr._fence_t is None and notes.depth == 1
    tr._hooks.pop()
    tr.train_epoch(1, start_step=2)
    assert notes.depth == 0
    by_step = {r["step"]: r["spans"] for r in tr.spans.records()[-2:]}
    assert sorted(by_step) == [7, 8]
    assert "epoch_gap" not in by_step[7] and "epoch_fence" in by_step[8]


def test_profiler_trace_holds_the_spans_with_their_step(tmp_path):
    """Three steps under a CPU `jax.profiler` session: the program's spans
    are events of a host line, on the profiler's clock, each with the
    global step attached."""
    import jax

    from tpu_dp.obs.xplane import find_xplane

    tr = _trainer(tmp_path, **{"train.obs": "basic",
                               "data.synthetic_train_size": 48})
    tr.train_epoch(0)  # compile outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        tr.train_epoch(1)
        jax.block_until_ready(tr.state)
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        str(find_xplane(tmp_path / "prof")))
    found: dict[str, set] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpu_dp."):
                    stats = dict(ev.stats)
                    found.setdefault(ev.name, set()).add(int(stats["step"]))
    for name in ("data_wait", "pre_dispatch", "inflight_wait", "dispatch",
                 "telemetry", "accumulate", "hooks"):
        assert found["tpu_dp." + name] >= {4, 5, 6}, (name, found)
    assert found["tpu_dp.epoch_fence"] == {6}


def test_obs_off_reads_no_clock_and_annotates_nothing(tmp_path, monkeypatch):
    """`train.obs=off`: no recorder, no annotation object, no clock read of
    the loop's own, no ``loop.*`` counter. Set-up's first-epoch phase is
    annotated once, at every ``train.obs`` (`obs/spans.py` `setup_span`)."""
    import jax

    from tpu_dp.train import trainer as trainer_mod

    tr = _trainer(tmp_path)
    assert tr.spans is None and not hasattr(tr, "_inflight")
    made = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, *args, **kwargs):
            made.append(args)
            super().__init__(*args, **kwargs)

    class CountedTime:
        reads = 0

        def __getattr__(self, name):
            CountedTime.reads += 1
            return getattr(__import__("time"), name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    monkeypatch.setattr(trainer_mod, "time", CountedTime())
    tr.train_epoch(0)
    tr.train_epoch(1)
    assert made == [("tpu_dp.setup.first_epoch",)]
    assert CountedTime.reads == 0
    assert tr._fence_t is None
    assert not [k for k in global_counters.snapshot() if k.startswith("loop.")]
    # The same loop with the recorder on does annotate and count.
    on = _trainer(tmp_path / "on", **{"train.obs": "basic"})
    on.train_epoch(0)
    assert made and global_counters.get("loop.dispatches") == 3


# ------------------------------------------------------ the dispatch boundary

class Loss:
    def __init__(self):
        self.done = False

    def is_ready(self) -> bool:
        return self.done


def test_inflight_steps_ahead_level_starved():
    reg = Counters()
    depth = InflightSteps(registry=reg)
    losses = [Loss() for _ in range(6)]

    def dispatch(k, n=1, **kwargs):
        depth.before_dispatch(**kwargs)
        depth.dispatched(losses[k], n)

    dispatch(0, first_of_epoch=True)   # counted by epoch_gap, not here
    assert reg.snapshot() == {}
    dispatch(1)                        # ahead: step 0 still runs
    assert reg.get("loop.inflight_steps") == 1
    dispatch(2)                        # further ahead: 0 and 1 enqueued
    assert reg.get("loop.inflight_steps") == 2
    losses[0].done = losses[1].done = True
    dispatch(3)                        # level: one step in flight
    assert reg.get("loop.inflight_steps") == 1
    assert reg.get("loop.dispatch_onto_idle") == 0  # made, at nought
    losses[2].done = losses[3].done = True
    dispatch(4, n=4)                   # starved: the device had nothing
    assert reg.get("loop.inflight_steps") == 0
    assert reg.get("loop.dispatch_onto_idle") == 1
    dispatch(5)                        # a window counts its steps
    assert reg.get("loop.inflight_steps") == 4
    assert reg.get("loop.dispatches") == 5
    assert reg.get("loop.inflight_sum") == 1 + 2 + 1 + 0 + 4
    # Only the left end is asked: a finished step behind a running one
    # stays, as the device runs them in order.
    losses[5].done = True
    dispatch(0)
    assert reg.get("loop.inflight_steps") == 5
    # A new epoch starts from a drained device.
    losses[1].done = False
    dispatch(1, first_of_epoch=True)
    dispatch(2)
    assert reg.get("loop.inflight_steps") == 1
    assert reg.get("loop.dispatches") == 7


def test_trainer_counts_every_dispatch_but_an_epochs_first(tmp_path):
    tr = _trainer(tmp_path, **{"train.obs": "basic"})
    for epoch in range(2):
        tr.train_epoch(epoch)
    snap = global_counters.snapshot()
    assert snap["loop.dispatches"] == 6  # 2 x (4 - 1)
    assert 0 <= snap["loop.dispatch_onto_idle"] <= 6
    assert 0 <= snap["loop.inflight_sum"] <= 6 * 4
    from tpu_dp.obs.counters import METRICS

    assert {k for k in snap if k.startswith("loop.")} <= set(METRICS)


# ------------------------------------------------------------------ the seams

def test_add_hook_and_datasets_are_public_seams(tmp_path):
    import numpy as np

    from tpu_dp.data.cifar import ArrayDataset
    from tpu_dp.train.hooks import StepHook
    from tpu_dp.train.trainer import Trainer

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (48, 32, 32, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, (48,), dtype=np.int32)
    train = ArrayDataset(images, labels, "synthetic", 10, synthetic=True)
    test = ArrayDataset(images[:16], labels[:16], "synthetic", 10,
                        synthetic=True)

    class Seen(StepHook):
        steps = 0

        def on_step_end(self, ev):
            Seen.steps += ev.n

    tr = Trainer(_cfg(tmp_path), datasets=(train, test))
    assert tr.train_ds is train and tr.test_ds is test
    assert len(tr.train_pipe) == 3
    hook = Seen(tr)
    tr.add_hook(hook)
    assert tr._hooks[-1] is hook
    tr.train_epoch(0)
    assert Seen.steps == 3
