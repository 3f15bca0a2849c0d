"""Between an epoch's first dispatch and its fence the loop launches nothing
but the step (`Trainer.train_epoch`, `trainer._EpochSums`).

(a) a profiled epoch holds one executable launch a step and no other;
(b) the sums folded on the host are, bit for bit, the running sums that
eager adds on the device gave (image model, token model with its counters,
a windowed epoch with a log line in mid-window: (e)); (c) the loop never
has more than `MAX_INFLIGHT` dispatches unfinished, the one it has just
made among them, so no hook finds more; it waits straight before the
dispatch under its own span, and at ``obs=off`` too; (d) an epoch that a
hook raises out of leaves no kept array behind.
"""

from __future__ import annotations

import contextlib
import gc
import types
import weakref

import jax
import numpy as np
import pytest

from test_loop_spans import TickClock, _isolate_global_counters  # noqa: F401
from test_loop_spans import _cfg as _spans_cfg
from tpu_dp.obs import SpanRecorder
from tpu_dp.obs import counters as global_counters
from tpu_dp.obs.spans import STEP_SPANS, tile_ms
from tpu_dp.train import trainer as trainer_mod
from tpu_dp.train.hooks import StepHook
from tpu_dp.train.trainer import MAX_INFLIGHT, Trainer, _GuardRollback

pytestmark = pytest.mark.obs


def _cfg(tmp_path, **overrides):
    """Six steps an epoch and no log line, unless the test says so."""
    return _spans_cfg(tmp_path, **{"data.synthetic_train_size": 96,
                                   "train.log_every": 1000, **overrides})


class Metrics(StepHook):
    """Keeps every step's metrics as the hooks are handed them."""

    def __init__(self, trainer):
        super().__init__(trainer)
        self.steps: list[dict] = []
        self.sizes: list[int] = []

    def on_step_end(self, ev):
        assert len(ev.window) == ev.n
        self.steps += ev.window
        self.sizes.append(ev.n)


# ------------------------------------------------------------ (a) launches

@pytest.mark.parametrize("resident", ["on", "off"])
def test_an_epoch_launches_one_program_a_step_and_no_other(tmp_path,
                                                           resident):
    """Read from a `jax.profiler` session of the CPU backend: every
    executable the python thread launches is a `PjRtCpuExecutable::Execute`
    event and every jitted call (an eager operation is one) a
    `PjitFunction(<name>)`. At the parent commit the same epoch of six
    steps held 93 launches resident (a `gather`, a `broadcast_in_dim` and a
    `convert_element_type` for each of four `v[0]` a step, and three `add`
    a step after the first) and 21 streamed (the adds), for 6 here."""
    from tpu_dp.obs.xplane import find_xplane

    tr = Trainer(_cfg(tmp_path, **{"train.obs": "basic",
                                   "data.device_resident": resident}))
    assert (tr.resident_train is not None) == (resident == "on")
    tr.train_epoch(0)  # compiles; and the launches of a first epoch
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        tr.train_epoch(1)
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(
        str(find_xplane(tmp_path / "prof")))
    events = [(ev.start_ns, ev.name)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    first = min(t for t, name in events if name == "tpu_dp.dispatch")
    (fence,) = [t for t, name in events if name == "tpu_dp.epoch_fence"]
    between = [name for t, name in events if first <= t <= fence]
    launches = [n for n in between if n == "PjRtCpuExecutable::Execute"]
    assert len(launches) == 6
    jitted = {n for n in between if n.startswith("PjitFunction(")}
    assert jitted == {"PjitFunction(loop)" if resident == "on"
                      else "PjitFunction(body)"}


# ------------------------------------------------------------ (b), (e) sums

def _image_trainer(tmp_path, **overrides):
    return Trainer(_cfg(tmp_path, **overrides))


def _token_trainer(tmp_path, **overrides):
    from test_sdar_train import tiny_cfg, token_sets

    return Trainer(tiny_cfg(tmp_path, **overrides), datasets=token_sets())


SUMS = {
    # the epoch's last two steps come after its one log line
    "image": (_image_trainer, {"train.log_every": 4}),
    "image-streamed": (_image_trainer, {"train.log_every": 4,
                                        "data.device_resident": "off"}),
    # the counters
    "tokens": (_token_trainer, {"train.log_every": 2}),
    # (e) windows of four and a tail of single steps, stacked and not in
    # one epoch, the log line in mid-window
    "image-windowed": (_image_trainer, {"train.log_every": 3,
                                        "train.steps_per_call": 4,
                                        "data.synthetic_train_size": 160}),
    "image-windowed-streamed": (
        _image_trainer, {"train.log_every": 3, "train.steps_per_call": 4,
                         "data.synthetic_train_size": 160,
                         "data.device_resident": "off"}),
}


@pytest.mark.parametrize("case", list(SUMS))
def test_host_sums_are_the_device_s_running_sums_bit_for_bit(
        tmp_path, monkeypatch, case):
    build, overrides = SUMS[case]
    tr = build(tmp_path, **overrides)
    seen = Metrics(tr)
    tr.add_hook(seen)
    logged = []
    real = trainer_mod._EpochSums.running_loss
    monkeypatch.setattr(
        trainer_mod._EpochSums, "running_loss",
        lambda self, steps: logged.append(real(self, steps)) or logged[-1])
    stats = tr.train_epoch(0)

    # The parent's way: eager adds on the device, a step at a time.
    log_every = tr.cfg.train.log_every
    run = ep_loss = ep_correct = ep_counters = None
    run_steps, want_logged = 0, []
    for i, m in enumerate(seen.steps):
        run = m["loss"] if run is None else run + m["loss"]
        run_steps += 1
        ep_loss = m["loss"] if ep_loss is None else ep_loss + m["loss"]
        ep_correct = (m["correct"] if ep_correct is None
                      else ep_correct + m["correct"])
        if "counters" in m:
            ep_counters = (m["counters"] if ep_counters is None
                           else ep_counters + m["counters"])
        if i % log_every == log_every - 1:
            want_logged.append(float(run) / run_steps)
            run, run_steps = None, 0
    steps = len(seen.steps)
    assert steps == len(tr.train_pipe) and steps > log_every
    assert ep_loss.dtype == np.float32 and ep_correct.dtype == np.int32
    assert logged == want_logged and len(logged) == steps // log_every
    assert stats["loss"] == float(ep_loss) / steps
    count = steps * tr.global_batch_size
    if ep_counters is not None:
        totals = dict(zip(tr.model.counter_names,
                          np.asarray(ep_counters, np.float64)))
        published = global_counters.snapshot()
        for name, value in totals.items():
            assert published[name] == float(value), name
        count = int(totals[tr.model.count_counter])
    assert (ep_counters is not None) == (case == "tokens")
    assert stats["accuracy"] == float(ep_correct) / count
    if "windowed" in case:
        assert seen.sizes == [4, 4, 1, 1]


# ---------------------------------------------------- (c) the loop's bound

class Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: keeps which span is
    open."""

    def __init__(self):
        self.open: list[str] = []

    def __call__(self, name, **kwargs):
        return self._scope(name)

    @contextlib.contextmanager
    def _scope(self, name):
        self.open.append(name)
        yield
        self.open.remove(name)


class Device:
    """A device that finishes a step only when the host blocks on it (or
    fetches it), and everything dispatched before it with it: the arrays'
    readiness is the test's."""

    def __init__(self, notes=None):
        self.notes = notes
        self.dispatched: list[Scalar] = []
        self.unfinished_at_dispatch: list[int] = []
        self.waited_under: list[tuple] = []

    def unfinished(self) -> int:
        return sum(not a.done for a in self.dispatched)

    def run(self, state, *fed):
        self.unfinished_at_dispatch.append(self.unfinished())
        out = {"loss": Scalar(self, np.float32(2.0)),
               "correct": Scalar(self, np.int32(3))}
        self.dispatched.append(out["loss"])
        return state, out

    def finish(self, upto) -> None:
        for a in self.dispatched[:self.dispatched.index(upto) + 1]:
            a.done = True


class Scalar:
    def __init__(self, device, value):
        self.device, self.value, self.done = device, value, False

    def is_ready(self) -> bool:
        return self.done

    def block_until_ready(self):
        if self.device.notes is not None:
            self.device.waited_under.append(tuple(self.device.notes.open))
        self.device.finish(self)
        return self

    def __array__(self, dtype=None, copy=None):
        if self in self.device.dispatched:
            self.device.finish(self)
        return np.asarray(self.value, dtype)


class UnfinishedAtHooks(StepHook):
    """What a hook finds on the device at the host's step boundary."""

    def __init__(self, trainer, device):
        super().__init__(trainer)
        self.device, self.found = device, []

    def on_step_end(self, ev):
        self.found.append(self.device.unfinished())


def _on_a_controlled_device(tr, device) -> UnfinishedAtHooks:
    real = tr._program(1)
    tr._program = lambda n: real._replace(run=device.run)
    hook = UnfinishedAtHooks(tr, device)
    tr.add_hook(hook)
    return hook


def test_never_more_than_two_unfinished_and_the_wait_is_a_tile(tmp_path):
    clock, notes = TickClock(), Annotations()
    tr = Trainer(_cfg(tmp_path, **{"train.obs": "basic",
                                   "data.device_resident": "off"}))
    tr.spans = SpanRecorder(clock=clock, annotate=notes)
    device = Device(notes)
    at_hooks = _on_a_controlled_device(tr, device)
    stats = tr.train_epoch(0)
    assert MAX_INFLIGHT == 2
    # Nothing finishes by itself here: without the wait the sixth dispatch
    # would find five unfinished. With it a dispatch finds one, and makes
    # the second; the hooks of a step find one running and one queued, and
    # never a third.
    assert device.unfinished_at_dispatch == [0, 1, 1, 1, 1, 1]
    assert at_hooks.found == [1, 2, 2, 2, 2, 2]
    # One wait a dispatch that would have been a third unfinished, with
    # the span of its own open and no other.
    assert device.waited_under == [("tpu_dp.inflight_wait",)] * 4
    assert stats == {"loss": 2.0, "accuracy": 6 * 3 / (6 * 16)}
    assert global_counters.get("loop.inflight_steps") == 2
    assert global_counters.get("loop.inflight_sum") == 1 + 2 + 2 + 2 + 2
    records = tr.spans.records()
    tiles = [s for s in STEP_SPANS if s not in ("h2d", "device")]
    assert tiles.index("inflight_wait") == tiles.index("dispatch") - 1
    for r in records[:-1]:
        assert list(r["spans"]) == tiles
    assert sum(tile_ms(r["spans"]) for r in records) == pytest.approx(
        (clock.reads - 1) * 1.0)


def test_obs_off_waits_all_the_same_and_reads_no_clock(tmp_path, monkeypatch):
    tr = Trainer(_cfg(tmp_path, **{"data.device_resident": "off"}))
    assert tr.spans is None
    device = Device()
    at_hooks = _on_a_controlled_device(tr, device)

    class CountedTime:
        reads = 0

        def __getattr__(self, name):
            CountedTime.reads += 1
            return getattr(__import__("time"), name)

    monkeypatch.setattr(trainer_mod, "time", CountedTime())
    tr.train_epoch(0)
    assert device.unfinished_at_dispatch == [0, 1, 1, 1, 1, 1]
    assert at_hooks.found == [1, 2, 2, 2, 2, 2]
    assert CountedTime.reads == 0


# --------------------------------------------- (d) an epoch that is left

def test_a_rollback_out_of_the_epoch_leaves_no_kept_array(tmp_path,
                                                          monkeypatch):
    made = []

    class Watched(trainer_mod._EpochSums):
        def __init__(self):
            super().__init__()
            made.append(weakref.ref(self))

    monkeypatch.setattr(trainer_mod, "_EpochSums", Watched)

    class Rollback(StepHook):
        def on_step_end(self, ev):
            if ev.done == 4:
                raise _GuardRollback(ev.epoch, ev.done,
                                     types.SimpleNamespace(reason="test"))

    tr = Trainer(_cfg(tmp_path))
    tr.add_hook(Rollback(tr))
    try:
        tr.train_epoch(0)
    except _GuardRollback as left:
        assert left.done == 4
    else:
        pytest.fail("the hook did not raise")
    gc.collect()
    assert len(made) == 1 and made[0]() is None
    # The re-entry's sums are its own steps' and no other's.
    tr._hooks.pop()
    seen = Metrics(tr)
    tr.add_hook(seen)
    stats = tr.train_epoch(0, start_step=4)
    first, second = (m["loss"] for m in seen.steps)
    assert stats["loss"] == float(first + second) / 2
    assert stats["resumed_at_step"] == 4
