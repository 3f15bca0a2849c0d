"""Set-up measured from inside the program (`obs/spans.py` `setup_span`,
`obs/compiles.py`, `Trainer.__init__` and its first `train_epoch`).

A trainer publishes its set-up phases as gauges that tile construction and
the first epoch; the compile listener keeps each stage's own seconds, tells
a program the persistent cache served from one it compiled, freezes its
set-up totals at the first epoch's fence and names every program after it;
the benchmark's `counter_value` reader sums what is published and reads
nothing where nothing is.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tpu_dp.obs import Counters, compiles, spans
from tpu_dp.obs import counters as global_counters
from tpu_dp.obs.counters import METRICS

pytestmark = pytest.mark.obs

REPO = Path(__file__).resolve().parent.parent
SETUP_METRICS = {
    "setup_before_trainer_s": "setup.before_trainer_s",
    "setup_trainer_s": "setup.trainer_s",
    "setup_init_state_s": "setup.init_state_s",
    "setup_caller_s": "setup.caller_s",
    "setup_first_epoch_s": "setup.first_epoch_s",
    "setup_trace_s": "setup.trace_s",
    "setup_compile_s": "setup.compile_s",
    "setup_compiled_anew": "setup.compiled_anew",
    "setup_step_entries": "setup.step_entries",
}


def _counter_value():
    path = REPO / "benchmark" / "metrics" / "readers" / "counter_value.py"
    spec = importlib.util.spec_from_file_location("counter_value", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cfg(tmp_path):
    from tpu_dp.config import Config

    c = Config()  # LeNet
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 16
    c.train.log_every = 100
    c.train.ckpt_dir = str(tmp_path / "ck")
    return c


class Lines:
    """Stands in for `log0`: keeps each line as it would read."""

    def __init__(self):
        self.lines: list[str] = []

    def __call__(self, msg, *args, **kwargs):
        self.lines.append(msg % args)


class TickClock:
    def __init__(self, step=0.5):
        self.now, self.step = 0.0, step

    def __call__(self):
        self.now += self.step
        return self.now


class Annotations:
    def __init__(self):
        self.opened: list[str] = []
        self.depth = 0

    def __call__(self, name, **kwargs):
        self.opened.append(name)
        return self._scope()

    @contextlib.contextmanager
    def _scope(self):
        self.depth += 1
        yield
        self.depth -= 1


# --------------------------------------------------------------- a trainer

@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """One LeNet trainer through its first epoch, its own process-wide
    `before_trainer`, the wall time around both, and what it published."""
    from tpu_dp.train.trainer import Trainer

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "_before_trainer_published", False)
        lines = Lines()
        mp.setattr(compiles.install(), "log", lines)
        t0 = time.perf_counter()
        tr = Trainer(_cfg(tmp_path_factory.mktemp("setup")))
        tr.train_epoch(0)
        wall = time.perf_counter() - t0
        first = global_counters.snapshot()
        yield tr, wall, first, lines


def test_a_trainer_publishes_its_setup_spans(built):
    tr, wall, snap, lines = built
    phase = {k: snap.get(f"setup.{k}_s") for k in spans.SETUP_SPANS}
    assert all(v is not None and v > 0.0 for v in phase.values()), phase
    assert phase["init_state"] <= phase["trainer"]
    # Construction, the caller's time and the first epoch tile the wall
    # time around them; the time before was the process's.
    covered = phase["trainer"] + phase["caller"] + phase["first_epoch"]
    assert covered == pytest.approx(wall, rel=0.1)
    assert covered <= wall
    # The freeze: the set-up's own programs, and one line that says so.
    assert snap["setup.compiled_anew"] >= 1
    assert 0.0 < snap["setup.trace_s"] + snap["setup.compile_s"] <= covered
    (summary,) = [ln for ln in lines.lines if ln.startswith("set-up:")]
    assert "first_epoch" in summary and "longest:" in summary
    assert tr._setup is None


def test_a_second_epoch_leaves_setup_alone_and_names_new_programs(built):
    tr, _, first, lines = built
    frozen = ("setup.first_epoch_s", "setup.trace_s", "setup.compile_s",
              "setup.compiled_anew")
    listener = compiles.install()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(listener, "log", lines)
        tr.train_epoch(1)
        after = global_counters.snapshot()
        assert {k: after[k] for k in frozen} == {k: first[k] for k in frozen}

        x = jnp.arange(29.0)
        before = global_counters.snapshot()

        def after_setup_program(v):
            return jnp.cos(v) * 5.0

        jax.jit(after_setup_program)(x)
    grown = global_counters.snapshot()
    assert grown["compile.programs"] == before["compile.programs"] + 1
    assert grown["compile.trace_s"] > before["compile.trace_s"]
    assert grown["setup.trace_s"] == first["setup.trace_s"]
    (line,) = [ln for ln in lines.lines if "after_setup_program" in ln]
    assert line.startswith("compile: after_setup_program traced in ")
    assert "compiled in" in line


# -------------------------------------------------------- the compile listener

def test_a_program_the_cache_serves_is_not_compiled_anew(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    listener = compiles.install()
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        def served_by_the_cache(v):
            return jnp.tanh(v) + 7.0

        x = jnp.arange(31.0)
        c0 = global_counters.snapshot()
        jax.jit(served_by_the_cache)(x)
        jax.clear_caches()
        c1 = global_counters.snapshot()
        jax.jit(served_by_the_cache)(x)
        c2 = global_counters.snapshot()
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
    row = listener.table["served_by_the_cache"]
    assert (row["programs"], row["compiled_anew"], row["cache_hits"],
            row["traces"]) == (2, 1, 1, 2)
    assert c1["compile.compiled_anew"] == c0.get("compile.compiled_anew",
                                                 0.0) + 1
    assert c2["compile.compiled_anew"] == c1["compile.compiled_anew"]
    assert c2["compile.cache_hits"] == c1.get("compile.cache_hits", 0.0) + 1
    assert c2["compile.programs"] == c1["compile.programs"] + 1


def test_installing_twice_counts_each_event_once():
    first, second = compiles.install(), compiles.install()
    assert first is second
    x = jnp.arange(37.0)
    before = global_counters.snapshot()

    def counted_once(v):
        return v * v - 2.0

    jax.jit(counted_once)(x)
    after = global_counters.snapshot()
    assert after["compile.programs"] == before["compile.programs"] + 1
    assert first.table["counted_once"]["traces"] == 1


def test_each_stage_keeps_its_own_seconds_and_freeze_totals_them():
    """A trace that encloses a nested trace and an eager compile keeps its
    duration less theirs; the backend stage the cache served is a hit; the
    freeze's totals are the table's and its line names the longest."""
    reg, lines = Counters(), Lines()
    lis = compiles.CompileListener(registry=reg, log=lines)
    trace, lower, backend = compiles._STAGES
    lis.on_scalar(trace, 0.0, fun_name="step")
    lis.on_scalar(trace, 0.0, fun_name="inner")
    lis.on_duration(trace, 0.25, fun_name="inner")
    lis.on_scalar(backend, 0.0, fun_name="jit(constant)")
    lis.on_duration(backend, 0.5, fun_name="jit(constant)")
    lis.on_duration(trace, 2.0, fun_name="step")
    lis.on_scalar(lower, 0.0, fun_name="jit(step)")
    lis.on_duration(lower, 1.0, fun_name="jit(step)")
    lis.on_scalar(backend, 0.0, fun_name="jit(step)")
    lis.on_event(compiles._CACHE_HIT)
    lis.on_duration(backend, 3.0, fun_name="jit(step)")
    step = lis.table["step"]
    assert step["trace_s"] == pytest.approx(1.25)
    assert (step["lower_s"], step["backend_s"]) == (1.0, 3.0)
    assert (step["programs"], step["cache_hits"],
            step["compiled_anew"]) == (1, 1, 0)
    assert lis.table["inner"]["trace_s"] == 0.25
    assert lis.table["constant"]["compiled_anew"] == 1
    assert reg.get("compile.trace_s") == pytest.approx(1.5)  # wall: 2.0
    assert (reg.get("compile.backend_s"), reg.get("compile.programs"),
            reg.get("compile.cache_hits"),
            reg.get("compile.compiled_anew")) == (3.5, 2, 1, 1)
    assert lines.lines == []  # quiet during a set-up

    total = lis.freeze()
    assert total["compiled_anew"] == 1
    assert reg.get("setup.trace_s") == pytest.approx(2.5)
    assert reg.get("setup.compile_s") == pytest.approx(3.5)
    assert reg.get("setup.compiled_anew") == 1
    (summary,) = lines.lines
    assert "longest: step 5.25 s (traced 1x" in summary

    # After the freeze: a line a program, with its own stages' seconds.
    lis.on_scalar(trace, 0.0, fun_name="late")
    lis.on_duration(trace, 0.125, fun_name="late")
    lis.on_scalar(backend, 0.0, fun_name="jit(late)")
    lis.on_duration(backend, 0.5, fun_name="jit(late)")
    assert lines.lines[-1] == ("compile: late traced in 0.125 s, lowered in "
                               "0.000 s, compiled in 0.500 s")
    assert reg.get("setup.compile_s") == pytest.approx(3.5)


# ------------------------------------------------------------ the spans

def test_setup_span_times_on_its_clock_under_the_setup_annotation():
    reg, notes = Counters(), Annotations()
    with spans.setup_span("trainer", registry=reg, clock=TickClock(0.25),
                          annotate=notes) as span:
        assert notes.depth == 1
    assert notes.depth == 0 and notes.opened == ["tpu_dp.setup.trainer"]
    assert span.name == "trainer"
    assert reg.get("setup.trainer_s") == 0.25
    with pytest.raises(ValueError, match="not a set-up span"):
        spans.setup_span("warm_up", registry=reg, annotate=notes)


def test_the_process_age_comes_from_the_os_and_is_absent_without_proc(
        tmp_path, monkeypatch):
    age = spans.process_age_s()
    assert age is not None and 0.0 < age < time.clock_gettime(
        time.CLOCK_BOOTTIME)
    assert spans.process_age_s(str(tmp_path)) is None

    reg = Counters()
    monkeypatch.setattr(spans, "_before_trainer_published", False)
    monkeypatch.setattr(spans, "process_age_s", lambda: None)
    assert spans.publish_before_trainer(reg) is None
    assert "setup.before_trainer_s" not in reg.snapshot()
    monkeypatch.setattr(spans, "process_age_s", lambda: 12.5)
    # Once a process: the first construction has had its turn.
    assert spans.publish_before_trainer(reg) is None
    monkeypatch.setattr(spans, "_before_trainer_published", False)
    assert spans.publish_before_trainer(reg) == 12.5
    assert reg.get("setup.before_trainer_s") == 12.5


# --------------------------------------------------------- the benchmark side

def test_counter_value_sums_what_is_published_and_reads_nothing_else(
        monkeypatch):
    read = _counter_value().read
    reg = Counters()
    monkeypatch.setattr(global_counters, "_counts", reg._counts)
    monkeypatch.setattr(global_counters, "_gauges", reg._gauges)
    assert read({}, ["setup.trainer_s"]) is None
    reg.gauge("setup.trainer_s", 1.5)
    reg.inc("compile.programs", 4)
    assert read({}, ["setup.trainer_s"]) == 1.5
    assert read({}, ["setup.trainer_s", "compile.programs", "absent"]) == 5.5
    assert read({}, ["compile.programs"], scale=0.5) == 2.0


@pytest.mark.parametrize("metric", sorted(SETUP_METRICS))
def test_each_setup_metric_reads_a_declared_counter_in_every_cell(metric):
    spec = json.loads(
        (REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert spec["reader"] == "counter_value"
    assert spec["args"] == {"names": [SETUP_METRICS[metric]]}
    assert SETUP_METRICS[metric] in METRICS
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry == {"name": metric, "unit": spec["unit"], "better": "lower",
                     "source": "program_counter", "layer": "entry",
                     "moves": "setup_s"}
