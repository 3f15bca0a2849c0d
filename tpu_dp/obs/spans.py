"""Per-step span recording: where a training step's wall time actually goes.

A single throughput number cannot tell apart the places a step can lose
time ("Scalable Training of Language Models using JAX pjit and TPUv4",
arXiv:2204.06514 — step-time *breakdowns* are how pod-scale runs stay
debuggable). The trainer's loop therefore names everything the host does
in one iteration. The spans tile it: contiguous, non-overlapping, in loop
order, so their sum is the iteration's wall time (`STEP_SPANS`):

- ``data_wait``    — blocked in the pipeline's ``next()``, nothing else:
  host gather, a prefetch that fell behind, or back-pressure from a device
  that is behind (the ``loop.*`` counters tell which);
- ``pre_dispatch`` — the ``on_window_start`` hooks and the guard's input;
- ``h2d``          — (``full`` only) waiting for the batch's host→device
  transfer to land (zero when prefetch overlapped it);
- ``inflight_wait`` — the loop's own bound on its lead: blocked until the
  dispatch about to be made will be at most the `trainer.MAX_INFLIGHT`th
  unfinished (about a step's time whenever the device is the slower of the
  two, which is the aim);
- ``dispatch``     — the host's own cost of launching the compiled step;
- ``device``       — (``full`` only) from dispatch return to a
  device→host scalar fetch, the same fence discipline as
  `ThroughputMeter.mark()` (`tpu_dp/utils/meter.py`);
- ``telemetry``    — what the recorder, the efficiency meter and the
  gauges cost themselves;
- ``accumulate``   — keeping the dispatch's metrics for the host's sums
  (unstacking a window's for the hooks), the meter, the log line and its
  fetch;
- ``hooks``        — the ``on_step_end`` sweep.

Two more are per epoch, each on one record (`EPOCH_SPANS`):
``epoch_fence`` on the epoch's last step (from the end of its hooks to the
return of the stats fetch that drains the device) and ``epoch_gap`` on the
epoch's first step (from that return to the return of this epoch's first
dispatch: device idle time measured on the host). ``epoch_gap`` overlays
the first step's tiles up to ``dispatch``; `tile_ms` leaves it out.

`SpanRecorder` is the low-overhead sink: a ring buffer (`deque(maxlen=)`)
of per-step records, each ``{"step", "ts", "spans": {name: ms}}``, with
percentile rollups computed only when asked (log boundaries, epoch ends,
export). `SpanRecorder.begin` is the one span primitive: it ends the open
span, adds its milliseconds to the current step's record and opens the
next, under a ``jax.profiler.TraceAnnotation("tpu_dp.<name>", step=N)``
that puts the span on the profiler's clock beside the device planes.
Windowed dispatch (`train.steps_per_call > 1`) measures per *window* and
attributes the totals evenly across the window's steps (documented in
docs/OBSERVABILITY.md — per-step attribution inside one device-side scan
is not observable from the host).

Set-up has spans of its own, once per construction and at every
``train.obs`` (`SETUP_SPANS`, `setup_span`): on the same clock and under the
same annotation convention (``tpu_dp.setup.<name>``), each published on
close as the gauge ``setup.<name>_s``. ``before_trainer`` runs from the
process's start as the OS gives it (`process_age_s`) to the first
construction; ``trainer`` is the whole of ``Trainer.__init__``, with
``init_state`` inside it; ``caller`` from its return to the first
``train_epoch``; ``first_epoch`` from that call's entry to the return of
its fence. What of them went to making programs is
`tpu_dp.obs.compiles`'s to say.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Callable, Iterable, Mapping

from tpu_dp.obs.counters import counters as _registry

#: The spans that tile one iteration of the trainer's loop, in loop order.
STEP_SPANS = ("data_wait", "pre_dispatch", "h2d", "inflight_wait", "dispatch",
              "device", "telemetry", "accumulate", "hooks")
#: Per-epoch spans, each on one record: the last step's, the first step's.
EPOCH_SPANS = ("epoch_fence", "epoch_gap")
#: Set-up's spans, in the order a run goes through them (``init_state``
#: inside ``trainer``); each is the gauge ``setup.<name>_s``.
SETUP_SPANS = ("before_trainer", "trainer", "init_state", "caller",
               "first_epoch")


def tile_ms(spans: Mapping[str, float]) -> float:
    """A record's wall time: every span but ``epoch_gap``, which overlays
    the first step's ``data_wait`` … ``dispatch``."""
    return sum(v for k, v in spans.items() if k != "epoch_gap")


def percentile(sorted_values: list[float], q: float) -> float:
    """Linear-interpolated percentile of an ascending list (q in [0, 100]).

    Pure Python on sorted input: rollups run at log boundaries over ring
    buffers of a few thousand floats — numpy would be an import and an
    array copy for no measurable win.
    """
    if not sorted_values:
        raise ValueError("percentile of an empty sequence")
    if len(sorted_values) == 1:
        return sorted_values[0]
    rank = (q / 100.0) * (len(sorted_values) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_values) - 1)
    frac = rank - lo
    return sorted_values[lo] * (1.0 - frac) + sorted_values[hi] * frac


class SpanRecorder:
    """Ring-buffered per-step span records with percentile rollups.

    ``capacity`` bounds memory (and the Perfetto export window): a
    multi-day run keeps the most recent ``capacity`` steps, which is what
    a "why is it slow *now*" investigation needs.
    """

    def __init__(self, capacity: int = 4096,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Callable | None = None):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = int(capacity)
        self._records: deque[dict] = deque(maxlen=self.capacity)
        self.total_recorded = 0  # lifetime count, beyond the ring
        self._clock = clock
        if annotate is None:
            from jax.profiler import TraceAnnotation as annotate  # lazy
        self._annotate = annotate
        self._open: tuple | None = None  # (name, start, annotation, rec)
        self._step = 0
        self._ts = 0.0
        self._held: dict[str, float] = {}  # closed, not yet on a record
        self._window: list[dict] | None = None  # the iteration's records

    # ------------------------------------------------- the span primitive
    def begin(self, name: str, step: int | None = None,
              rec: dict | None = None) -> float:
        """End the open span and open ``name`` on the same clock reading,
        so consecutive spans tile the time between them. Returns it.

        ``step`` starts a new iteration at that global step: spans ended
        from here on are held until `open_window` makes the iteration's
        records and add to those afterwards; what an abandoned iteration
        held (the ``next()`` that found the epoch exhausted) is dropped.
        ``rec`` names the one record this span is added to instead, after
        the iteration is over; what was held goes with it (the time since
        that record's last span ended).
        """
        now = self._clock()
        self._close(now)
        if step is not None:
            self._step, self._ts = int(step), time.time()
            self._held, self._window = {}, None
        carried = 0.0
        if rec is not None:
            carried, self._held = sum(self._held.values()), {}
        ann = self._annotate(
            "tpu_dp." + name,
            step=self._step if rec is None else rec["step"])
        ann.__enter__()
        self._open = (name, now - carried / 1e3, ann, rec)
        return now

    def end(self) -> float:
        """End the open span; returns the clock reading it ended on."""
        now = self._clock()
        self._close(now)
        return now

    def abandon(self) -> None:
        """Drop the open span unrecorded (a hook raised out of the loop)."""
        if self._open is not None:
            self._open[2].__exit__(None, None, None)
            self._open = None

    def _close(self, now: float) -> None:
        if self._open is None:
            return
        name, start, ann, rec = self._open
        self._open = None
        ann.__exit__(None, None, None)
        ms = (now - start) * 1e3
        records = self._window if rec is None else [rec]
        if records is None:
            self._held[name] = self._held.get(name, 0.0) + ms
            return
        share = ms / len(records)
        for j, r in enumerate(records):
            r["spans"][name] = r["spans"].get(name, 0.0) + share
            r["ts"] += j * share / 1e3  # keep a window's steps back to back

    @property
    def held(self) -> Mapping[str, float]:
        """The iteration's ended spans (ms) that are on no record yet."""
        return self._held

    def open_window(self, n_steps: int, gen: int = 0) -> list[dict]:
        """Make the iteration's records, ``n_steps`` from the step it began
        at, from what is held (an even split, as `record_window`); spans
        ended later add to them the same way."""
        self._window = self.record_window(self._step, n_steps, self._held,
                                          ts=self._ts, gen=gen)
        self._held = {}
        return self._window

    def record(self, step: int, spans: Mapping[str, float],
               ts: float | None = None, gen: int = 0) -> dict:
        """Append one per-step record; ``spans`` maps name → milliseconds.

        ``ts`` is the step's wall-clock start (``time.time()`` seconds);
        stamped now when omitted. ``gen`` is the rollback generation the
        step ran under (stamped only when nonzero): the Perfetto export
        renders each generation as its own track group, so a replayed
        step never overdraws the attempt it rewound
        (docs/OBSERVABILITY.md "Rollback rewind guard"). Returns the
        stored record.
        """
        rec = {
            "step": int(step),
            "ts": time.time() if ts is None else float(ts),
            "spans": {k: float(v) for k, v in spans.items()},
        }
        if gen:
            rec["gen"] = int(gen)
        self._records.append(rec)
        self.total_recorded += 1
        return rec

    def record_window(self, first_step: int, n_steps: int,
                      spans: Mapping[str, float],
                      ts: float | None = None, gen: int = 0) -> list[dict]:
        """Attribute one window's span totals evenly across its steps.

        A window of ``n_steps`` compiled into one dispatch is observable
        from the host only as totals; each of its steps gets total/n and a
        start time spaced by the window's per-step share. Returns the
        ``n_steps`` records appended (the trainer forwards them to the
        per-step `metrics.jsonl` sink at ``obs=full``).
        """
        n = max(1, int(n_steps))
        ts0 = time.time() if ts is None else float(ts)
        per = {k: float(v) / n for k, v in spans.items()}
        stride_s = tile_ms(per) / 1e3
        return [
            self.record(first_step + j, per, ts=ts0 + j * stride_s, gen=gen)
            for j in range(n)
        ]

    def records(self) -> list[dict]:
        """The ring's contents, oldest first."""
        return list(self._records)

    def __len__(self) -> int:
        return len(self._records)

    def rollup(self, spans: Iterable[str] | None = None) -> dict[str, dict]:
        """Per-span percentiles over the ring: p50/p95/p99, mean, max, n.

        ``spans`` restricts the rollup; default is every span name seen.
        Milliseconds, rounded to 3 decimals (µs resolution — below that is
        clock noise).
        """
        by_name: dict[str, list[float]] = {}
        for rec in self._records:
            for name, v in rec["spans"].items():
                by_name.setdefault(name, []).append(v)
        names = list(by_name) if spans is None else [
            s for s in spans if s in by_name
        ]
        out: dict[str, dict] = {}
        for name in names:
            vals = sorted(by_name[name])
            out[name] = {
                "p50": round(percentile(vals, 50), 3),
                "p95": round(percentile(vals, 95), 3),
                "p99": round(percentile(vals, 99), 3),
                "mean": round(sum(vals) / len(vals), 3),
                "max": round(vals[-1], 3),
                "n": len(vals),
            }
        return out

    def reset(self) -> None:
        self._records.clear()


class InflightSteps:
    """Did the device wait for the host? Counted where the loop dispatches.

    Holds the loss arrays of the windows dispatched and not yet seen
    finished. Just before a dispatch, `before_dispatch` drops those whose
    ``is_ready()`` is true (non-blocking: no fence) and publishes
    ``loop.dispatches``, ``loop.inflight_sum`` and the gauge
    ``loop.inflight_steps`` (steps enqueued and not finished), and
    ``loop.dispatch_onto_idle`` (+1 when nothing was left: the previous
    step had finished, the device had nothing queued). An epoch's first
    dispatch follows a fence that drained the device: ``epoch_gap`` counts
    it, and it is left out here. The loop asks on arrival, before its
    ``inflight_wait`` (which then holds the unfinished, the new dispatch
    among them, to `trainer.MAX_INFLIGHT`), so ``loop.inflight_steps`` reads
    that bound wherever the device is the slower, and less wherever the
    host is.
    """

    def __init__(self, registry=_registry):
        self._registry = registry
        self._queue: deque[tuple] = deque()  # (loss array, steps)

    def before_dispatch(self, first_of_epoch: bool = False) -> None:
        q = self._queue
        if first_of_epoch:
            q.clear()
            return
        while q and q[0][0].is_ready():
            q.popleft()
        steps = sum(n for _, n in q)
        reg = self._registry
        reg.inc("loop.dispatches")
        reg.inc("loop.inflight_sum", steps)
        reg.gauge("loop.inflight_steps", steps)
        # inc(0) creates the counter: "never onto an idle device" is a
        # statement, absence is not.
        reg.inc("loop.dispatch_onto_idle", 0.0 if q else 1.0)

    def dispatched(self, loss, n_steps: int) -> None:
        self._queue.append((loss, int(n_steps)))


# ------------------------------------------------------------------ set-up

def process_age_s(proc: str = "/proc") -> float | None:
    """Seconds since this process started, as the OS counts them: its start
    in ``<proc>/self/stat`` (clock ticks since boot) against the boot clock.
    ``/proc/stat``'s ``btime`` is whole seconds, too coarse for this; the
    boot clock is the one the start is counted on. None where there is no
    such file."""
    try:
        with open(os.path.join(proc, "self", "stat")) as f:
            stat = f.read()
        # Field 22, counted past the command name, which may hold spaces.
        ticks = int(stat[stat.rindex(")") + 2:].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


_before_trainer_published = False


def publish_before_trainer(registry=_registry) -> float | None:
    """The gauge ``setup.before_trainer_s``: the process's age at the first
    trainer's construction, once a process (a later trainer's time before
    it is another trainer's and the caller's); none where the OS does not
    say when the process started."""
    global _before_trainer_published
    if _before_trainer_published:
        return None
    _before_trainer_published = True
    age = process_age_s()
    if age is not None:
        registry.gauge("setup.before_trainer_s", age)
    return age


class setup_span:
    """One set-up phase, timed on `time.perf_counter` (the loop spans'
    clock) under ``jax.profiler.TraceAnnotation("tpu_dp.setup.<name>")``
    and published on close as the gauge ``setup.<name>_s``. A context
    manager, or opened here and closed elsewhere where the phase crosses
    calls (``caller``, ``first_epoch``)."""

    def __init__(self, name: str, registry=_registry,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Callable | None = None):
        # The gauge's name is computed, so DP405 cannot see it: only the
        # declared phases (`counters.METRICS`) are published.
        if name not in SETUP_SPANS:
            raise ValueError(f"{name!r} is not a set-up span {SETUP_SPANS}")
        if annotate is None:
            from jax.profiler import TraceAnnotation as annotate  # lazy
        self.name = name
        self._registry, self._clock = registry, clock
        self._ann = annotate("tpu_dp.setup." + name)
        self._ann.__enter__()
        self.start = clock()

    def close(self) -> float:
        """End the phase; returns its seconds, which it publishes."""
        seconds = self._clock() - self.start
        self._ann.__exit__(None, None, None)
        self._registry.gauge("setup." + self.name + "_s", seconds)
        return seconds

    def __enter__(self) -> "setup_span":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
