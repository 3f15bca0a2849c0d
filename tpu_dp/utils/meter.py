"""Step-time / images-per-second metering.

The reference has zero timing instrumentation (SURVEY.md §5 "Tracing /
profiling — ABSENT"), but images/sec/chip is the BASELINE.json north-star
metric, so the meter is a required subsystem. Excludes a configurable number
of warmup steps (compilation happens on step 0).
"""

from __future__ import annotations

import time


class ThroughputMeter:
    def __init__(self, warmup_steps: int = 2):
        # The measurement window opens at the warmup-th step's dispatch, so
        # at least one step must be excluded — a rate needs a start stamp.
        self.warmup_steps = max(1, warmup_steps)
        self.reset()

    def reset(self) -> None:
        self._steps = 0
        self._images = 0
        self._start: float | None = None
        self._last: float | None = None

    def step(self, batch_size: int) -> float:
        """Call after each dispatched step; returns the dispatch timestamp
        (`time.perf_counter` seconds — the span recorder's clock, so obs
        code can share this stamp instead of reading the clock twice)."""
        now = time.perf_counter()
        self._steps += 1
        if self._steps == self.warmup_steps:
            self._start = now
            self._images = 0
        elif self._steps > self.warmup_steps:
            self._images += batch_size
        self._last = now
        return now

    def mark(self, images: int | None = None) -> float:
        """Record 'now' as the end of measured work; returns the fence
        timestamp.

        Call after a true host↔device fence (e.g. fetching a metric scalar):
        step() timestamps dispatch, which runs ahead of device execution, so
        without a fence the rate would be a dispatch rate, not a throughput.
        The returned stamp is the same fence time `tpu_dp.obs` uses as the
        end of a step's ``device`` span — one fence, two consumers.

        ``images`` credits a completed batch *at the fence* — the serving
        pattern (`tpu_dp.serve`), where batch sizes vary per bucket and
        work is not back-to-back, so crediting at dispatch (step()'s fixed
        per-call ``batch_size``) would attribute the wrong bucket's images
        to the window edges. Mark-credited flow: call ``step(0)`` at each
        dispatch (advances the warmup window without double-counting) and
        ``mark(batch_images)`` at each fence; images are counted iff their
        fence lands inside the open measurement window — including the
        window-opening step's own batch, whose execution is in-window even
        though its dispatch stamp *is* the window start.
        """
        now = time.perf_counter()
        if self._start is None:
            return now  # window not open: warmup fences are not measured
        if images and self._steps >= self.warmup_steps:
            self._images += int(images)
            self._last = now
        elif self._steps > self.warmup_steps:
            self._last = now
        return now

    @property
    def measured_steps(self) -> int:
        return max(0, self._steps - self.warmup_steps)

    @property
    def elapsed(self) -> float:
        if self._start is None or self._last is None:
            return 0.0
        return self._last - self._start

    @property
    def items_per_sec(self) -> float:
        """The counted items a second: images, or a token model's tokens
        (the caller says how many a step holds)."""
        return self._images / self.elapsed if self.elapsed > 0 else 0.0

    @property
    def images_per_sec(self) -> float:
        """`items_per_sec` under the name it has where an item is an image."""
        return self.items_per_sec

    @property
    def step_time_ms(self) -> float:
        n = self.measured_steps
        return (self.elapsed / n) * 1e3 if n > 0 else 0.0
