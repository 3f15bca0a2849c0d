"""The benchmark's own step hook: what it learns from the loop without
stalling it.

Appended last to the trainer's hook list, it

- hands each step's ``loss`` array to a watcher thread that blocks on it and
  stamps the completion time, so the loop itself is never fenced;
- in the first epoch, which is the warm-up, reads what the comparison needs
  from the timed object's own state: the parameters before step 1, the
  optimizer's state after step 1 (from which the configuration's family
  reads back the first gradient as the optimizer got it) and the parameters
  after step 3, each reduced to norms by leaf on the device;
- in a traced run, starts and stops `jax.profiler` around a few steady
  steps inside one epoch and writes host annotations (``bench.data_wait``,
  ``bench.dispatch``, ``bench.epoch_boundary``) on the trace's clock.
"""

from __future__ import annotations

import queue
import sys
import threading
import time

import jax
import jax.numpy as jnp

from compare import leaf_norms as _leaf_norms
from tpu_dp.train.hooks import StepEvent, StepHook

FOLLOWED_STEPS = 3


@jax.jit
def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@jax.jit
def _delta_norms(params, p0):
    return _leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, params, p0))


class LoopHook(StepHook):
    def __init__(self, trainer, first_gradient, steps_per_epoch: int,
                 trace_dir: str | None = None):
        """``first_gradient(opt_state, params0)`` is the family's pure
        function from the optimizer's state after step 1 and the parameters
        before it to the first gradient, a tree of the parameters' shape."""
        super().__init__(trainer)
        self._first_grad_norms = jax.jit(
            lambda opt_state, p0: _leaf_norms(first_gradient(opt_state, p0)))
        self.spe = int(steps_per_epoch)
        self.done: list[tuple[float, float]] = []  # (completion time, loss)
        self._q: queue.Queue = queue.Queue()
        self._watcher = threading.Thread(
            target=self._watch, name="bench-watcher", daemon=True)
        self._watcher.start()
        self.steps_seen = 0
        # first-steps capture (device values until `first_steps()` reads them)
        self._p0 = None
        self._grad1 = None
        self._delta = None
        # tracing
        self.trace_dir = trace_dir
        self.trace_after = None      # perf_counter() after which to trace
        self.traced = trace_dir is None
        self._tracing = False
        self._trace_left = 0
        n = min(12, self.spe - 2)
        self._trace_len = max(1, n)
        self._trace_at = max(1, (self.spe - n) // 2)
        self._ann = None
        self._annotate = trace_dir is not None

    # ------------------------------------------------------------ watcher
    def _watch(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                value = float(item)  # blocks until the step has completed
            except Exception:  # a failed step is a non-finite loss
                value = float("nan")
            self.done.append((time.perf_counter(), value))

    def finish(self) -> None:
        """Stop the watcher once every handed step has been stamped."""
        self._q.put(None)
        self._watcher.join(timeout=120)
        if self._watcher.is_alive():
            raise RuntimeError("the completion watcher did not finish")
        self._span(None)

    # --------------------------------------------------------- annotations
    def _span(self, name: str | None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if name is not None and self._annotate:
            self._ann = jax.profiler.TraceAnnotation("bench." + name)
            self._ann.__enter__()

    # ------------------------------------------------------------ lifecycle
    def capture_initial(self) -> None:
        """Before step 1: keep the parameters the timed object starts from."""
        self._p0 = _copy_tree(self.tr.state.params)

    def on_epoch_start(self, epoch: int) -> None:
        self._epoch_step = 0
        self._span("data_wait")

    def on_window_start(self, first_step: int, n: int) -> None:
        if (not self.traced and not self._tracing
                and self.trace_after is not None
                and time.perf_counter() >= self.trace_after
                and self._epoch_step == self._trace_at):
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            print(f"[bench] trace on at epoch step {self._epoch_step}",
                  file=sys.stderr, flush=True)
            self._tracing = True
            self._trace_left = self._trace_len
        self._span("dispatch")

    def on_step_end(self, ev: StepEvent) -> None:
        self._q.put(ev.window[-1]["loss"])
        self.steps_seen += ev.n
        self._epoch_step += ev.n
        if self._p0 is not None:
            if self.steps_seen == 1:
                self._grad1 = self._first_grad_norms(
                    self.tr.state.opt_state, self._p0)
            elif self.steps_seen == FOLLOWED_STEPS:
                self._delta = _delta_norms(self.tr.state.params, self._p0)
                self._p0 = None
        if self._tracing:
            self._trace_left -= ev.n
            if self._trace_left <= 0:
                self._span(None)
                jax.profiler.stop_trace()
                self._tracing = False
                self.traced = True
        self._span("epoch_boundary" if self._epoch_step >= self.spe
                   else "data_wait")

    def first_steps(self) -> dict:
        """Losses of the first steps, the first gradient's norm and the
        parameters' change after them, by leaf, as host floats."""
        if self._grad1 is None or self._delta is None:
            raise RuntimeError(
                f"fewer than {FOLLOWED_STEPS} steps ran before the window")
        host = lambda d: {k: float(v) for k, v in d.items()}  # noqa: E731
        return {"loss": [v for _, v in self.done[:FOLLOWED_STEPS]],
                "grad1": host(self._grad1), "delta": host(self._delta)}
