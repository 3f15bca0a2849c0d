"""Which programs JAX traces, lowers, compiles or loads, and for how long.

JAX 0.9 announces each stage of making a program through `jax.monitoring`,
with the program's ``fun_name``: a scalar at the stage's start and a
duration at its end for ``/jax/core/compile/jaxpr_trace_duration`` (the
function traced to a jaxpr), ``jaxpr_to_mlir_module_duration`` (lowered)
and ``backend_compile_duration`` (compiled, or loaded from the persistent
cache: the stage encloses the cache's read, `jax/_src/compiler.py`
``compile_or_get_cached``), and the event
``/jax/compilation_cache/cache_hits`` inside a backend stage the cache
served. A stage can run inside another on the same thread (a nested jit is
traced inside its caller's trace; an eager op on constants is compiled
inside a trace), so each keeps its own seconds: its duration less the
stages it enclosed. The listener publishes the counters ``compile.trace_s``,
``compile.lower_s``, ``compile.backend_s``, ``compile.programs`` (backend
stages), ``compile.cache_hits`` and ``compile.compiled_anew`` (backend
stages the cache did not serve; JAX's own ``cache_misses`` fires only where
it writes an entry, so it misses programs under the size threshold), and
keeps a table by program.

A trainer's construction starts a set-up (`CompileListener.begin`): the
table starts empty and the listener is quiet. At the first epoch's fence
the trainer freezes it (`CompileListener.freeze`): the table's totals
become the gauges ``setup.trace_s`` (trace and lower), ``setup.compile_s``
(backend) and ``setup.compiled_anew``, the train programs' jit cache
entries the gauge ``setup.step_entries``, and one line says where set-up
went.
From then on every program compiled or loaded gets a line of its own with
its seconds: which step compiled again, mid-run.
"""

from __future__ import annotations

import re
import threading
from typing import Callable

from tpu_dp.obs.counters import counters as _registry
from tpu_dp.obs.spans import SETUP_SPANS

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: A row of the table: seconds by stage, then counts.
ROW = ("trace_s", "lower_s", "backend_s", "traces", "programs",
       "cache_hits", "compiled_anew")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")  # lowered "jit(step)" is "step"


def _program_name(fun_name: str) -> str:
    m = _WRAPPED.match(fun_name)
    return m.group(1) if m else fun_name


def _seconds(row: dict) -> float:
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


class CompileListener:
    """The `jax.monitoring` callbacks, the table by program, and the set-up's
    freeze. One a process (`install`)."""

    def __init__(self, registry=_registry, log: Callable | None = None):
        if log is None:
            from tpu_dp.utils import log0 as log
        self._registry = registry
        self.log = log
        self._local = threading.local()
        self.table: dict[str, dict[str, float]] = {}
        self.quiet = True

    def _frames(self) -> list:
        # Open stages of this thread, innermost last, each
        # [stage, seconds of the stages it enclosed, served by the cache].
        frames = getattr(self._local, "frames", None)
        if frames is None:
            frames = self._local.frames = []
        return frames

    def _pending(self) -> dict:
        pending = getattr(self._local, "pending", None)
        if pending is None:
            pending = self._local.pending = {}
        return pending

    def on_scalar(self, event: str, value, **kwargs) -> None:
        stage = _STAGES.get(event)
        if stage is not None:
            self._frames().append([stage, 0.0, False])

    def on_event(self, event: str, **kwargs) -> None:
        if event != _CACHE_HIT:
            return
        self._registry.inc("compile.cache_hits")
        frames = self._frames()
        if frames and frames[-1][0] == "backend_s":
            frames[-1][2] = True

    def on_duration(self, event: str, seconds: float,
                    fun_name: str = "", **kwargs) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        frames = self._frames()
        enclosed, hit = 0.0, False
        if frames and frames[-1][0] == stage:
            _, enclosed, hit = frames.pop()
        if frames:
            frames[-1][1] += seconds
        own = max(seconds - enclosed, 0.0)
        name = _program_name(str(fun_name))
        row = self.table.get(name)
        if row is None:
            row = self.table[name] = dict.fromkeys(ROW, 0.0)
        row[stage] += own
        reg = self._registry
        if stage == "trace_s":
            row["traces"] += 1
            reg.inc("compile.trace_s", own)
        elif stage == "lower_s":
            reg.inc("compile.lower_s", own)
        else:
            row["programs"] += 1
            row["cache_hits" if hit else "compiled_anew"] += 1
            reg.inc("compile.backend_s", own)
            reg.inc("compile.programs")
            if not hit:
                reg.inc("compile.compiled_anew")
        if self.quiet or frames:
            return
        # After set-up, a line a program: its trace and lowering wait here
        # for its backend stage.
        pending = self._pending()
        if stage != "backend_s":
            pending[(name, stage)] = seconds
            return
        self.log("compile: %s traced in %.3f s, lowered in %.3f s, %s %.3f s",
                 name, pending.pop((name, "trace_s"), 0.0),
                 pending.pop((name, "lower_s"), 0.0),
                 "loaded from the cache in" if hit else "compiled in",
                 seconds)

    def begin(self) -> None:
        """A set-up starts: an empty table, no line a program."""
        self.table = {}
        self.quiet = True

    def freeze(self, step_entries: int | None = None) -> dict[str, float]:
        """The set-up's totals as the ``setup.*`` gauges, one line that says
        where set-up went (the spans, the totals, the three longest
        programs), and a line a program from here on. ``step_entries``, the
        jit cache entries the train programs hold (one a program used, where
        each was made once), is the gauge ``setup.step_entries`` and a field
        of the line. Returns the totals."""
        rows = list(self.table.values())
        total = {k: sum(r[k] for r in rows) for k in ROW}
        reg = self._registry
        reg.gauge("setup.trace_s", total["trace_s"] + total["lower_s"])
        reg.gauge("setup.compile_s", total["backend_s"])
        reg.gauge("setup.compiled_anew", total["compiled_anew"])
        entries = ""
        if step_entries is not None:
            reg.gauge("setup.step_entries", step_entries)
            entries = f", step entries {step_entries}"
        snap = reg.snapshot()
        spans = {k: snap[f"setup.{k}_s"] for k in SETUP_SPANS
                 if f"setup.{k}_s" in snap}
        longest = sorted(self.table.items(), key=lambda kv: -_seconds(kv[1]))
        self.log(
            "set-up: %s; programs %d (%d compiled anew)%s, trace %.2f s, "
            "lower %.2f s, compile %.2f s; longest: %s",
            ", ".join(f"{k} {v:.2f} s" for k, v in spans.items()),
            total["programs"], total["compiled_anew"], entries,
            total["trace_s"],
            total["lower_s"], total["backend_s"],
            "; ".join(
                f"{name} {_seconds(r):.2f} s (traced {r['traces']:.0f}x "
                f"{r['trace_s']:.2f} s, lowered {r['lower_s']:.2f} s, "
                f"compiled {r['compiled_anew']:.0f}, loaded "
                f"{r['cache_hits']:.0f} in {r['backend_s']:.2f} s)"
                for name, r in longest[:3]))
        self.quiet = False
        return total


_listener: CompileListener | None = None
_install_lock = threading.Lock()


def install() -> CompileListener:
    """The process's listener, registered with `jax.monitoring` on the first
    call only: a second call registers nothing and counts nothing twice."""
    global _listener
    with _install_lock:
        if _listener is None:
            from jax import monitoring

            listener = CompileListener()
            monitoring.register_scalar_listener(listener.on_scalar)
            monitoring.register_event_listener(listener.on_event)
            monitoring.register_event_duration_secs_listener(
                listener.on_duration)
            _listener = listener
    return _listener
