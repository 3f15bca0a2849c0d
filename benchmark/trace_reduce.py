"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Reads with `jax.profiler.ProfileData` alone. A TPU chip is a plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per executed
program), ``XLA Ops`` (one per HLO op, a ``while`` enclosing its body's ops)
and ``Async XLA Ops`` (a start-to-done span per asynchronous op). Host
threads are lines of ``/host:CPU``; the benchmark's own annotations
(``bench.*``) lie there on the same clock.

The traced window of a device runs from the start of the second execution
of the step program (the program with the most device time) to the start
of the last one: whole periods only, each a step with the gap after it, so
neither a step cut off by the trace's start nor one cut off by its end is
counted. Everything is clipped to that window:

- busy: the union of the op intervals;
- collectives: the union of the intervals of collective ops (from either
  ops line), and the part of it during which no other op runs (exposed);
- op ranking: self time by op name, a ``while`` less its body;
- idle gaps: the complement of busy, longest first, each named by the
  host annotation that covers the moment the device fell idle.
"""

from __future__ import annotations

import re
from pathlib import Path

Interval = tuple[float, float]

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "collective-broadcast")
_SUFFIX = re.compile(r"\.\d+$")
HOST_SPAN_PREFIX = "bench."


def merge(intervals: list[Interval]) -> list[Interval]:
    """Sorted union of (start, end) intervals."""
    out: list[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged: list[Interval]) -> float:
    return sum(e - s for s, e in merged)


def subtract_total(a: list[Interval], b: list[Interval]) -> float:
    """|A minus B| for two merged interval lists."""
    out = 0.0
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k >= len(b) or b[k][0] >= e:
                out += e - cur
                break
            bs, be = b[k]
            if bs > cur:
                out += bs - cur
            cur = max(cur, be)
            k += 1
    return out


def complement(merged: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The gaps of a merged list inside [lo, hi]."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion``."""
    name = event_name.split(" = ")[0].lstrip("%")
    return _SUFFIX.sub("", name)


def is_collective(name: str) -> bool:
    base = name[:-6] if name.endswith("-start") else name
    base = base[:-5] if base.endswith("-done") else base
    return base.startswith(COLLECTIVE_KINDS)


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self seconds by op name; an enclosing op is charged less its body."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        if stack:
            stack[-1][2] -= (min(e, stack[-1][0]) - s)
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def leaves(events: list[tuple[float, float, str]]) -> list[tuple[float, float, str]]:
    """Events that enclose no other event."""
    ordered = sorted(events, key=lambda t: (t[0], -t[1]))
    out = []
    for i, (s, e, name) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[0] >= e:
            out.append((s, e, name))
    return out


def _events(line) -> list[tuple[float, float, str]]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


def reduce_device(modules, ops, async_ops, host_spans) -> dict | None:
    """One device plane's numbers; None where no step program ran thrice."""
    by_module: dict[str, float] = {}
    for s, e, name in modules:
        by_module[name] = by_module.get(name, 0.0) + (e - s)
    if not by_module:
        return None
    step_name = max(by_module, key=by_module.get)
    steps = sorted((s, e) for s, e, name in modules if name == step_name)
    if len(steps) < 3:
        return None
    lo, hi = steps[1][0], steps[-1][0]
    n_steps = len(steps) - 2
    named = [(max(s, lo), min(e, hi), op_name(name)) for s, e, name in ops
             if e > lo and s < hi]
    busy = merge([(s, e) for s, e, _ in named])
    coll = [(s, e) for s, e, name in named if is_collective(name)]
    coll += clip([(s, e) for s, e, name in async_ops
                  if is_collective(op_name(name))], lo, hi)
    coll_m = merge(coll)
    compute_m = merge([(s, e) for s, e, name in leaves(named)
                       if not is_collective(name)])
    gaps = sorted(complement(busy, lo, hi), key=lambda g: g[0] - g[1])
    ranked = sorted(self_times(named).items(), key=lambda kv: -kv[1])
    return {
        "step_program": step_name,
        "steps": n_steps,
        "window_s": hi - lo,
        "busy_s": total(busy),
        "collective_s": total(coll_m),
        "collective_exposed_s": subtract_total(coll_m, compute_m),
        "collective_ops": len(coll),
        "device_ops": [[name, sec] for name, sec in ranked[:10]],
        "idle_gaps": [[_host_span_at(host_spans, s), e - s]
                      for s, e in gaps[:10]],
    }


def _host_span_at(host_spans, t: float) -> str:
    for s, e, name in host_spans:
        if s <= t < e:
            return name
    return "other"


def find_xplane(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_trace(path) -> dict:
    """All device planes of one trace, and the fullest device's numbers."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host_spans: list[tuple[float, float, str]] = []
    planes = list(data.planes)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            host_spans += [(s, e, name[len(HOST_SPAN_PREFIX):])
                           for s, e, name in _events(line)
                           if name.startswith(HOST_SPAN_PREFIX)]
    host_spans.sort()
    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        dev = reduce_device(
            _events(lines["XLA Modules"]), _events(lines["XLA Ops"]),
            _events(lines["Async XLA Ops"]) if "Async XLA Ops" in lines
            else [], host_spans)
        if dev is not None:
            dev["plane"] = plane.name
            devices.append(dev)
    if not devices:
        return {"devices": []}
    fullest = max(devices, key=lambda d: d["busy_s"])
    return {
        "devices": devices,
        "fullest": fullest,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "window_s": sum(d["window_s"] for d in devices) / len(devices),
    }
