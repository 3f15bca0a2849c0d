"""xplane-proto parsing: one reusable reader for `jax.profiler` traces.

The in-run comm/compute attribution layer (`tpu_dp.obs.commprof`) reads
traces through this module. A captured trace directory holds one
``*.xplane.pb`` per capture; this module finds the newest, parses it with
tensorflow's bundled xplane proto, and aggregates the op events into a
backend-neutral summary:

- **Device planes** (TPU): planes named ``/device:...`` carry an
  ``"XLA Ops"`` line whose events have ``hlo_category`` /
  ``model_flops`` / ``bytes_accessed`` stats; the ``%while`` scan
  wrapper spans the whole window and is excluded from op totals (it is
  the window clock instead).
- **Host thunk planes** (the CPU backend): there is no device plane;
  the ``/host:CPU`` plane's ``tf_XLA*`` thread lines carry one event per
  executed thunk, named after the HLO op (``all-reduce.1``,
  ``slice_concatenate_fusion.2``, ...) with no stats. Each virtual
  device executes its own copy, so raw event counts normalize by
  (devices x steps) — the property the commprof reconciliation check
  is built on.

Protobuf backends: some environments' C++/upb protobuf runtime rejects
the TF-generated xplane module (a ``TypeError`` at import, not an
``ImportError``). The historical workaround — re-exec the process with
``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` — lives here behind
two documented helpers: `reexec_with_python_protobuf` (CLI entry points;
replaces the process) and `summarize_robust` (library consumers; retries
the parse in a subprocess with the env var set, so an in-run caller —
a Trainer mid-training — never re-execs itself).

``python -m tpu_dp.obs.xplane <trace_dir> [--json]`` prints a summary —
also the subprocess half of `summarize_robust`.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from glob import glob
from pathlib import Path

#: Collective op base names, as they appear in HLO/thunk names. Must stay
#: in sync with `tpu_dp.analysis.hlo._COLLECTIVE_KINDS` (pinned by
#: tests/test_commprof.py) — the reconciliation check compares trace
#: events against the DP304 fingerprint schedule, so both sides must
#: classify identically.
COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

#: HLO instruction names that JAX 0.9 derives from its own primitives
#: instead of the HLO opcode (``%reduce_scatter.14 = ... reduce-scatter(``):
#: trace events carry the instruction name, so they map back to the kind.
_PRIMITIVE_KINDS = {
    "psum": "all-reduce", "psum_invariant": "all-reduce",
    "pmax": "all-reduce", "pmin": "all-reduce",
    "all_gather": "all-gather", "all_gather_invariant": "all-gather",
    "reduce_scatter": "reduce-scatter", "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
}

#: Host-plane event names that are executor scaffolding, not ops (the
#: ``end: <op>`` twin of every thunk event and the collective rendezvous
#: waits among them).
_INFRA_MARKERS = ("::", "D2D Dispatch", "ThunkExecutor", "end: ",
                  "Rendezvous", "Wait: ", "Wait for ", "Handle inputs")

_SUFFIX_RE = re.compile(r"\.\d+$")


class XplaneError(ValueError):
    """Typed parse failure: missing/empty trace, unloadable proto, or an
    XSpace with no recognizable op plane (the parser refuses layouts it
    does not understand rather than returning an empty breakdown —
    the `flightrec.read_dump` schema-refusal discipline)."""


def reexec_with_python_protobuf() -> None:
    """Re-exec the current process under the pure-python protobuf runtime.

    The documented hack for CLI entry points whose protobuf C++ backend
    rejects TF's generated xplane module: sets
    ``PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python`` and replaces the
    process with an identical invocation. No-op when the env var is
    already set. NEVER call this from library code running inside a
    training process — use `summarize_robust`, which retries in a
    subprocess instead.
    """
    if os.environ.get("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION") != "python":
        os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"
        os.execv(sys.executable, [sys.executable] + sys.argv)


def import_xplane_pb2():
    """TF's bundled xplane proto module, or a typed `XplaneError`.

    Any import failure maps to XplaneError: the C++-backend rejection is
    a ``TypeError``, a missing tensorflow an ``ImportError`` — callers
    need one exception to branch the subprocess fallback on.
    """
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
        return xplane_pb2
    except Exception as e:
        raise XplaneError(
            f"tensorflow xplane proto unavailable "
            f"({type(e).__name__}: {e}); if this is the protobuf C++ "
            f"backend rejecting the generated module, parse under "
            f"PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python "
            f"(see tpu_dp.obs.xplane.summarize_robust)"
        ) from e


def find_xplane(trace_dir: str | os.PathLike) -> Path | None:
    """Newest ``*.xplane.pb`` under ``trace_dir`` (recursive), or None."""
    paths = glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return Path(sorted(paths)[-1]) if paths else None


def load_xspace(path: str | os.PathLike):
    """Parse one xplane.pb file into an XSpace proto."""
    xplane_pb2 = import_xplane_pb2()
    xs = xplane_pb2.XSpace()
    try:
        xs.ParseFromString(Path(path).read_bytes())
    except Exception as e:
        raise XplaneError(f"cannot parse xplane file {path}: {e}") from e
    return xs


def base_op_name(name: str) -> str:
    """HLO op/thunk event name -> its base kind.

    ``"%all-reduce.1 = ..."`` / ``"all-reduce.1"`` -> ``"all-reduce"``;
    async ``-start`` halves count as the op, ``-done`` halves map to a
    ``"-done"``-suffixed base the caller skips (an async pair is one
    collective, the `analysis.hlo.collect_ops` convention).
    """
    base = name.lstrip("%").split(" = ")[0]
    base = _SUFFIX_RE.sub("", base)
    if base.endswith("-start"):
        base = base[:-6]
    return _PRIMITIVE_KINDS.get(base, base)


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted union of (start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _total(merged: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _subtract_total(a: list[tuple[float, float]],
                    b: list[tuple[float, float]]) -> float:
    """|A \\ B| for two MERGED interval lists (seconds)."""
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


def exposed_seconds(comm: list[tuple[float, float]],
                    compute: list[tuple[float, float]]) -> float:
    """Wall seconds where a collective is running and NO compute op is —
    the exposed-communication time (docs/OBSERVABILITY.md "Comm/compute
    attribution"). Inputs are raw interval lists; merging happens here."""
    return _subtract_total(_merge(comm), _merge(compute))


class _PlaneWalk:
    """Shared accumulator for the two plane layouts."""

    def __init__(self):
        self.window_s = 0.0
        self.ops: dict[str, dict] = {}
        self.by_cat: dict[str, float] = {}
        self.comm_iv: list[tuple[float, float]] = []
        self.compute_iv: list[tuple[float, float]] = []

    def note(self, name: str, start_s: float, dur_s: float,
             category: str = "", flops: int = 0, nbytes: int = 0) -> None:
        base = base_op_name(name)
        if base.endswith("-done"):
            return  # async completion half; counted at -start
        rec = self.ops.get(name)
        if rec is None:
            rec = self.ops[name] = {"name": name.split(" = ")[0],
                                    "base": base, "count": 0, "dur_s": 0.0,
                                    "flops": 0, "bytes": 0,
                                    "category": category}
        rec["count"] += 1
        rec["dur_s"] += dur_s
        rec["flops"] += int(flops)
        rec["bytes"] += int(nbytes)
        if category:
            self.by_cat[category] = self.by_cat.get(category, 0.0) + dur_s
        iv = (start_s, start_s + dur_s)
        if base in COLLECTIVE_KINDS:
            self.comm_iv.append(iv)
        else:
            self.compute_iv.append(iv)

    def summary(self, source: str, plane_name: str) -> dict:
        coll_counts: dict[str, int] = {}
        coll_dur: dict[str, float] = {}
        for rec in self.ops.values():
            if rec["base"] in COLLECTIVE_KINDS:
                coll_counts[rec["base"]] = (
                    coll_counts.get(rec["base"], 0) + rec["count"]
                )
                coll_dur[rec["base"]] = (
                    coll_dur.get(rec["base"], 0.0) + rec["dur_s"]
                )
        comm_merged = _merge(self.comm_iv)
        compute_merged = _merge(self.compute_iv)
        return {
            "schema": 1,
            "source": source,
            "plane": plane_name,
            "window_s": self.window_s,
            "op_busy_s": sum(r["dur_s"] for r in self.ops.values()),
            "by_category": self.by_cat,
            "ops": sorted(self.ops.values(), key=lambda r: -r["dur_s"]),
            "collectives": {"counts": coll_counts, "dur_s": coll_dur},
            "comm_s": _total(comm_merged),
            "compute_s": _total(compute_merged),
            "exposed_comm_s": _subtract_total(comm_merged, compute_merged),
        }


def device_plane_summary(plane) -> dict:
    """Summary of one TPU device plane's ``"XLA Ops"`` line.

    The ``%while`` scan wrapper spans the whole window — it becomes
    ``window_s``, never an op. Empty op lists are the caller's verdict
    to make (`summarize` raises).
    """
    walk = _PlaneWalk()
    md, sm = plane.event_metadata, plane.stat_metadata
    sname = {k: v.name for k, v in sm.items()}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        t0 = line.timestamp_ns / 1e9
        for e in line.events:
            m = md[e.metadata_id]
            dur_s = e.duration_ps / 1e12
            if m.name.startswith("%while"):
                walk.window_s += dur_s
                continue
            st = {sname[s.metadata_id]: s for s in m.stats}
            cat = (st["hlo_category"].str_value
                   if "hlo_category" in st else "?")
            fl = (st["model_flops"].int64_value if "model_flops" in st
                  else st["flops"].int64_value if "flops" in st else 0)
            by = (st["bytes_accessed"].int64_value
                  if "bytes_accessed" in st else 0)
            walk.note(m.name, t0 + e.offset_ps / 1e12, dur_s,
                      category=cat, flops=fl, nbytes=by)
    return walk.summary("device", plane.name)


def host_plane_summary(plane) -> dict:
    """Summary of a host plane's ``tf_XLA*`` thunk lines (CPU backend).

    Every executed thunk is one event named after its HLO op; executor
    scaffolding (ThreadpoolListener, ThunkExecutor, dispatch markers) is
    skipped. ``window_s`` is the span of op events.
    """
    walk = _PlaneWalk()
    md = plane.event_metadata
    span_lo = span_hi = None
    for line in plane.lines:
        if not line.name.startswith("tf_XLA"):
            continue
        t0 = line.timestamp_ns / 1e9
        for e in line.events:
            name = md[e.metadata_id].name
            if any(m in name for m in _INFRA_MARKERS):
                continue
            start = t0 + e.offset_ps / 1e12
            dur_s = e.duration_ps / 1e12
            walk.note(name, start, dur_s)
            span_lo = start if span_lo is None else min(span_lo, start)
            span_hi = (start + dur_s if span_hi is None
                       else max(span_hi, start + dur_s))
    if span_lo is not None:
        walk.window_s = span_hi - span_lo
    return walk.summary("host", plane.name)


def summarize(trace_dir: str | os.PathLike) -> dict:
    """Parse the newest trace under ``trace_dir`` into one summary dict.

    ::

        {"schema": 1, "source": "device"|"host", "plane": ...,
         "window_s", "op_busy_s", "by_category": {cat: dur_s},
         "ops": [{"name", "base", "count", "dur_s", "flops", "bytes"}],
         "collectives": {"counts": {kind: raw events},
                          "dur_s": {kind: seconds}},
         "comm_s", "compute_s", "exposed_comm_s"}

    Device planes are preferred (TPU); with none present the host thunk
    plane is the fallback (CPU). ``comm_s``/``compute_s`` are
    merged-interval union lengths (an op running on two thread lines at
    once counts its wall span once); ``exposed_comm_s`` is the
    comm-interval time not covered by any compute interval. Raises
    `XplaneError` when no trace exists, the XSpace carries no
    recognizable op plane, or no op events landed.
    """
    path = find_xplane(trace_dir)
    if path is None:
        raise XplaneError(f"no xplane.pb under {trace_dir}")
    xs = load_xspace(path)
    devs = [p for p in xs.planes if p.name.startswith("/device:")
            and any(line.events for line in p.lines)]
    if devs:
        out = device_plane_summary(devs[0])
    else:
        hosts = [p for p in xs.planes if p.name.startswith("/host:")
                 and any(line.name.startswith("tf_XLA") and line.events
                         for line in p.lines)]
        if not hosts:
            raise XplaneError(
                f"{path}: no device plane with an 'XLA Ops' line and no "
                f"host tf_XLA* thunk lines — unrecognized xplane layout "
                f"(planes: {[p.name for p in xs.planes]})"
            )
        out = host_plane_summary(hosts[0])
    if not out["ops"]:
        raise XplaneError(f"{path}: no op events in the trace — was a "
                          f"step actually executed inside the profiled "
                          f"region?")
    out["path"] = str(path)
    return out


def summarize_robust(trace_dir: str | os.PathLike,
                     timeout_s: float = 120.0) -> dict:
    """`summarize`, retried in a subprocess under the pure-python
    protobuf runtime when the in-process import is rejected.

    The in-run consumer's entry point: a Trainer parsing its own capture
    window must never re-exec itself, so the env-var half of the
    historical hack runs in a child (``python -m tpu_dp.obs.xplane``)
    whose JSON output is this function's return value. Parse errors
    (no trace, unrecognized layout) propagate as `XplaneError` from
    either path.
    """
    try:
        import_xplane_pb2()
    except XplaneError:
        env = dict(os.environ,
                   PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION="python")
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_dp.obs.xplane", str(trace_dir),
             "--json"],
            capture_output=True, text=True, env=env, timeout=timeout_s,
        )
        if proc.returncode != 0:
            tail = (proc.stderr.strip().splitlines() or ["no stderr"])[-1]
            raise XplaneError(
                f"subprocess xplane parse of {trace_dir} failed "
                f"(rc={proc.returncode}): {tail[:300]}"
            )
        return json.loads(proc.stdout)
    return summarize(trace_dir)


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tpu_dp.obs.xplane",
        description="Parse a jax.profiler trace dir into an op summary "
                    "(device 'XLA Ops' plane, or host thunk lines on the "
                    "CPU backend).",
    )
    ap.add_argument("trace_dir")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    try:
        s = summarize(args.trace_dir)
    except XplaneError as e:
        print(f"xplane: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(s))
        return 0
    print(f"{s['source']} plane {s['plane']}: window {s['window_s']*1e3:.1f} "
          f"ms, op-busy {s['op_busy_s']*1e3:.1f} ms")
    print(f"comm {s['comm_s']*1e3:.2f} ms ({s['collectives']['counts']}), "
          f"compute {s['compute_s']*1e3:.2f} ms, "
          f"exposed comm {s['exposed_comm_s']*1e3:.2f} ms")
    print(f"\n-- top {args.top} ops by time --")
    for rec in s["ops"][:args.top]:
        print(f"{rec['dur_s']*1e3:9.2f} ms {rec['count']:6d}x  {rec['name']}")
    return 0


if __name__ == "__main__":
    sys.exit(_main())
