#!/usr/bin/env python
"""Trace one scanned bench window on the device and print an op breakdown.

Produces the numbers behind docs/DESIGN.md "Where the other half of peak
goes": captures a `jax.profiler` trace of a `make_multi_step` window
(identical config to bench.py's headline point), parses the xplane proto
through `tpu_dp.obs.xplane` (the reusable library this tool is now a thin
CLI over — the in-run comm attribution layer `tpu_dp.obs.commprof` reads
traces through the same code path), and aggregates device time by HLO
category plus a per-op efficiency table (achieved TFLOP/s and GB/s vs the
chip's peaks, from the unified `tpu_dp.obs.chips` registry).

    python tools/profile_breakdown.py                  # b2048, w30 (headline)
    python tools/profile_breakdown.py --per-chip-batch 1024 --window 30
    python tools/profile_breakdown.py --model resnet50 --per-chip-batch 1024
    python tools/profile_breakdown.py --fused-stages all   # fused Pallas path

Parsing notes: the device lanes are read from the xplane.pb. The protobuf
runtime may reject TF's generated xplane module under the C++ backend, so
this tool re-execs itself with PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python
when needed (the documented helper
`tpu_dp.obs.xplane.reexec_with_python_protobuf`). Tracing inflates wall
time; the *within-trace* device timestamps are what's reported.
CPU-backend traces have no device plane — inspect those with
`python -m tpu_dp.obs.xplane`.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

# One source of truth for model -> num_classes: bench.py's MODEL_SPECS
# (BASELINE.json config 3 runs ResNet-50 on CIFAR-100).
from bench import MODEL_SPECS  # noqa: E402  (repo root on sys.path above)
from tpu_dp.obs import chips  # noqa: E402  (unified chip-peak registry)

MODEL_CLASSES = {name: spec[1] for name, spec in MODEL_SPECS.items()}

#: The tool's target chip; docs/DESIGN.md numbers cite the same registry
#: MFU divides by.
_V5E = chips.chip_spec("v5e")


def capture(trace_dir: str, per_chip: int, window: int, model_name: str,
            fused_stages: str, fused_block_b: int, fused_bwd: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dp.data.cifar import make_synthetic
    from tpu_dp.models import build_model, parse_fused_stages
    from tpu_dp.parallel import dist
    from tpu_dp.parallel.sharding import scan_batch_sharding, shard_batch
    from tpu_dp.train import SGD, cosine_lr, create_train_state, make_multi_step

    mesh = dist.data_mesh()
    gb = per_chip * int(mesh.devices.size)
    nc = MODEL_CLASSES[model_name]
    model = build_model(model_name, num_classes=nc, dtype=jnp.bfloat16,
                        fused_stages=parse_fused_stages(fused_stages),
                        fused_block_b=fused_block_b, fused_bwd=fused_bwd)
    opt = SGD(momentum=0.9, weight_decay=5e-4)
    state = create_train_state(model, jax.random.PRNGKey(0),
                               np.zeros((1, 32, 32, 3), np.float32), opt)
    pool_host = [make_synthetic(gb, nc, seed=i, name="bench") for i in range(4)]
    stacked = {"image": np.stack([d.images for d in pool_host]),
               "label": np.stack([d.labels for d in pool_host])}
    pool = shard_batch(stacked, mesh, spec=scan_batch_sharding(mesh))
    loop = make_multi_step(model, opt, mesh, cosine_lr(0.4, 2 * window, 2),
                           num_steps=window)
    state, m = loop(state, pool)  # compile + warmup
    float(m["loss"][-1])
    with jax.profiler.trace(trace_dir):
        state, m = loop(state, pool)
        float(m["loss"][-1])  # fence inside the trace


def report(trace_dir: str, top: int) -> None:
    """Parse + print the device-plane breakdown (output format unchanged
    from the pre-library versions; tests/test_profile_breakdown.py pins
    it). The heavy lifting — file discovery, proto parse, the %while
    wrapper/window split, per-op aggregation — is `tpu_dp.obs.xplane`'s."""
    from tpu_dp.obs import xplane

    path = xplane.find_xplane(trace_dir)
    if path is None:
        sys.exit(f"no xplane.pb under {trace_dir}")
    xs = xplane.load_xspace(path)
    devs = [p for p in xs.planes if p.name.startswith("/device:")
            and any(line.events for line in p.lines)]
    if not devs:
        sys.exit("no device plane with events (tracing unsupported here?)")
    dev = devs[0]
    if not any(line.name == "XLA Ops" for line in dev.lines):
        sys.exit(f"device plane {dev.name} has no 'XLA Ops' line "
                 f"(lines: {[line.name for line in dev.lines]})")
    s = xplane.device_plane_summary(dev)

    by_cat = s["by_category"]
    window_s = s["window_s"]
    total = sum(by_cat.values())
    if total <= 0:
        sys.exit("no non-wrapper op events in the trace — was a step "
                 "actually executed inside the profiled region?")
    print(f"\ndevice {dev.name}: window {window_s*1e3:.1f} ms, "
          f"op-busy {total*1e3:.1f} ms, idle {max(0, window_s-total)*1e3:.1f} ms")
    print("\n-- by HLO category --")
    for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"{v*1e3:9.1f} ms {100*v/total:6.1f}%  {k}")

    tot_f = sum(r["flops"] for r in s["ops"])
    print(f"\nmodel FLOPs in window: {tot_f/1e12:.2f} T "
          f"(avg {tot_f/total/1e12:.1f} TF/s, "
          f"{100*tot_f/total/_V5E.peak_flops:.0f}% of v5e bf16 peak)")
    print(f"\n-- top {top} ops by device time --")
    print(f"{'ms':>8} {'TF/s':>6} {'%peak':>6} {'GB/s':>7} {'n':>4}  op")
    for r in s["ops"][:top]:
        d, f, b, n = r["dur_s"], r["flops"], r["bytes"], r["count"]
        print(f"{d*1e3:8.1f} {f/d/1e12:6.1f} "
              f"{100*f/d/_V5E.peak_flops:6.1f} {b/d/1e9:7.0f} "
              f"{n:4d}  {r['name']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet18", choices=sorted(MODEL_CLASSES))
    ap.add_argument("--platform", default=None, choices=["cpu"],
                    help="force cpu (harness smoke test: Pallas kernels "
                         "run in the interpreter)")
    ap.add_argument("--fused-stages", default="",
                    help="ResNet stages on the fused Pallas conv path "
                         "('', '0', 'all'; tpu_dp/ops/conv_block.py)")
    ap.add_argument("--fused-block-b", type=int, default=0)
    ap.add_argument("--fused-bwd", action="store_true")
    ap.add_argument("--per-chip-batch", type=int, default=2048)
    ap.add_argument("--window", type=int, default=30)
    ap.add_argument("--trace-dir", default=None,
                    help="reuse/keep a trace dir (default: temp, capture+report)")
    ap.add_argument("--report-only", action="store_true",
                    help="parse an existing --trace-dir without touching the device")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    # The TF-bundled xplane_pb2 may need the pure-python protobuf runtime;
    # the re-exec hack lives in the library now (one documented helper).
    from tpu_dp.obs.xplane import reexec_with_python_protobuf

    reexec_with_python_protobuf()

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="tpu_dp_trace_")
    if not args.report_only:
        kernels = contextlib.nullcontext()
        if args.platform == "cpu":
            import jax

            from tpu_dp.ops import interpret_kernels

            jax.config.update("jax_platforms", "cpu")
            kernels = interpret_kernels()
        with kernels:
            capture(trace_dir, args.per_chip_batch, args.window, args.model,
                    args.fused_stages, args.fused_block_b, args.fused_bwd)
    report(trace_dir, args.top)
    print(f"\ntrace kept at {trace_dir}")


if __name__ == "__main__":
    main()
