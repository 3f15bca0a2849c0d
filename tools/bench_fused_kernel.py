"""Microbenchmark: fused Pallas conv kernel vs XLA's unfused chain.

Measures the op this kernel replaces — BN-apply + ReLU (+residual) + 3x3
stride-1 conv (`tpu_dp/ops/conv_block.py`) — at each ResNet stage's shape,
against the XLA statement of the same math, on whatever backend is up
(intended: the real TPU chip; falls back to interpret-mode on CPU, which
is a correctness run, not a perf number).

Prints one JSON line per point:
  {"shape": [B,H,W,C], "block_b": n, "impl": "pallas"|"xla",
   "ms": t, "tflops": f, "pct_peak": p}

Usage:
  python tools/bench_fused_kernel.py                 # stage shapes, b2048
  python tools/bench_fused_kernel.py --batch 1024 --stages 0 --block-b 4,8
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tpu_dp.ops.conv_block import (
    fused_affine_relu_conv,
    reference_affine_relu_conv,
)

BF16_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # per chip
}

# CIFAR ResNet-18 stage shapes (H=W spatial, C channels at stride-1 blocks).
STAGE_SHAPES = {0: (32, 64), 1: (16, 128), 2: (8, 256), 3: (4, 512)}


def _fence(y):
    # Every leaf: keeps XLA from dead-code-eliminating any grad output.
    jax.block_until_ready(y)


def timeit(f, *args, iters=20):
    y = f(*args)
    _fence(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(*args)
    _fence(y)
    return (time.perf_counter() - t0) / iters


def check_shard_map(batch: int) -> int:
    """On-chip pin of the real (non-interpret) kernel under `jax.shard_map`.

    Off-TPU the per-shard code takes the XLA fallback
    (`tpu_dp/ops/_partition.py:shard_map_interp`), so the CPU suite can
    never reach the kernel *body* under shard_map — this check runs it on
    a real TPU mesh and compares against the GSPMD path and the XLA
    oracle (expected bit-identical: same f32 affine, same bf16 rounding,
    same f32 conv accumulation). Returns a process exit code.
    """
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_dp.ops.conv_block import fused_conv_bn

    if jax.default_backend() != "tpu":
        print(json.dumps({"check": "shard_map_fused", "skipped": True,
                          "reason": f"backend is {jax.default_backend()}, "
                                    "not tpu (fallback path would run)"}))
        return 0

    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("data",))
    hw, c = STAGE_SHAPES[0]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (batch, hw, hw, c), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (3, 3, c, c)) * 0.1).astype(jnp.float32)
    scale = jax.random.normal(ks[2], (c,)) * 0.5 + 1.0
    shift = jax.random.normal(ks[3], (c,)) * 0.1
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    specs = (P("data"), P(None, None, None, None), P(None), P(None))

    failures = 0

    def compare(name, a, b, atol=0.0):
        # Kernel-vs-kernel paths (shard_map vs GSPMD run the same Pallas
        # program) must match bitwise; the kernel-vs-XLA-oracle pair is
        # allowed bf16-ulp accumulation-order noise, same as
        # tests/test_conv_block.py's atol.
        nonlocal failures
        diff = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
        ok = diff <= atol
        failures += not ok
        print(json.dumps({"check": f"shard_map_fused/{name}",
                          "max_abs_diff": diff, "atol": atol, "ok": ok,
                          "n_devices": int(devs.size),
                          "device": jax.devices()[0].device_kind}),
              flush=True)

    # Forward: shard_map kernel vs GSPMD kernel vs XLA oracle.
    gspmd = jax.jit(lambda x, w, s, b: fused_affine_relu_conv(x, w, s, b,
                                                              None))
    smap = jax.jit(jax.shard_map(
        lambda x, w, s, b: fused_affine_relu_conv(x, w, s, b, None),
        mesh=mesh, in_specs=specs, out_specs=P("data")))
    ref = jax.jit(lambda x, w, s, b: reference_affine_relu_conv(x, w, s, b))
    y_s = smap(xs, w, scale, shift)
    compare("fwd_vs_gspmd", y_s, gspmd(xs, w, scale, shift))
    compare("fwd_vs_xla", y_s, ref(xs, w, scale, shift), atol=5e-2)

    # Emit + stats variants (stats: per-shard partials psum'd to the
    # global sums the GSPMD partition rule produces).
    def smap_emit_stats(x, w, s, b):
        y, z, st = fused_conv_bn(x, w, s, b, None, emit_z=True)
        return y, z, jax.lax.psum(st, "data")

    smap_es = jax.jit(jax.shard_map(
        smap_emit_stats, mesh=mesh, in_specs=specs,
        out_specs=(P("data"), P("data"), P(None, None))))
    gspmd_es = jax.jit(lambda x, w, s, b: fused_conv_bn(x, w, s, b, None,
                                                        emit_z=True))
    ys, zs, sts = smap_es(xs, w, scale, shift)
    yg, zg, stg = gspmd_es(xs, w, scale, shift)
    compare("emit_y", ys, yg)
    compare("emit_z", zs, zg)
    compare("stats", sts, stg)

    # Backward (input grad), XLA conv-transpose and Pallas bwd variants:
    # d/dx_shard of the global sum == per-shard grad, no collective needed.
    for pallas_bwd in (False, True):
        def local_grad(x, w, s, b, pb=pallas_bwd):
            return jax.grad(lambda xi: jnp.sum(
                fused_affine_relu_conv(xi, w, s, b, None,
                                       pallas_bwd=pb).astype(jnp.float32)))(x)

        smap_g = jax.jit(jax.shard_map(local_grad, mesh=mesh,
                                       in_specs=specs, out_specs=P("data")))
        gspmd_g = jax.jit(local_grad)
        tag = "dx_pallas_bwd" if pallas_bwd else "dx"
        compare(tag, smap_g(xs, w, scale, shift),
                gspmd_g(xs, w, scale, shift))

    print(json.dumps({"check": "shard_map_fused", "failures": failures,
                      "ok": failures == 0}), flush=True)
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--stages", default="0,1,2,3")
    ap.add_argument("--block-b", default="4,8,16")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--with-residual", action="store_true")
    ap.add_argument("--grad", action="store_true",
                    help="time the full fwd+bwd (input+weight+affine "
                         "grads) instead of forward only — compares the "
                         "XLA backward against pallas_bwd variants")
    ap.add_argument("--check-shard-map", action="store_true",
                    help="instead of benchmarking, pin the real kernel "
                         "under jax.shard_map against the GSPMD path on a "
                         "TPU mesh (VERDICT r3 weak #3); exits 0 on match")
    ap.add_argument("--platform", default=None, choices=["cpu"],
                    help="force cpu (a correctness run: the kernels run "
                         "in the Pallas interpreter)")
    args = ap.parse_args()

    kernels = contextlib.nullcontext()
    if args.platform == "cpu":
        from tpu_dp.ops import interpret_kernels

        jax.config.update("jax_platforms", "cpu")
        kernels = interpret_kernels()
    with kernels:
        _run(args)


def _run(args):
    if args.check_shard_map:
        sys.exit(check_shard_map(min(args.batch, 256)))
    dev = jax.devices()[0]
    peak = BF16_PEAK_FLOPS.get(dev.device_kind)
    stages = [int(s) for s in args.stages.split(",")]
    blocks = [int(b) for b in args.block_b.split(",")]

    for stage in stages:
        hw, c = STAGE_SHAPES[stage]
        shape = (args.batch, hw, hw, c)
        ks = jax.random.split(jax.random.PRNGKey(stage), 5)
        x = jax.random.normal(ks[0], shape, jnp.bfloat16)
        w = (jax.random.normal(ks[1], (3, 3, c, c)) * 0.1).astype(jnp.float32)
        scale = jax.random.normal(ks[2], (c,)) * 0.5 + 1.0
        shift = jax.random.normal(ks[3], (c,)) * 0.1
        res = (jax.random.normal(ks[4], shape, jnp.bfloat16)
               if args.with_residual else None)
        # fwd: one 3x3 conv; fwd+bwd adds the input-grad conv and the
        # weight-grad contraction (same contraction size each) ~= 3x.
        flops = 2 * args.batch * hw * hw * c * c * 9 * (3 if args.grad else 1)

        def emit(impl, block_b, dt):
            rec = {"shape": list(shape), "block_b": block_b, "impl": impl,
                   "ms": round(dt * 1e3, 3),
                   "tflops": round(flops / dt / 1e12, 1),
                   "pct_peak": (round(100 * flops / dt / peak, 1)
                                if peak else None),
                   "residual": args.with_residual, "grad": args.grad,
                   "device": dev.device_kind}
            print(json.dumps(rec), flush=True)

        def grad_of(op):
            # Full training-shaped backward: grads wrt every differentiable
            # operand (returning them all keeps XLA from DCE'ing any path).
            argnums = (0, 1, 2, 3) if res is None else (0, 1, 2, 3, 4)

            def f(x, w, scale, shift, res):
                def loss(*a):
                    y = op(*a)
                    return jnp.sum(y.astype(jnp.float32))
                return jax.grad(loss, argnums)(x, w, scale, shift, res)
            return f

        def run(impl, block_b, op):
            try:
                f = jax.jit(grad_of(op)) if args.grad else jax.jit(
                    lambda x, w, s, sh, r: op(x, w, s, sh, r))
                dt = timeit(f, x, w, scale, shift, res, iters=args.iters)
                emit(impl, block_b, dt)
            except Exception as e:
                print(json.dumps({"shape": list(shape), "block_b": block_b,
                                  "impl": impl, "grad": args.grad,
                                  "error": f"{type(e).__name__}: {e}"[:200]}),
                      flush=True)

        run("xla", 0, reference_affine_relu_conv)
        for bb in blocks:
            run("pallas", bb, functools.partial(
                fused_affine_relu_conv, block_b=bb))
            if args.grad:
                run("pallas+bwd", bb, functools.partial(
                    fused_affine_relu_conv, block_b=bb, pallas_bwd=True))


if __name__ == "__main__":
    main()
