#!/usr/bin/env python
"""The quickest proof that tpu_dp still starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host: data parallelism

One process; it imports JAX once and starts no child. It exits non-zero —
with no result line — unless JAX finds a TPU whose kind `tpu_dp.obs.chips`
knows, and any phase that raises ends the run: nothing is caught.

Default run (one chip):

1. the trainer through `train.main`: ResNet-18 at full width, bf16
   compute, batch 2048, synthetic data, two epochs of eight steps, eval, a
   checkpoint, then ``--resume=auto`` for one more epoch;
2. each Pallas kernel, compiled, against its `jax.numpy` statement at the
   shapes the trainer gives it;
3. the trainer once more with ``--train.pallas_xent=true``.

``--chips 4`` runs only the data-parallel comparison, each run through
`train.main` from the same seed and batches: on one device, on four with
the replicated (GSPMD) update, and on four with the sharded update — ten
steps, a checkpoint, and an eleventh step after ``--resume=auto``.
Per-step and eval losses must agree with one device; the restored state
and the batch must really be spread over the four devices; and the
compiled steps must reduce the gradients (and, sharded, gather the
parameters) across them, whatever ops the compiler chose for it.

The times printed are smoke readings of one short run, not a benchmark.
The last line of stdout is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import shutil
import sys
from pathlib import Path

WORK = Path(__file__).resolve().parent / ".chip_smoke"

#: Per-step |loss - loss on one device| allowed in the ``--chips 4``
#: comparison. The three runs differ only in the order f32 partial sums
#: are added (per-device then across devices); ten steps of ResNet-18
#: amplify that to ~1e-5 on a CPU mesh (`tests/test_shard_update.py`,
#: atol 2e-5) and somewhat more through the MXU's bf16 passes. A gradient
#: scaled or reduced wrongly moves the loss by >1e-1 within three steps.
DP_LOSS_ATOL = 2e-3
#: The eval loss is allowed this many times more: it reads the final
#: parameters, which carry every step's difference, through BatchNorm's
#: running statistics on images the model was not trained on. State restored
#: wrongly or onto the wrong device moves it by >1e-1 as well.
EVAL_SLACK = 5


class _Tee(io.TextIOBase):
    """stdout that also keeps what passes through (train.main's summary)."""

    def __init__(self, stream):
        self.stream, self.kept = stream, io.StringIO()

    def write(self, s):
        self.kept.write(s)
        return self.stream.write(s)

    def flush(self):
        self.stream.flush()


class _CompileClock:
    """Sums JAX's own compile events: backend seconds, cache hits/misses."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.hits, self.requests = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def lap(self):
        out = (self.seconds, self.hits, self.requests)
        self.seconds, self.hits, self.requests = 0.0, 0, 0
        return out


def run_trainer(argv, ckpt_dir):
    """`train.main(argv)`; returns its JSON summary and epoch records."""
    import train

    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = train.main([*argv, f"--train.ckpt_dir={ckpt_dir}"])
    assert rc == 0, f"train.main returned {rc}"
    summary = json.loads(tee.kept.getvalue().strip().splitlines()[-1])
    records = [json.loads(line) for line in
               (Path(ckpt_dir) / "metrics.jsonl").read_text().splitlines()]
    epochs = [r for r in records if "epoch" in r and "loss" in r]
    return summary, epochs


def _check_run(summary, epochs, batch):
    assert summary["devices"] == 1, summary
    assert summary["synthetic"] is True, summary
    losses = [e["loss"] for e in epochs]
    assert losses and all(math.isfinite(x) for x in losses), losses
    ev = summary["eval"]
    assert math.isfinite(ev["loss"]) and 0.0 <= ev["accuracy"] <= 1.0, ev
    return batch / summary["images_per_sec"] * 1e3  # steady ms/step


def _trainer_argv(model, batch, steps):
    return [
        f"--model.name={model}", "--model.bf16=true",
        "--data.dataset=synthetic", f"--data.batch_size={batch}",
        f"--data.synthetic_train_size={batch * steps}",
        f"--data.synthetic_test_size={batch}",
        "--optim.lr=0.05", f"--train.log_every={steps // 2}",
    ]


def phase_trainer(clock, *, model="resnet18", batch=2048, steps=8,
                  extra=()):
    """Train two epochs, eval, checkpoint; resume for a third."""
    ckpt = WORK / "train"
    argv = [*_trainer_argv(model, batch, steps), *extra]
    clock.lap()
    summary, epochs = run_trainer([*argv, "--train.epochs=2"], ckpt)
    first_s, first_hits, first_requests = clock.lap()
    # The cache directory may outlive the machine (the variable can point
    # at a persistent one): the first run is cold only if nothing hit.
    first = ("cold" if first_hits == 0 else
             "warm" if first_hits == first_requests else "partly warm")
    step_ms = _check_run(summary, epochs, batch)
    assert [e["epoch"] for e in epochs] == [1, 2], epochs
    assert epochs[-1]["loss"] < epochs[0]["loss"], (
        f"loss did not fall: {[e['loss'] for e in epochs]}")
    saved = sorted(p.name for p in ckpt.glob("step_*"))
    assert saved, f"no checkpoint under {ckpt}"
    print(f"smoke reading, first run: compile {first_s:.1f} s ({first}: "
          f"{first_hits}/{first_requests} programs from the compile cache), "
          f"steady {step_ms:.2f} ms/step, "
          f"{summary['images_per_sec']:.0f} images/s, "
          f"epoch losses {[round(e['loss'], 4) for e in epochs]}, "
          f"eval acc {summary['eval']['accuracy']:.3f}, "
          f"checkpoints {saved}")

    summary, epochs = run_trainer(
        [*argv, "--train.epochs=3", "--resume=auto"], ckpt)
    warm_s, hits, requests = clock.lap()
    _check_run(summary, epochs, batch)
    assert [e["epoch"] for e in epochs] == [1, 2, 3], (
        f"resumed run did not start at epoch 3: {epochs}")
    assert hits > 0, "resumed run compiled everything again: cache unused"
    print(f"smoke reading, resumed run: epoch 3 loss "
          f"{epochs[-1]['loss']:.4f}; compile {warm_s:.1f} s with "
          f"{hits}/{requests} programs served from the compile cache "
          f"(first run, {first}: {first_s:.1f} s | resumed run, warm: "
          f"{warm_s:.1f} s)")


def _assert_kernel(fn, args, name):
    """Compile ``fn``; its program must hold a Mosaic kernel. Returns
    the results."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no tpu_custom_call in the compiled program")
    return compiled(*args)


def _close(got, want, atol, name):
    """max|got - want| <= atol * max|want|, per array."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite output"
    scale = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got - want).max()) / scale
    assert err <= atol, f"{name}: {err:.3e} of max|ref| > {atol:.0e}"
    return err


def phase_kernels():
    """Each kernel, compiled on the chip, against its jnp statement."""
    import jax
    import jax.numpy as jnp

    from tpu_dp.ops import conv_block, xent

    def xent_ref(logits, labels):
        logits = logits.astype(jnp.float32)
        true = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - true

    for batch, classes in ((2048, 10), (1024, 100)):
        k1, k2 = jax.random.split(jax.random.PRNGKey(classes))
        logits = (jax.random.normal(k1, (batch, classes)) * 3).astype(
            jnp.bfloat16)
        labels = jax.random.randint(k2, (batch,), 0, classes, jnp.int32)
        name = f"xent ({batch},{classes}) bf16"
        fwd = _assert_kernel(xent.softmax_xent, (logits, labels), name)
        bwd = _assert_kernel(
            jax.grad(lambda lg, lb: jnp.sum(xent.softmax_xent(lg, lb))),
            (logits, labels), name + " bwd")
        ref_bwd = jax.grad(lambda lg: jnp.sum(xent_ref(lg, labels)))(logits)
        # Forward is f32 on both sides (1e-5: exp/log differ in the last
        # bits); the gradient is rounded to bf16 (one ulp of 1.0 = 8e-3).
        e_f = _close(fwd, xent_ref(logits, labels), 1e-5, name)
        e_b = _close(bwd, ref_bwd, 1e-2, name + " bwd")
        print(f"kernel {name}: fwd err {e_f:.1e}, bwd err {e_b:.1e} "
              f"(of max|ref|)")

    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    shape = (256, 32, 32, 64)
    x = jax.random.normal(ks[0], shape, jnp.bfloat16)
    res = jax.random.normal(ks[1], shape, jnp.bfloat16)
    w = (jax.random.normal(ks[2], (3, 3, 64, 64)) / 24.0).astype(
        jnp.bfloat16)  # 1/sqrt(9*64): outputs of order one
    scale = jax.random.normal(ks[3], (64,)) * 0.5 + 1.0
    shift = jax.random.normal(ks[4], (64,)) * 0.1

    name = f"conv_block {shape} plain"
    y = _assert_kernel(
        lambda *a: conv_block.fused_affine_relu_conv(*a, None),
        (x, w, scale, shift), name)
    # One bf16 ulp at the top of the output range, as tests/test_conv_block.
    e = _close(y, conv_block.reference_affine_relu_conv(x, w, scale, shift),
               1e-2, name)
    print(f"kernel {name}: err {e:.1e} (of max|ref|)")

    name = f"conv_block {shape} residual+z+stats"
    y, z, stats = _assert_kernel(
        lambda *a: conv_block.fused_conv_bn(*a, emit_z=True),
        (x, w, scale, shift, res), name)
    y_ref = conv_block.reference_affine_relu_conv(x, w, scale, shift, res)
    z_ref = jnp.maximum(
        x.astype(jnp.float32) * scale + shift + res.astype(jnp.float32),
        0.0).astype(jnp.bfloat16)
    yf = y_ref.astype(jnp.float32)
    stats_ref = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                           jnp.sum(yf * yf, axis=(0, 1, 2))])
    e_y = _close(y, y_ref, 1e-2, name + " y")
    e_z = _close(z, z_ref, 1e-2, name + " z")
    # Sums of 262,144 values that may each differ by a bf16 ulp: the
    # sum of squares (the larger row, which sets the scale) to 2e-3.
    e_s = _close(stats, stats_ref, 2e-3, name + " stats")
    print(f"kernel {name}: y err {e_y:.1e}, z err {e_z:.1e}, "
          f"stats err {e_s:.1e} (of max|ref|)")

    from tpu_dp.models.sdar import rms_norm, rope, rope_tables
    from tpu_dp.ops.qk_norm_rope import qk_norm_rope

    # q's heads and k's, at the token cell's row (2 x 4,096 positions,
    # heads of 128), against the plain form in float32 turned heads major.
    tables = rope_tables(jnp.concatenate([jnp.arange(4096)] * 2), 1e6, 128)
    for layout, shape, s in (("q", (1, 8192, 32, 128), 128 ** -0.5),
                             ("k", (4, 8192, 4, 128), 1.0)):
        kx, kw, kg = jax.random.split(jax.random.PRNGKey(shape[2]), 3)
        x = (jax.random.normal(kx, shape) * 3).astype(jnp.bfloat16)
        w = 1.0 + 0.3 * jax.random.normal(kw, (128,))
        dy = jax.random.normal(
            kg, (shape[0], shape[2], shape[1], 128)).astype(jnp.bfloat16)

        def plain(x, w, cos, sin):
            y = rope(rms_norm(x, w, 1e-6), cos[:, None], sin[:, None])
            return s * y.transpose(0, 2, 1, 3)

        def fused(x, w, cos, sin):
            return qk_norm_rope(x, w, cos, sin, 1e-6, s)

        def grads(f):
            # every array an argument: one closed over would be compiled
            # into the program as a constant of its size
            def run(x, w, cos, sin, dy):
                def loss(x, w):
                    return jnp.sum(f(x, w, cos, sin).astype(jnp.float32) * dy)
                return jax.grad(loss, (0, 1))(x, w)
            return run

        name = f"qk_norm_rope {layout} {shape} bf16"
        y = _assert_kernel(fused, (x, w, *tables), name)
        dx, dw = _assert_kernel(grads(fused), (x, w, *tables, dy),
                                name + " bwd")
        xf = x.astype(jnp.float32)
        y_ref = jax.jit(plain)(xf, w, *tables)
        dx_ref, dw_ref = jax.jit(grads(plain))(xf, w, *tables, dy)
        # One rounding to bf16 of the result and of dx; dw is a float32
        # sum on both sides.
        e_y = _close(y, y_ref, 1e-2, name)
        e_x = _close(dx, dx_ref, 1e-2, name + " dx")
        e_w = _close(dw, dw_ref, 1e-4, name + " dw")
        print(f"kernel {name}: y err {e_y:.1e}, dx err {e_x:.1e}, "
              f"dw err {e_w:.1e} (of max|ref|)")

    from tpu_dp.models.sdar import block_diffusion_attention
    from tpu_dp.ops.flash_block_diffusion import (
        BlockDiffusionMask,
        flash_attention,
    )

    # The flash pair at the token cell's shapes (4 rows, 4 key/value heads
    # of 8 query heads, 2 x 4,096 positions in blocks of 4) against the
    # compiler's own form in float32 on the same operands.
    shape = (4, 4, 8, 8192, 128)
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(33), 4)
    q = (jax.random.normal(kq, shape) * 128 ** -0.5).astype(jnp.bfloat16)
    k = jax.random.normal(kk, shape[:2] + shape[3:]).astype(jnp.bfloat16)
    v = jax.random.normal(kv, k.shape).astype(jnp.bfloat16)
    do = jax.random.normal(kg, shape).astype(jnp.bfloat16)

    def pair(q, k, v):
        return flash_attention(q, k, v, BlockDiffusionMask(4096, 4))

    def plain(q, k, v):
        # positions major, the scale its own: q arrives with it
        rows, kvh, g, n2, d = q.shape
        out = block_diffusion_attention(
            q.transpose(0, 3, 1, 2, 4).reshape(rows, n2, kvh * g, d)
            * d ** 0.5, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            4, chunk=512)
        return out.reshape(rows, n2, kvh, g, d).transpose(0, 2, 3, 1, 4)

    def with_grads(f):
        def run(q, k, v, do):
            out, vjp = jax.vjp(f, q, k, v)
            return (out, *vjp(do.astype(out.dtype)))
        return run

    name = f"flash_block_diffusion q {shape} bf16"
    got = _assert_kernel(with_grads(pair), (q, k, v, do), name)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(with_grads(plain))(
            *(x.astype(jnp.float32) for x in (q, k, v, do)))
    # One rounding to bf16 of the output; the gradients are sums of
    # products of bf16-rounded p, ds and do.
    errs = [_close(a, b, atol, f"{name} {what}") for a, b, atol, what in zip(
        got, want, (1e-2, 2e-2, 2e-2, 2e-2), ("o", "dq", "dk", "dv"))]
    print(f"kernel {name}: o err {errs[0]:.1e}, dq err {errs[1]:.1e}, "
          f"dk err {errs[2]:.1e}, dv err {errs[3]:.1e} (of max|ref|)")

    from tpu_dp.models.nemotron_h import ssd_chunked
    from tpu_dp.ops.ssd_scan import CHUNK, ssd_scan

    # The scan's pair at the hybrid cell's shapes (a row of 8,192 positions,
    # 64 heads of 64 in 8 groups, state 128) against the chunked form the
    # compiler gets elsewhere, in float32 on the same operands.
    length, heads, p, groups, n = 8192, 64, 64, 8, 128
    keys = jax.random.split(jax.random.PRNGKey(35), 7)
    x = jax.random.normal(keys[0], (1, length, heads * p)).astype(jnp.bfloat16)
    b = jax.random.normal(keys[1], (1, length, groups * n)).astype(jnp.bfloat16)
    c = jax.random.normal(keys[2], (1, length, groups * n)).astype(jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(keys[3], (1, length, heads)) - 4)
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32)
    d = jax.random.normal(keys[4], (heads,))
    dy = jax.random.normal(keys[5], x.shape)

    def scan_pair(x, delta, a, b, c, d):
        # a group at a time, [1, groups, L, a group's channels]
        y = ssd_scan(x, delta, a, b, c, d, groups)
        return y.swapaxes(1, 2).reshape(x.shape)

    def scan_plain(x, delta, a, b, c, d):
        x4 = x.reshape(1, length, heads, p)
        y = ssd_chunked(x4, delta, a, b.reshape(1, length, groups, n),
                        c.reshape(1, length, groups, n), CHUNK)
        return (y + d[:, None] * x4).reshape(x.shape)

    def scan_grads(f):
        def run(*operands):
            out, vjp = jax.vjp(f, *operands)
            return (out, *vjp(dy))
        return run

    name = f"ssd_scan x {x.shape} bf16"
    got = _assert_kernel(scan_grads(scan_pair), (x, delta, a, b, c, d), name)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(scan_grads(scan_plain))(
            x.astype(jnp.float32), delta, a, b.astype(jnp.float32),
            c.astype(jnp.float32), d)
    # Sums of products of bf16-rounded weights, states and cotangents; dx,
    # dB and dC are rounded to bf16 on the way out.
    whats = ("y", "dx", "ddelta", "da", "dB", "dC", "dD")
    errs = [_close(u, v, 2e-2, f"{name} {what}")
            for u, v, what in zip(got, want, whats)]
    print(f"kernel {name}: " + ", ".join(
        f"{what} err {err:.1e}" for what, err in zip(whats, errs))
        + " (of max|ref|)")


def phase_trainer_pallas_xent(clock, *, model="resnet18", batch=2048,
                              steps=8, extra=()):
    """One short trainer run with the fused cross-entropy in the step."""
    clock.lap()
    summary, epochs = run_trainer(
        [*_trainer_argv(model, batch, steps), *extra, "--train.epochs=1",
         "--train.pallas_xent=true"], WORK / "train_pallas_xent")
    secs, _, _ = clock.lap()
    step_ms = _check_run(summary, epochs, batch)
    print(f"smoke reading, pallas_xent run: compile {secs:.1f} s, steady "
          f"{step_ms:.2f} ms/step, epoch loss {epochs[-1]['loss']:.4f}")


def _on_all(tree, devices, name):
    """Every leaf's sharding spans exactly ``devices``."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    assert leaves, name
    for leaf in leaves:
        assert leaf.sharding.device_set == devices, (
            f"{name}: leaf {leaf.shape} lives on "
            f"{sorted(d.id for d in leaf.sharding.device_set)}")
    return len(leaves)


def _resume_and_inspect(name, argv, ckpt, *, devices, batch, epochs):
    """The last epoch as `train.main` runs it after ``--resume=auto`` —
    `Trainer(cfg).fit()` — keeping the Trainer, to see where the state it
    restored and trained and a batch of its pipeline live, and what
    collectives its compiled step holds. Returns (train loss, eval loss)."""
    import jax

    from tpu_dp.analysis.hlo import (
        _shape_elements,
        collect_ops,
        count_collectives,
    )
    from tpu_dp.config import parse_cli
    from tpu_dp.train.trainer import Trainer

    world = len(devices)
    tr = Trainer(parse_cli([*argv, f"--train.epochs={epochs}",
                            "--resume=auto", f"--train.ckpt_dir={ckpt}"]))
    assert tr.num_devices == world
    assert (tr.start_epoch, int(tr.state.step)) == (epochs - 1, epochs - 1), (
        f"{name}: resumed at epoch {tr.start_epoch}, step "
        f"{int(tr.state.step)}, not {epochs - 1}")
    result = tr.fit()
    assert len(result["history"]) == 1 and int(tr.state.step) == epochs
    n = _on_all(tr.state.params, devices, f"{name} params")
    _on_all(tr.state.batch_stats or tr.state.step, devices,
            f"{name} batch_stats")
    tr.train_pipe.set_epoch(0)
    _, first = next(iter(tr.train_pipe.windows(1)))
    _on_all(first, devices, f"{name} batch")
    for key, leaf in first.items():
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        assert rows == {batch // world}, (name, key, rows)
    opt = jax.tree_util.tree_leaves(tr.state.opt_state)
    _on_all(opt, devices, f"{name} opt_state")
    per_device = sum(s.data.size for leaf in opt
                     for s in leaf.addressable_shards
                     if s.device == jax.devices()[0])
    total = sum(leaf.size for leaf in opt)
    if name == "sharded":
        assert per_device * world == total, (per_device, total)
    else:
        assert per_device == total, (per_device, total)
    # The step holds the collectives this mode is made of: as traced
    # (StableHLO) and as the compiler left them. XLA may rewrite them — on
    # a 2x2 host the v5e compiler turns the reduce-scatters into one
    # combined all-reduce and slices, and the all-gathers of leaves under
    # 512 elements into an all-reduce — so the compiled text is held to
    # what no rewrite takes away: nearly every gradient element is reduced
    # across devices (the metric scalars' all-reduces do not count) and,
    # sharded, nearly every parameter element is gathered back.
    lowered = tr.train_step.lower(tr.state, first)
    traced = lowered.as_text()
    compiled = lowered.compile().as_text()
    ops, counts = collect_ops(compiled), count_collectives(compiled)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(tr.state.params))
    reduced = sum(_shape_elements(op.shape) for op in ops
                  if op.kind == "all-reduce" and not op.is_scalar)
    reduced += world * sum(_shape_elements(op.shape) for op in ops
                           if op.kind == "reduce-scatter")
    gathered = sum(_shape_elements(op.shape) for op in ops
                   if op.kind == "all-gather")
    assert reduced >= 0.99 * n_params, (name, reduced, n_params, counts)
    if name == "sharded":
        assert "reduce_scatter" in traced and "all_gather" in traced
        assert gathered >= 0.99 * n_params, (name, gathered, n_params, counts)
    else:
        assert "reduce_scatter" not in traced
        assert not counts.get("reduce-scatter"), counts
    print(f"{name}: resumed at step {epochs - 1}; {n} param leaves and the "
          f"batch on {world} devices ({batch // world} rows each), "
          f"optimizer state {per_device}/{total} elements per device; "
          f"compiled step reduces {reduced} and gathers {gathered} "
          f"elements for {n_params} parameters, collectives {counts}")
    return result["history"][0]["loss"], result["eval"]["loss"]


def compare_data_parallel(*, model="resnet18", batch=512, steps=10,
                          world=4, atol=DP_LOSS_ATOL):
    """One device vs ``world`` devices, replicated and sharded update. The
    train set is one global batch, so an epoch is a step and every
    per-epoch loss a per-step loss. One device trains ``steps + 1`` epochs
    in one go through `train.main`; the others train ``steps`` through
    `train.main`, checkpoint, and take the last one after ``--resume=auto``
    (`_resume_and_inspect`) — so the last loss also says the optimizer
    state came back from the checkpoint onto the devices it was saved
    from."""
    import jax

    devices = set(jax.devices()[:world])
    assert len(devices) == world, f"{len(jax.devices())} device(s) < {world}"
    base = [
        f"--model.name={model}", "--data.dataset=synthetic",
        f"--data.batch_size={batch}", f"--data.synthetic_train_size={batch}",
        f"--data.synthetic_test_size={batch}", "--data.device_resident=off",
        "--optim.lr=0.05", "--optim.schedule=constant", "--train.seed=0",
        "--train.log_every=1",
    ]
    runs = {
        "one device": [*base, "--parallel.num_devices=1"],
        "replicated": [*base, f"--parallel.num_devices={world}"],
        "sharded": [*base, f"--parallel.num_devices={world}",
                    "--train.update_sharding=sharded"],
    }
    losses, evals = {}, {}
    for name, argv in runs.items():
        ckpt = WORK / f"dp_{name.replace(' ', '_')}"
        n_epochs = steps + 1 if name == "one device" else steps
        summary, epochs = run_trainer(
            [*argv, f"--train.epochs={n_epochs}"], ckpt)
        assert [e["epoch"] for e in epochs] == list(range(1, n_epochs + 1))
        losses[name] = [e["loss"] for e in epochs]
        evals[name] = summary["eval"]["loss"]
        if name == "one device":
            assert summary["devices"] == 1, summary
        else:
            assert summary["devices"] == world, summary
            last, evals[name] = _resume_and_inspect(
                name, argv, ckpt, devices=devices, batch=batch,
                epochs=steps + 1)
            losses[name].append(last)
        assert all(math.isfinite(x) for x in [*losses[name], evals[name]])
        print(f"{name}: losses {[round(x, 5) for x in losses[name]]}, "
              f"eval loss {evals[name]:.5f}")
    for name in ("replicated", "sharded"):
        diffs = [abs(a - b) for a, b in
                 zip(losses[name], losses["one device"])]
        eval_diff = abs(evals[name] - evals["one device"])
        print(f"{name} vs one device: |loss difference| per step "
              f"{[float(f'{d:.1e}') for d in diffs]} (the last after "
              f"--resume=auto), max {max(diffs):.3e} (allowed {atol:.0e}); "
              f"eval {eval_diff:.3e} (allowed {EVAL_SLACK * atol:.0e})")
        assert max(diffs) <= atol, (
            f"{name} departs from one device by {max(diffs):.3e} > "
            f"{atol:.0e}")
        assert eval_diff <= EVAL_SLACK * atol, (
            f"{name}: eval loss departs from one device by "
            f"{eval_diff:.3e} > {EVAL_SLACK * atol:.0e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from tpu_dp.obs.chips import chip_spec
    from tpu_dp.utils import place_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    spec = chip_spec(device["kind"])
    print(f"device: platform={device['platform']} kind={device['kind']!r} "
          f"count={device['count']} -> "
          + (f"{spec.name}: {spec.peak_flops / 1e12:.0f} TFLOP/s bf16, "
             f"{spec.hbm_gbs:.0f} GB/s HBM" if spec else "no such chip in "
             "tpu_dp.obs.chips"))
    if device["platform"] != "tpu" or spec is None:
        print("chip_smoke: needs a TPU that tpu_dp.obs.chips knows",
              file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {place_compile_cache()}")

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    try:
        if args.chips == 4:
            compare_data_parallel()
        else:
            clock = _CompileClock()
            phase_trainer(clock)
            phase_kernels()
            phase_trainer_pallas_xent(clock)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
