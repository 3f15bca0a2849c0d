#!/usr/bin/env python3
"""One cell of the benchmark, one run: the trainer's own loop on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It reads the cell from
``BENCHMARK.json`` (configuration, traffic, chips), the configuration's and
the traffic's files by their names, builds the program's `Config` from the
argv those files hold, constructs the program's `Trainer` on data made from
``--seed`` and drives `Trainer.train_epoch`, whole epochs one after another
as `Trainer.fit` does between its checkpoints: a warm-up epoch outside the
window (it compiles or loads the step, and its first steps are what
``correct`` compares), a fence, epochs until the clock has passed
``--seconds``, a fence. The window is what actually ran, overshoot included.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (the same loop with ``train.obs=basic`` and a profiler
window of a few steady steps). The last line of standard output is one JSON
object; without the cell's TPUs the run fails and prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
for p in (str(REPO), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


class Refused(Exception):
    """The run cannot be made here: no result is printed."""


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------------- the cell

def load_cell(name: str) -> dict:
    """The cell as its files state it, found by the names in BENCHMARK.json."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_file"] = json.loads((REPO / config["file"]).read_text())
    cell["traffic_file"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (HERE / "cells" / f"{name}.json").read_text())["limits"]

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return cell


def read_metric(name: str, ctx: dict):
    """A per-layer metric by its own file and reader; None where the reader
    finds nothing to read."""
    spec = json.loads((HERE / "metrics" / f"{name}.json").read_text())
    path = HERE / "metrics" / "readers" / f"{spec['reader']}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reader_{spec['reader']}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    value = module.read(ctx, **spec.get("args", {}))
    return None if value is None else float(value)


def percentile(values, q: float) -> float:
    ordered = sorted(values)
    rank = (q / 100.0) * (len(ordered) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


# --------------------------------------------------------------------- a run

# The program bakes `train.seed` into its compiled step (the augmentation's
# key is a constant of the program), so a run with a new `train.seed`
# compiles for a minute. The program's own seed therefore stays fixed, which
# fixes the order of the rows and the crops; what `--seed` draws is the data
# and the weights, both made here.
PROGRAM_SEED = 0


class Session:
    """One cell, sized and built: the program's trainer on the seed's data
    and weights, with the benchmark's hook in its loop."""

    def __init__(self, cell: dict, seed: int, trace: bool,
                 rehearsal: dict | None):
        import jax

        # Every program goes to the persistent cache, the small ones too:
        # a run's set-up otherwise compiles some hundred of them again.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

        import reference
        import work
        from datagen import make_dataset

        self.cell, self.seed = cell, int(seed)
        self.chips = int(cell["chips"])
        self.config, traffic = cell["config_file"], cell["traffic_file"]
        self.model = model = self.config["model"]
        self.batch_per_chip = int(traffic["batch_per_chip"])
        self.train_size = int(traffic["train_size"])
        if rehearsal:
            self.batch_per_chip = int(
                rehearsal.get("batch_per_chip", self.batch_per_chip))
            self.train_size = int(
                rehearsal.get("train_size", self.train_size))
        self.global_batch = self.batch_per_chip * self.chips
        self.steps_per_epoch = self.train_size // self.global_batch
        if self.steps_per_epoch < 3:
            raise Refused("an epoch has fewer steps than are followed")

        self.devices = devices = jax.devices()
        self.platform = devices[0].platform
        self.kind = devices[0].device_kind
        if not rehearsal and self.platform != "tpu":
            raise Refused(f"needs a TPU, JAX found {self.platform!r}")
        if len(devices) < self.chips:
            raise Refused(f"needs {self.chips} chips, JAX found "
                          f"{len(devices)}")
        # A rehearsal does the readers' arithmetic with the v5e's peaks.
        self.peaks = work.load_peaks("TPU v5 lite" if rehearsal
                                     else self.kind)
        log(f"devices: {len(devices)} x {self.kind} ({self.platform}), "
            f"using {self.chips}")

        from tpu_dp.config import parse_cli
        from tpu_dp.data.cifar import ArrayDataset
        from tpu_dp.parallel.sharding import replicated_sharding
        from tpu_dp.train.trainer import Trainer
        from tpu_dp.utils import place_compile_cache

        from loop_hook import LoopHook

        self.cache_dir = place_compile_cache()
        self.workdir = tempfile.mkdtemp(prefix="tpu_dp_bench_")
        argv = list(self.config["argv"]) + list(traffic.get("argv", [])) + [
            f"--data.batch_size={self.global_batch}",
            f"--train.seed={PROGRAM_SEED}",
            f"--parallel.num_devices={self.chips}",
            f"--train.ckpt_dir={self.workdir}/ckpt",
            "--resilience.handle_signals=false",
        ]
        if trace:
            argv.append("--train.obs=basic")
        self.cfg = cfg = parse_cli(argv)
        self.augmented = bool(cfg.data.augment)

        classes = int(model["num_classes"])
        images, labels = make_dataset(
            seed, self.train_size, classes, int(model["image_size"]),
            int(model["image_channels"]))
        name = cfg.data.dataset
        train_ds = ArrayDataset(images, labels, name, classes, synthetic=True)
        test_ds = ArrayDataset(images[:self.global_batch],
                               labels[:self.global_batch], name, classes,
                               synthetic=True)
        log(f"data: {self.train_size} items made from seed {seed}")

        class BenchTrainer(Trainer):
            """The program's trainer on the benchmark's inputs."""

            def _load_data(self, cfg):
                self.train_ds, self.test_ds = train_ds, test_ds

        self.trainer = trainer = BenchTrainer(cfg)
        if len(trainer.train_pipe) != self.steps_per_epoch:
            raise RuntimeError(
                f"the trainer plans {len(trainer.train_pipe)} steps an "
                f"epoch, the traffic file gives {self.steps_per_epoch}")
        # Weights from the seed, made on the device in one call, in the
        # place of those the program drew from its own seed.
        params = jax.device_put(reference.init_params(model, seed),
                                replicated_sharding(trainer.mesh))
        ours = jax.tree_util.tree_map(
            lambda x: (x.shape, x.dtype), params)
        theirs = jax.tree_util.tree_map(
            lambda x: (x.shape, x.dtype), trainer.state.params)
        if ours != theirs:
            raise RuntimeError("the configuration's file and the program "
                               "disagree on the parameters' shapes")
        trainer.state = trainer.state.replace(params=params)
        self.trace_dir = os.path.join(self.workdir, "trace") if trace else None
        self.hook = LoopHook(
            trainer, float(self.config["optimizer"]["weight_decay"]),
            self.steps_per_epoch, self.trace_dir)
        # The program offers no public seam for an outside hook.
        trainer._hooks.append(self.hook)
        log("trainer built")

    def warm_up(self) -> None:
        """The first epoch: compiles or loads the step, and its first steps
        are the ones the comparison follows. Same object, same call, same
        feed as the window's. Ends fenced."""
        import jax

        self.hook.capture_initial()
        self.trainer.train_epoch(0)
        jax.block_until_ready(self.trainer.state)

    def release(self) -> None:
        """Free the program's state, so that the reference has the chip."""
        trainer, self.trainer, self.hook = self.trainer, None, None
        del trainer.state
        trainer._resident_train = None
        trainer._hooks.clear()
        del trainer
        gc.collect()

    def close(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rehearsal: dict | None = None) -> dict:
    """Drive one run of ``cell``; returns the result line as a dict.

    ``rehearsal`` (the rehearsal script's and the tests' alone) skips the
    look for the chip and cuts the sizes; its result carries no metric.
    """
    import jax

    import work

    ses = Session(cell, seed, trace, rehearsal)
    try:
        trainer, hook = ses.trainer, ses.hook
        chips, steps_per_epoch = ses.chips, ses.steps_per_epoch
        global_batch = ses.global_batch
        ses.warm_up()
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        log(f"set-up done ({setup_s:.2f}s, cache {ses.cache_dir})")

        if trace:
            # In the middle of the window; a rehearsal's window is one epoch.
            hook.trace_after = t0 + (0.0 if rehearsal else 0.4 * seconds)
        epoch = 1
        while time.perf_counter() - t0 < seconds:
            trainer.train_epoch(epoch)
            epoch += 1
        jax.block_until_ready(trainer.state)
        t1 = time.perf_counter()
        hook.finish()
        window_s = t1 - t0
        steps = (epoch - 1) * steps_per_epoch
        log(f"window: {steps} steps in {window_s:.3f}s")

        stamps = hook.done[steps_per_epoch:]
        if len(stamps) != steps:
            raise RuntimeError(f"{len(stamps)} completions for {steps} steps")
        failed = sum(1 for _, v in stamps if not math.isfinite(v))
        times = [t0] + [t for t, _ in stamps]
        gaps_ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]

        memory = [d.memory_stats() or {} for d in ses.devices[:chips]]
        # Live buffers and the running program's reserved memory are
        # counted apart by the runtime; the chip holds both at once.
        memory_peak = max((m.get("peak_bytes_in_use", 0)
                           + m.get("peak_bytes_reserved", 0))
                          for m in memory)
        spans = []
        if trainer.spans is not None:
            spans = [r["spans"] for r in trainer.spans.records()][-steps:]
        prog = hook.first_steps()

        reduced = None
        if trace:
            from trace_reduce import find_xplane, reduce_trace

            xplane = find_xplane(ses.trace_dir)
            if xplane is None:
                raise RuntimeError("the traced run left no trace")
            reduced = reduce_trace(xplane)
            log(f"trace reduced: {len(reduced['devices'])} device planes")

        del trainer, hook, stamps
        ses.release()
        ref = reference_readings(cell, seed, chips, global_batch,
                                 ses.train_size, steps_per_epoch,
                                 ses.augmented)
        log("reference followed")
    finally:
        ses.close()

    from compare import compare, observed, render

    model, peaks = ses.model, ses.peaks
    correct, rows = compare(prog, ref, cell["limits"])
    least = work.least_step_seconds(
        model, ses.batch_per_chip, ses.config["precision"]["compute"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    metrics = {}
    if not trace:
        values = {
            "throughput_per_chip": steps * global_batch / window_s / chips,
            "step_ms_p95": percentile(gaps_ms, 95.0),
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {
            "trace": reduced, "spans": spans, "chips": chips,
            "global_batch": global_batch,
            "batch_per_chip": ses.batch_per_chip,
            "flops_per_item": work.train_flops_per_item(model),
            "least_step_s": least["seconds"],
            "peaks": peaks, "memory_peak_bytes": memory_peak,
            "model": model,
        }
        for m in cell["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": ses.platform, "kind": ses.kind, "count": chips,
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if rehearsal:
        result["rehearsal"] = True
        result["would_report"] = sorted(metrics)
        metrics = {}
    result["metrics"] = metrics
    result["device"] = device
    if trace and reduced and reduced.get("devices"):
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["fullest"]["device_ops"],
            "idle_gaps": reduced["fullest"]["idle_gaps"],
        }
    result["window"] = {"seconds": window_s, "steps": steps,
                        "steps_per_epoch": steps_per_epoch,
                        "global_batch": global_batch, "setup_s": setup_s}
    result["compared"] = [
        {k: (None if isinstance(v, float) and not math.isfinite(v) else v)
         for k, v in row.items()} for row in rows]
    for name, value in observed(prog, ref).items():
        print(f"observed {name} value={value:.6g}", file=sys.stderr)
    print(render(rows), file=sys.stderr, flush=True)
    return result


def reference_readings(cell, seed, chips, global_batch, train_size,
                       steps_per_epoch, augmented, precision="float32",
                       fault=None) -> dict:
    """The plain reference's reading of the first steps, on the cell's chips."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import reference
    from datagen import device_dataset, step_rows
    from loop_hook import FOLLOWED_STEPS

    model = cell["config_file"]["model"]
    sharding = None
    if chips > 1:
        mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
        sharding = NamedSharding(mesh, P("data"))
    images, labels = device_dataset(
        seed, train_size, int(model["num_classes"]),
        int(model["image_size"]), int(model["image_channels"]))
    batches = []
    for k in range(FOLLOWED_STEPS):
        rows = step_rows(PROGRAM_SEED, 0, train_size, global_batch, k)
        x, y = images[rows], labels[rows]
        if sharding is not None:
            x, y = jax.device_put((x, y), sharding)
        batches.append((x, y))
    del images, labels
    return reference.follow(
        model, cell["config_file"]["optimizer"], seed, PROGRAM_SEED,
        steps_per_epoch, augmented, batches, precision=precision,
        fault=fault, chips=chips, batch_sharding=sharding)


def main(argv=None, rehearsal: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     rehearsal)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
