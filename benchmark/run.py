#!/usr/bin/env python3
"""One cell of the benchmark, one run: the trainer's own loop on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It reads the cell from
``BENCHMARK.json`` (configuration, traffic, chips), the configuration's and
the traffic's files by their names and the configuration's family
(``families/<family>.py``, which knows what the data, the weights, the
reference and the required work of such a model are), builds the program's
`Config` from the argv those files hold, constructs the program's `Trainer`
on the family's data from ``--seed`` and drives `Trainer.train_epoch`,
whole epochs one after another as `Trainer.fit` does between its
checkpoints: a warm-up epoch outside the window (it compiles or loads the
step, and its first steps are what ``correct`` compares), a fence, epochs
until the clock has passed ``--seconds``, a fence. The window is what
actually ran, overshoot included.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (the same loop with ``train.obs=basic`` and a profiler
window of a few steady steps). The last line of standard output is one JSON
object; without the cell's TPUs the run fails and prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
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
    cell["family"] = load_family(cell["config_file"], config["file"])
    cell["traffic_file"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (HERE / "cells" / f"{name}.json").read_text())["limits"]

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return cell


def load_module(name: str, path: Path):
    """A module of the benchmark's by its file, under no fixed name."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


# What a family's file answers (`README.md`, "What a family file answers").
FAMILY_API = ("datasets", "init_params", "first_gradient", "variants",
              "reference_readings", "train_flops_per_item",
              "least_step_seconds", "items_per_row")


def load_family(config: dict, where: str):
    """The module ``families/<family>.py`` that the configuration's file
    names under ``family``."""
    name = config.get("family")
    if not name:
        raise Refused(f"{where} has no key 'family': it names the file "
                      f"under benchmark/families/ that knows such a model")
    path = HERE / "families" / f"{name}.py"
    if not path.is_file():
        raise Refused(f"{where} names the family {name!r}, and there is "
                      f"no file {path}")
    return check_family(load_module(f"bench_family_{name}", path), path)


def check_family(module, path):
    missing = [f for f in FAMILY_API if not callable(getattr(module, f, None))]
    if missing:
        raise Refused(f"{path} does not answer {missing}")
    return module


def read_metric(name: str, ctx: dict):
    """A per-layer metric by its own file and reader; None where the reader
    finds nothing to read."""
    spec = json.loads((HERE / "metrics" / f"{name}.json").read_text())
    module = load_module(
        f"bench_reader_{spec['reader']}",
        HERE / "metrics" / "readers" / f"{spec['reader']}.py")
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
# and the weights, both made by the configuration's family.
PROGRAM_SEED = 0


@dataclasses.dataclass(frozen=True)
class Job:
    """What a family's functions are told of a run: the configuration's and
    the traffic's files, the seeds, and the sizes as this run has them (a
    rehearsal cuts the batch and the data set)."""

    config: dict
    traffic: dict
    seed: int
    chips: int
    batch_per_chip: int
    train_size: int
    program_seed: int = PROGRAM_SEED

    @property
    def global_batch(self) -> int:
        return self.batch_per_chip * self.chips

    @property
    def steps_per_epoch(self) -> int:
        return self.train_size // self.global_batch


def job_of(cell: dict, seed: int, rehearsal: dict | None = None) -> Job:
    sizes = {**cell["traffic_file"], **(rehearsal or {})}
    return Job(cell["config_file"], cell["traffic_file"], int(seed),
               int(cell["chips"]), int(sizes["batch_per_chip"]),
               int(sizes["train_size"]))


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

        from work import load_peaks

        self.family = family = cell["family"]
        self.job = job = job_of(cell, seed, rehearsal)
        if job.steps_per_epoch < 3:
            raise Refused("an epoch has fewer steps than are followed")

        self.devices = devices = jax.devices()
        self.platform = devices[0].platform
        self.kind = devices[0].device_kind
        if not rehearsal and self.platform != "tpu":
            raise Refused(f"needs a TPU, JAX found {self.platform!r}")
        if len(devices) < job.chips:
            raise Refused(f"needs {job.chips} chips, JAX found "
                          f"{len(devices)}")
        # A rehearsal does the readers' arithmetic with the v5e's peaks.
        self.peaks = load_peaks("TPU v5 lite" if rehearsal else self.kind)
        log(f"devices: {len(devices)} x {self.kind} ({self.platform}), "
            f"using {job.chips}")

        from tpu_dp.config import parse_cli
        from tpu_dp.parallel.sharding import replicated_sharding
        from tpu_dp.train.trainer import Trainer
        from tpu_dp.utils import place_compile_cache

        from loop_hook import LoopHook

        self.cache_dir = place_compile_cache()
        self.workdir = tempfile.mkdtemp(prefix="tpu_dp_bench_")
        argv = list(job.config["argv"]) + list(job.traffic.get("argv", [])) + [
            f"--data.batch_size={job.global_batch}",
            f"--train.seed={PROGRAM_SEED}",
            f"--parallel.num_devices={job.chips}",
            f"--train.ckpt_dir={self.workdir}/ckpt",
            "--resilience.handle_signals=false",
        ]
        if trace:
            argv.append("--train.obs=basic")
        self.cfg = cfg = parse_cli(argv)

        datasets = family.datasets(job)
        log(f"data: {job.train_size} rows made from seed {seed}")
        self.trainer = trainer = Trainer(cfg, datasets=datasets)
        if len(trainer.train_pipe) != job.steps_per_epoch:
            raise RuntimeError(
                f"the trainer plans {len(trainer.train_pipe)} steps an "
                f"epoch, the traffic file gives {job.steps_per_epoch}")
        # Weights from the seed, made on the device in one call, in the
        # place of those the program drew from its own seed.
        params = jax.device_put(family.init_params(job),
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
            trainer,
            functools.partial(family.first_gradient,
                              optimizer=job.config["optimizer"]),
            job.steps_per_epoch, self.trace_dir)
        trainer.add_hook(self.hook)
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
        del trainer
        gc.collect()

    def close(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def window_metrics(cell: dict, family, job: Job, peaks: dict, window: dict,
                   trace: bool, reduced=None, spans=(),
                   memory_peak: int = 0) -> tuple[int, dict]:
    """``(items a step, metrics)`` of a window of ``steps`` steps that took
    ``seconds``, with ``gaps_ms`` between completions and ``setup_s``
    before it: the cell's end-to-end metrics, or (``trace``) its per-layer
    metrics as their readers find them in the reduced trace and the spans.
    """
    # What the window counts: a batch row holds as many items as the
    # family says (an image is one; a row of tokens its counted tokens).
    items_per_step = job.global_batch * int(family.items_per_row(job))
    metrics = {}
    if not trace:
        values = {
            "throughput_per_chip": (window["steps"] * items_per_step
                                    / window["seconds"] / job.chips),
            "step_ms_p95": percentile(window["gaps_ms"], 95.0),
            "setup_s": window["setup_s"],
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        return items_per_step, metrics
    ctx = {
        "trace": reduced, "spans": spans, "chips": job.chips,
        "global_batch": job.global_batch,
        "batch_per_chip": job.batch_per_chip,
        "items_per_step": items_per_step,
        "flops_per_item": family.train_flops_per_item(job),
        "least_step_s": family.least_step_seconds(job, peaks)["seconds"],
        "peaks": peaks, "memory_peak_bytes": memory_peak,
        "config": job.config,
    }
    for m in cell["per_layer"]:
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return items_per_step, metrics


def run(cell: dict, seed: int, seconds: float, trace: bool,
        rehearsal: dict | None = None) -> dict:
    """Drive one run of ``cell``; returns the result line as a dict.

    ``rehearsal`` (the rehearsal script's and the tests' alone) skips the
    look for the chip and cuts the sizes; its result carries no metric.
    """
    import jax

    from loop_hook import FOLLOWED_STEPS

    ses = Session(cell, seed, trace, rehearsal)
    try:
        trainer, hook = ses.trainer, ses.hook
        family, job = ses.family, ses.job
        chips, steps_per_epoch = job.chips, job.steps_per_epoch
        global_batch = job.global_batch
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
        ref = family.reference_readings(job, FOLLOWED_STEPS)
        log("reference followed")
    finally:
        ses.close()

    from compare import compare, observed, render

    correct, rows = compare(prog, ref, cell["limits"])
    window = {"seconds": window_s, "steps": steps, "gaps_ms": gaps_ms,
              "setup_s": setup_s}
    items_per_step, metrics = window_metrics(
        cell, family, job, ses.peaks, window, trace, reduced, spans,
        memory_peak)

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
                        "global_batch": global_batch,
                        "items_per_step": items_per_step,
                        "setup_s": setup_s}
    result["compared"] = [
        {k: (None if isinstance(v, float) and not math.isfinite(v) else v)
         for k, v in row.items()} for row in rows]
    for name, value in observed(prog, ref).items():
        print(f"observed {name} value={value:.6g}", file=sys.stderr)
    print(render(rows), file=sys.stderr, flush=True)
    return result


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
