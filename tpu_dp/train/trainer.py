"""Trainer: epochs, logging, eval, checkpoint — the reference's `main()`.

One code path from one chip to a full slice (mesh shape is the only
variable), replacing the reference's forked `cifar_example.py` /
`cifar_example_ddp.py` pair. Reproduces the observable behavior of
`/root/reference/cifar_example_ddp.py:90-136`: per-epoch `set_epoch`
reshuffle (`:92`), running-loss print every `log_every` steps in the
reference's exact format (`:111-114`, but process-0-gated and with a correct
remainder divisor), end-of-training weights export (`:118-119`), and a
synced-accuracy eval (`:124-136`) — plus what the reference lacks: resume,
throughput metering, and profiler hooks (SURVEY.md §5).

Hot-loop discipline: between an epoch's first dispatch and its fence the
Python loop launches no device program but the compiled step. It keeps the
returned replicated scalars as they came and adds them up on the host where
it fetches anyway, at log boundaries and epoch ends (`_EpochSums`), and it
stays `MAX_INFLIGHT` dispatches ahead of the device, so the input
pipeline's prefetch overlaps and the device never waits (unlike the
reference, whose `loss.item()` syncs every step, `cifar_example.py:83`).
"""

from __future__ import annotations

import collections
import functools
import json
import operator
import time
import weakref
from pathlib import Path
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

from tpu_dp import checkpoint as ckpt_lib
from tpu_dp.config import Config
from tpu_dp.data.cifar import load_dataset
from tpu_dp.data.pipeline import DataPipeline
from tpu_dp.models import build_model
from tpu_dp.parallel import dist
from tpu_dp.train.optim import SGD
from tpu_dp.train.schedule import make_schedule
from tpu_dp.obs import compiles
from tpu_dp.obs.counters import counters as _obs_counters
from tpu_dp.obs.spans import publish_before_trainer, setup_span
from tpu_dp.train.state import create_train_state
from tpu_dp.train.step import make_eval_step, make_train_step
from tpu_dp.utils import (
    StepProfiler,
    ThroughputMeter,
    log0,
    parse_profile_steps,
    print0,
    profile_trace,
)


def _unstack(stacked, n):
    """Lazy per-step views over a window's stacked metrics — no host sync."""
    return tuple({k: v[j] for k, v in stacked.items()} for j in range(n))


def _place_leaf(x, sharding):
    """One leaf of a state committed to ``sharding`` (`Trainer._place_state`).
    A process's own array behind a sharding across processes is placed a
    piece a device where this process holds the target, as a jit's first
    call places it: every process made the same value from one seed, so
    nothing is compared across them."""
    if (isinstance(x, jax.Array) and x.is_fully_addressable
            and not sharding.is_fully_addressable):
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])
    return jax.device_put(x, sharding, may_alias=True)


class _Program(NamedTuple):
    """One compiled train program as the loop calls it (`Trainer._program`).

    ``run(state, *fed, *guard_args)`` is the (recompile-guarded) program,
    ``tag`` its name to the cost registry, the efficiency meter and the
    guard's reports."""

    run: Callable
    tag: str

    @staticmethod
    def steps(out, n: int):
        """Per-step views of a dispatch's metrics — lazy, no host sync. A
        program of several steps returns them stacked by step, one of a
        single step that step's own (`make_train_step`)."""
        return _unstack(out, n) if n > 1 else (out,)


#: Dispatches unfinished at most, the one the loop has just launched among
#: them. Two: one running and one queued keep the device fed across the
#: host's own work, and hooks (guard, snapshots, preemption, a profiler's
#: window) act at the host's step, so every one more would make them answer
#: a step later.
MAX_INFLIGHT = 2


class _EpochSums:
    """An epoch's running sums (loss, correct, a model's counters), added up
    on the host so that the loop launches no device add between two steps.

    `keep` takes a dispatch's metrics as the program returned them: device
    arrays, a step's own or stacked by step. `fetch` brings what is kept to
    the host with one `jax.device_get` (the loop calls it where it
    synchronises anyway: the log line and the epoch's fence) and adds it up
    a step at a time in the arrays' own dtype: the order and the precision
    of a running sum on the device, so the same bits.
    """

    NAMES = ("loss", "correct", "counters")

    def __init__(self):
        # Dispatches not fetched yet, oldest first: (steps, their metrics).
        self.kept: list[tuple[int, dict]] = []
        self.total: dict[str, Any] = {}  # name -> sum over the fetched steps
        self._unlogged: list = []  # fetched losses no log line has shown

    def keep(self, out, n: int) -> None:
        self.kept.append((n, {k: out[k] for k in self.NAMES if k in out}))

    def fetch(self) -> None:
        host, self.kept = jax.device_get(self.kept), []
        for n, metrics in host:
            for step in _Program.steps(metrics, n):
                self._unlogged.append(step["loss"])
                for name, row in step.items():
                    self.total[name] = (
                        row if name not in self.total
                        else self.total[name] + row)

    def running_loss(self, steps: int) -> float:
        """Mean loss of the ``steps`` oldest steps no log line has shown."""
        self.fetch()
        rows, self._unlogged = self._unlogged[:steps], self._unlogged[steps:]
        return float(functools.reduce(operator.add, rows)) / steps


def _iso_ts(epoch_seconds: float) -> str:
    """ISO-8601 UTC stamp for metrics records (millisecond resolution)."""
    from datetime import datetime, timezone

    return datetime.fromtimestamp(
        epoch_seconds, timezone.utc
    ).isoformat(timespec="milliseconds")


class _RegroupSignal(Exception):
    """Raised out of `train_epoch` by a survivor when a quiesce completed:
    the mesh must shrink before the next step (`Trainer._execute_regroup`).
    Internal control flow — never escapes `fit()`."""

    def __init__(self, epoch: int, done: int, plan):
        super().__init__(f"elastic regroup at epoch {epoch} step {done}")
        self.epoch = int(epoch)
        self.done = int(done)
        self.plan = plan


class _GuardRollback(Exception):
    """Raised out of `train_epoch` by the guard hook when the divergence
    policy escalates to rollback: rewind to the newest complete (and
    non-quarantined) save before the next step
    (`Trainer._execute_guard_rollback`). Internal control flow — never
    escapes `fit()`."""

    def __init__(self, epoch: int, done: int, trigger):
        super().__init__(
            f"guard rollback at epoch {epoch} step {done}: {trigger.reason}"
        )
        self.epoch = int(epoch)
        self.done = int(done)
        self.trigger = trigger


def _elastic_fatal_errors() -> tuple[type[BaseException], ...]:
    """Exception types that mean "a peer is gone" in elastic mode:
    a wedged/failed collective (XLA runtime) or an exhausted resilient
    ring (`PeerFailedError`) — the rollback-regroup triggers."""
    from tpu_dp.resilience import PeerFailedError

    errs: list[type[BaseException]] = [PeerFailedError]
    try:
        from jax._src.lib import xla_extension

        errs.append(xla_extension.XlaRuntimeError)
    except Exception:  # jaxlib layout drift: JaxRuntimeError still covers it
        pass
    try:
        errs.append(jax.errors.JaxRuntimeError)
    except AttributeError:
        pass
    return tuple(errs)


class Trainer:
    def __init__(self, cfg: Config, mesh=None, datasets=None):
        """``datasets`` hands the trainer its ``(train, test)`` data sets
        (`ArrayDataset`s or `TokenDataset`s) in place of those ``cfg.data``
        would load.

        Set-up is timed from here, at every ``train.obs``
        (docs/OBSERVABILITY.md "Set-up"): the process's time before this
        first construction, the construction, the caller's time after it
        and the first epoch, and what of them went to making programs."""
        self._compiles = compiles.install()
        self._compiles.begin()
        publish_before_trainer()
        with setup_span("trainer"):
            self._construct(cfg, mesh, datasets)
        self._setup: setup_span | None = setup_span("caller")

    def _construct(self, cfg: Config, mesh, datasets) -> None:
        self.cfg = cfg
        # Elastic grow (docs/RESILIENCE.md "Grow"): before any classic
        # bootstrap, a starting process may instead JOIN a live run it
        # discovers through the membership ledger — the relaunched-after-
        # preemption path (`resilience.elastic_join`). The handshake
        # (fenced join request → admission → re-initialize into the grown
        # mesh) runs first because it replaces the bootstrap entirely:
        # the joiner's world and dense rank exist only once the members
        # admit it.
        self._join = None
        if cfg.resilience.elastic and mesh is None:
            from tpu_dp.resilience.elastic import maybe_join

            # Knowable-locally config errors must fail BEFORE the join
            # handshake: past confirm_join_ready, a dying joiner bills
            # the incumbents a whole quiesce + bootstrap timeout +
            # fallback regroup. (Deeper, dataset-dependent validation
            # still runs post-join; a joiner failing THERE costs the
            # fleet one bounded aborted grow — documented trade.)
            if not cfg.data.drop_remainder:
                raise ValueError(
                    "resilience.elastic requires data.drop_remainder=true "
                    "(the mid-epoch re-split carries no weight masks)"
                )
            self._join = maybe_join(cfg)
        if self._join is not None:
            self.ctx = self._join.ctx
        else:
            self.ctx = dist.initialize(
                cfg.parallel.coordinator_address,
                cfg.parallel.num_processes,
                cfg.parallel.process_id,
                elastic=cfg.resilience.elastic,
            )
        if mesh is not None and cfg.resilience.elastic:
            raise ValueError(
                "resilience.elastic cannot rebuild a caller-injected mesh "
                "after a regroup; pass parallel.num_devices instead"
            )
        self.mesh = mesh if mesh is not None else dist.data_mesh(
            num_devices=cfg.parallel.num_devices
        )
        self.num_devices = int(self.mesh.devices.size)
        # A parallel.num_devices restriction is remembered per process so
        # a regroup can rebuild the same per-process device footprint at
        # the new world (the restriction names a GLOBAL count for the
        # launch world; the global count shrinks with it).
        self._devices_per_process = (
            self.num_devices // max(1, self.ctx.process_count)
            if cfg.parallel.num_devices is not None else None
        )
        log0("topology: %s", json.dumps(dist.describe(self.mesh)))

        if datasets is not None:
            self.train_ds, self.test_ds = datasets
        else:
            self._load_data(cfg)

        # The dataset determines the number of classes; an explicit config
        # value must agree (a silently mis-sized head clamps labels inside
        # the compiled loss and trains garbage with no error).
        num_classes = self.train_ds.num_classes
        if cfg.model.num_classes is not None and (
            cfg.model.num_classes != num_classes
        ):
            raise ValueError(
                f"model.num_classes={cfg.model.num_classes} conflicts with "
                f"dataset {self.train_ds.name!r} ({num_classes} classes)"
            )

        import jax.numpy as jnp  # local: keep module import light

        dtype = jnp.bfloat16 if cfg.model.bf16 else jnp.float32
        from tpu_dp.models import parse_fused_stages

        # Cross-replica sharded weight update (docs/PERF.md). Validated
        # before model construction because the sharded path runs the
        # explicit-collectives `shard_map` program, where BatchNorm models
        # must sync their batch statistics in-forward (axis_name=DATA_AXIS
        # — sync-BN semantics, matching the global-batch stats the GSPMD
        # path computes automatically).
        us = cfg.train.update_sharding
        if us not in ("replicated", "sharded"):
            raise ValueError(
                f"train.update_sharding must be replicated|sharded, "
                f"got {us!r}"
            )
        if cfg.train.collective_dtype and us != "sharded":
            raise ValueError(
                "train.collective_dtype applies to the sharded update's "
                "reduce-scatter; set train.update_sharding=sharded"
            )
        self.update_sharding = us
        # Quantized collectives (train.collective_dtype=int8, docs/PERF.md
        # "Quantized collectives"): the step factories route quantizable
        # gradient leaves through the blockwise int8 wire codec, and the
        # TrainState carries per-replica error-feedback residuals
        # (initialized by `_with_residuals`, resharded by load_checkpoint).
        self._quant_enabled = cfg.train.collective_dtype in ("int8", "i8")
        if int(cfg.train.quant_block_size) < 1:
            raise ValueError(
                f"train.quant_block_size must be >= 1, got "
                f"{cfg.train.quant_block_size}"
            )
        # Bucketed overlap-scheduled collectives (train.bucket_mb,
        # docs/PERF.md "Overlapped collectives"): parsed once here so a
        # bad value fails at config time, threaded into every step
        # factory, the residual init, and the commprof wire report.
        from tpu_dp.parallel import bucketing

        self._bucket_bytes = bucketing.parse_bucket_mb(cfg.train.bucket_mb)
        if self._bucket_bytes and us != "sharded":
            raise ValueError(
                "train.bucket_mb applies to the sharded update's "
                "reduce-scatter; set train.update_sharding=sharded"
            )
        self._quant_pub_step = -1  # last window whose codec stats published
        # Coupled-knob guard (docs/TUNE.md "Coupled knobs"): the SAME rule
        # the tune search space and dplint DP105 apply — a hand-set config
        # gets the identical warning a tuner-proposed one would.
        from tpu_dp.config import coupling_warning

        coupled = coupling_warning(cfg.train.bucket_mb,
                                   cfg.train.quant_block_size,
                                   cfg.train.collective_dtype)
        if coupled:
            log0("config warning: %s", coupled)
        # A tuned profile (train.profile, set by --profile) is only valid
        # for the (workload, mesh geometry, backend) it was searched on —
        # re-check against the LIVE topology: parse_cli validated the file
        # but could not see the mesh. Typed refusal, never silent drift.
        if cfg.train.profile:
            from tpu_dp.tune.profile import check_key, load_profile

            check_key(load_profile(cfg.train.profile),
                      workload=cfg.model.name,
                      devices=self.num_devices,
                      backend=jax.default_backend(),
                      where="this Trainer")
            log0("profile: %s (key ok: %s x%d on %s)",
                 cfg.train.profile, cfg.model.name, self.num_devices,
                 jax.default_backend())

        from tpu_dp.models import BATCHNORM_MODELS, DECODER_SHAPES

        model_kwargs = dict(
            num_classes=num_classes, dtype=dtype,
            fused_stages=parse_fused_stages(cfg.model.fused_stages),
            fused_block_b=cfg.model.fused_block_b,
            fused_bwd=cfg.model.fused_bwd,
            **{k: getattr(cfg.model, k) for k in DECODER_SHAPES},
        )

        if us == "sharded" and cfg.model.name.lower() in BATCHNORM_MODELS:
            model_kwargs["axis_name"] = dist.DATA_AXIS
        self.model = build_model(cfg.model.name, **model_kwargs)
        # Sync-BN models need the data axis bound even at init; the
        # axis-free twin has the identical parameter tree and initializes
        # anywhere (same trick as tpu_dp.analysis.gradsync).
        self._init_model = self.model
        if "axis_name" in model_kwargs:
            self._init_model = build_model(
                cfg.model.name,
                **{k: v for k, v in model_kwargs.items()
                   if k != "axis_name"})

        # What the step does to its inputs between the feed and the model,
        # keyed by (seed + 1, step): the noise a model's objective is made
        # of (a model that has one makes the function), or the crops.
        augment_fn = None
        if hasattr(self.model, "make_noise_fn"):
            augment_fn = self.model.make_noise_fn(cfg.train.seed + 1)
        elif cfg.data.augment:
            from tpu_dp.data.augment import make_augment_fn

            augment_fn = make_augment_fn(cfg.train.seed + 1)
        self._augment_fn = augment_fn
        # A batch row's counted items (an image: 1; a row of tokens: its
        # length), and the name its rate goes by.
        self._items_per_row = int(self.train_ds.items_per_row)
        self._rate_name = ("images_per_sec" if self._items_per_row == 1
                           else "items_per_sec")
        # RecompileGuard (dplint DP305's runtime half): any post-warmup
        # growth of a step's trace cache is a silent recompile — a
        # step-time cliff this surfaces instead of swallowing. The eval
        # step is deliberately unguarded: its final partial batch
        # legitimately compiles a second variant.
        guard_mode = cfg.train.recompile_guard
        if guard_mode not in ("off", "warn", "raise"):
            raise ValueError(
                f"train.recompile_guard must be off|warn|raise, "
                f"got {guard_mode!r}"
            )
        self._guard = None if guard_mode == "off" else guard_mode
        if int(cfg.train.steps_per_call) < 0:
            raise ValueError(
                f"train.steps_per_call must be >= 0 (0 = auto), "
                f"got {int(cfg.train.steps_per_call)}"
            )
        mode = cfg.data.device_resident
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"data.device_resident must be auto|on|off, got {mode!r}"
            )
        if mode == "on" and not cfg.data.drop_remainder:
            raise ValueError(
                "data.device_resident=on requires data.drop_remainder=true"
            )
        if int(cfg.train.steps_per_call) > 1 and not cfg.data.drop_remainder:
            raise ValueError(
                "train.steps_per_call > 1 requires data.drop_remainder=true"
            )
        if cfg.resilience.elastic and not cfg.data.drop_remainder:
            raise ValueError(
                "resilience.elastic requires data.drop_remainder=true "
                "(the mid-epoch re-split carries no weight masks)"
            )
        # Training guardrails (tpu_dp/resilience/guard.py,
        # docs/RESILIENCE.md "Guardrails"): guard.enabled compiles the
        # sentinel into every train-step program (on-device health summary
        # + guarded update) and registers the GuardHook policy engine.
        self.guard_enabled = bool(cfg.guard.enabled)

        # Everything world-dependent — pipelines, optimizer layout,
        # compiled programs, resident staging — is built by the two
        # builders below so an elastic regroup (`_execute_regroup`) can
        # rebuild it against the shrunk mesh; `__init__` holds only the
        # run-once validation and construction.
        self._build_pipelines()
        if mode == "on":
            ds_bytes = self.train_pipe.dataset_bytes()
            if ds_bytes > cfg.data.resident_max_bytes:
                # Forced on is explicit user intent — warn with the numbers
                # (instead of the opaque allocator error staging would hit
                # on a dataset that genuinely exceeds HBM) and proceed.
                log0(
                    "warning: data.device_resident=on stages %d bytes, over "
                    "data.resident_max_bytes=%d — staging may exhaust "
                    "device memory; raise the budget or use auto",
                    ds_bytes, cfg.data.resident_max_bytes,
                )
        self._build_training()

        with setup_span("init_state"):
            self.state = self._place_state(self._fresh_state())
        self.start_epoch = 0
        self.start_step = 0  # step within start_epoch (mid-epoch resume)
        self.meter = ThroughputMeter(warmup_steps=2)

        self.ckpt_mgr = ckpt_lib.CheckpointManager(
            cfg.train.ckpt_dir, keep=cfg.train.ckpt_keep,
            async_save=cfg.train.ckpt_async,
        )

        # Resilience (tpu_dp/resilience/, docs/RESILIENCE.md): async
        # step-cadence snapshots, SIGTERM/SIGINT preemption hook, and
        # deterministic fault injection for the test suite. The snapshot
        # manager always exists — with snapshot_every_steps=0 the cadence
        # never fires, but the preemption hook's final snapshot still has
        # somewhere to land.
        from tpu_dp.resilience import (
            FaultInjector,
            PreemptionHandler,
            SnapshotManager,
        )

        res = cfg.resilience
        # The unified shared-filesystem IO retry budget: the membership
        # ledger AND checkpoint/snapshot writes derive their backoff
        # schedule from this one knob (tpu_dp/resilience/retry.py).
        from tpu_dp.resilience.retry import configure_io_retry

        configure_io_retry(res.io_retry_s)
        self.snapshot_dir = res.snapshot_dir or str(
            Path(cfg.train.ckpt_dir) / "snapshots"
        )
        self.snap_mgr = SnapshotManager(
            self.snapshot_dir, every_steps=res.snapshot_every_steps,
            keep=res.snapshot_keep, async_save=cfg.train.ckpt_async,
        )
        self.preempt = PreemptionHandler() if res.handle_signals else None
        self.fault = FaultInjector.from_spec(
            res.fault, rank=self.ctx.process_index
        )
        if self.fault is not None and not self.guard_enabled:
            seam = [k for k in self.fault.kinds() if k in ("nan", "spike")]
            if seam:
                # The nan/spike injection seam is compiled into the
                # sentinel step; without the sentinel the fault would
                # silently never fire — the worst property a
                # deterministic injector can have.
                raise ValueError(
                    f"TPU_DP_FAULT {seam[0]!r} requires guard.enabled=true "
                    f"(the injection seam lives in the sentinel-enabled "
                    f"step program)"
                )
        # Elastic world size (tpu_dp/resilience/elastic.py): this rank's
        # stable id is its process index at generation start; dense ranks
        # are reassigned per membership epoch, sids never. A JOINER's
        # stable id is the seat its admission granted — its dense rank at
        # the grown epoch is whatever sorted-sid order assigns.
        self.stable_rank = (
            self._join.coordinator.sid if self._join is not None
            else self.ctx.process_index
        )
        self.elastic = None
        self._epoch_lineage: list[list[int]] = []  # [world, steps] segments
        self._elastic_tail: Any = None
        self._quiesce_plan = None
        self._q_flavor = "graceful"
        if cfg.train.resume and self._join is None:
            self._maybe_resume()
        elif cfg.train.resume:
            log0("elastic join: ignoring --resume — a joiner's state comes "
                 "from the admitted membership record's snapshot, never "
                 "its stale local disk")
        # Host-side mirror of state.step: the snapshot cadence and fault
        # steps key off it without a per-window device sync.
        self._host_step = int(self.state.step)
        if res.elastic and self._join is not None:
            # The admission handshake already attached this process to the
            # live generation; adopt the record's resume truth (state,
            # step clock, re-split lineage) instead of minting anything.
            self.elastic = self._join.coordinator
            self._adopt_join_resume(self._join.record)
        elif res.elastic:
            import uuid

            from tpu_dp.resilience import ElasticCoordinator

            # The generation key combines state every rank already agrees
            # on (resumed global step + launch world) with a launch-unique
            # token minted over the coordination KV store — a restarted
            # incarnation gets a fresh ledger directory even when it
            # resumes from the very same step.
            nonce = dist.agree_token(
                "elastic_gen", lambda: uuid.uuid4().hex[:8],
                timeout_s=res.regroup_timeout_s,
            )
            self.elastic = ElasticCoordinator(
                res.membership_dir or str(
                    Path(cfg.train.ckpt_dir) / "membership"
                ),
                generation=(
                    f"gen_{self._host_step:010d}_w{self.ctx.process_count}"
                    f"_{nonce}"
                ),
                sid=self.stable_rank,
                world=self.ctx.process_count,
                coordinator_address=self.ctx.coordinator_address,
                regroup_timeout_s=res.regroup_timeout_s,
                poll_every_steps=res.elastic_poll_every_steps,
                coordinator_host=res.elastic_coordinator_host,
                min_world=res.elastic_min_world,
                max_world=res.elastic_max_world,
            )
        self._metrics_file = None  # lazily opened by _log_metrics (rank 0)
        self._hb_write_failed = False  # one-shot heartbeat-failure warning

        # Telemetry (tpu_dp/obs/, docs/OBSERVABILITY.md). Everything below
        # is None at obs=off — the hot loop then takes the untelemetered
        # path (one is-None check per window; benched within noise).
        if cfg.train.obs not in ("off", "basic", "full"):
            raise ValueError(
                f"train.obs must be off|basic|full, got {cfg.train.obs!r}"
            )
        self.obs_mode = cfg.train.obs
        self.obs_dir = Path(
            cfg.obs.run_dir or Path(cfg.train.ckpt_dir) / "obs"
        )
        self.spans = None
        self._fence_t = None  # when the last epoch's fence returned
        # The state the last epoch's loop left (weakly): in place already.
        self._epoch_state: weakref.ref | None = None
        self.heartbeat = None
        self.health = None
        if self.obs_mode != "off":
            from tpu_dp.obs import HealthMonitor, HeartbeatWriter, SpanRecorder
            from tpu_dp.obs.spans import InflightSteps

            self.spans = SpanRecorder(capacity=cfg.obs.span_capacity)
            self._inflight = InflightSteps()
            if cfg.obs.heartbeat_every_steps > 0 and self._join is None:
                # Every rank appends to its own heartbeat file — per-rank
                # host IO is the protocol, not a rank gate. A JOINER never
                # writes into the launch obs root: its dense rank's
                # filename there belongs to a me-epoch-0 seat it never
                # held (`_complete_join` homes it into obs/me<E>/).
                self.heartbeat = HeartbeatWriter(
                    self.obs_dir, rank=self.ctx.process_index,
                    every_steps=cfg.obs.heartbeat_every_steps,
                )
            if self.heartbeat is not None and self.ctx.process_index == 0:  # dplint: allow(DP101) host-only monitor
                self.health = HealthMonitor(
                    self.obs_dir, world=self.ctx.process_count,
                    straggler_factor=cfg.obs.straggler_factor,
                    stale_after_s=cfg.obs.stale_after_s,
                    min_step_ms=cfg.obs.min_step_ms,
                    on_flag=cfg.obs.on_straggler,
                )
        # Live efficiency accounting (tpu_dp/obs/costs.py): rolling MFU /
        # goodput / step-time gauges per dispatched window, computed from
        # the per-program cost registry (`_register_program_costs`). The
        # peak-FLOPs denominator comes from the device kind (override:
        # obs.peak_flops_override); unknown kinds publish no MFU rather
        # than a wrong one.
        self._eff = None
        self._last_efficiency: dict | None = None
        if self.obs_mode != "off":
            from tpu_dp.obs.costs import EfficiencyMeter
            from tpu_dp.obs.costs import peak_flops as _peak_flops

            peak = (cfg.obs.peak_flops_override
                    or _peak_flops(jax.devices()[0].device_kind))
            self._eff = EfficiencyMeter(peak=peak,
                                        capacity=cfg.obs.span_capacity)
        # Flight recorder (tpu_dp/obs/flightrec.py): the always-on black
        # box, independent of train.obs — crash forensics must not require
        # live telemetry. The dump filename uses the STABLE launch rank so
        # an elastic regroup's dense-rank reassignment can never make two
        # processes overwrite each other's dump; the dump dir stays the
        # launch obs root for the same reason (obsctl globs it).
        self.flightrec = None
        from tpu_dp.obs import flightrec as _flightrec

        if cfg.obs.flightrec_capacity <= 0:
            # "Disabled" must mean disabled: the subsystems' module-level
            # record() calls become no-ops, not silent in-memory growth.
            _flightrec.recorder.disable()
        else:
            self.flightrec = _flightrec.recorder.configure(
                rank=self.stable_rank, dump_dir=self.obs_dir,
                capacity=cfg.obs.flightrec_capacity,
                fresh=True,  # a new Trainer is a new run's black box
                # A rejoined incarnation's dump must coexist with its
                # predecessor's departure dump (same stable rank): the
                # membership epoch it was admitted at tags the filename.
                tag=(f"me{self._join.record.epoch:04d}"
                     if self._join is not None else ""),
                run={
                    "model": cfg.model.name,
                    "world": self.ctx.process_count,
                    "devices": self.num_devices,
                    "global_batch": self.global_batch_size,
                    "elastic": bool(cfg.resilience.elastic),
                    "guard": self.guard_enabled,
                    "joined": self._join is not None,
                },
            )
        self._prom_failed = False  # one-shot prom-write failure warning
        # Step-ranged profiling (train.profile_steps=START:END): trace only
        # the window under investigation instead of the whole run.
        profile_range = parse_profile_steps(cfg.train.profile_steps)
        self._step_profiler = None
        if profile_range is not None:
            self._step_profiler = StepProfiler(
                cfg.train.profile_dir, *profile_range
            )
        # In-run comm/compute attribution (tpu_dp/obs/commprof.py,
        # docs/OBSERVABILITY.md "Comm/compute attribution"): capture
        # windows over obs.comm_profile_steps, auto-parsed into the
        # obs.comm_ms / obs.exposed_comm_ms / obs.overlap_frac gauges, a
        # comm_profile metrics event, and <obs dir>/comm_report.json —
        # with the trace-vs-static reconciliation against the DP304
        # fingerprint schedule.
        self._comm_profiler = None
        self._build_comm_profiler()

        # Guardrail run state: the rollback generation stamps every
        # metrics/quarantine record written after a rewind (post-hoc
        # tooling must never double-count replayed steps), and the evict
        # flag is the SDC audit's "this rank is corrupt — leave" handoff
        # to the elastic boundary.
        self._rollback_gen = 0
        self._guard_evict = False
        self._sdc_suspect_active = False  # suppresses snapshots (hooks.py)

        # Per-program FLOP costs for the live MFU gauges (and bench's
        # single source of truth) — registered after state creation so the
        # optional measured path can AOT-compile the real step.
        self._register_program_costs()

        # The step-lifecycle hook registry (tpu_dp/train/hooks.py): every
        # cross-cutting subsystem — guardrails, snapshots, fault injection,
        # heartbeats, profiling, the elastic/preemption boundary —
        # registers here instead of splicing into the hot loop.
        self._build_hooks()

        if self._join is not None:
            # The joiner's half of the regroup epilogue — observers homed
            # into the me-epoch, then the SAME verify + barrier sequence
            # the incumbents run at the tail of `_execute_regroup`, so the
            # grown mesh's first collectives are exactly matched.
            self._complete_join(self._join.record)
        elif cfg.train.verify_fingerprint:
            self._verify_step_fingerprint()

    def _adopt_join_resume(self, record) -> None:
        """Install the admitted membership record's resume truth.

        The joiner's state comes from the grow quiesce's final snapshot
        (the record's ``resume.snapshot_dir``) through the resharding
        `load_checkpoint` path — NEVER from this process's own disk,
        which belongs to a retired incarnation and may be arbitrarily
        stale. Step clock, consumption lineage, and the re-split tail all
        follow the record, exactly like a surviving incumbent's.
        """
        resume = dict(record.resume or {})
        snap = resume.get("snapshot_dir")
        if snap:
            try:
                self.state, _ = ckpt_lib.load_checkpoint(Path(snap),
                                                         self.state)
            except ckpt_lib.CorruptCheckpointError as e:
                # The agreed snapshot IS the joiner's only legal state
                # source (its own disk is a retired incarnation's) — a
                # corrupt one is a typed admission abort, never a silent
                # restore of different bytes than the incumbents hold.
                # The incumbents' bounded bootstrap timeout then re-forms
                # the world without us (`establish_fallback`).
                from tpu_dp.resilience import ElasticError

                raise ElasticError(
                    f"elastic join: admitted snapshot {snap} failed "
                    f"checksum verification — aborting the join ({e})"
                ) from e
            self.state = self._place_state(self.state)
        else:
            # Nothing on disk at the agreed resume point: the run itself
            # restarted from scratch at this epoch; the joiner does too.
            log0("elastic join: admitted record carries no snapshot — "
                 "starting from init like the incumbents")
        self._host_step = int(resume.get("global_step", 0))
        self._quant_pub_step = self._host_step
        epoch = int(resume.get("epoch", 0))
        lineage = resume.get("lineage") or []
        if lineage:
            has_tail = self._set_elastic_tail(epoch, lineage)
            self.start_epoch, self.start_step = (
                (epoch, 0) if has_tail else (epoch + 1, 0)
            )
        else:
            self.start_epoch = epoch
            self.start_step = int(resume.get("steps_done", 0))
        log0("elastic join: adopted resume — epoch %d step %d (global "
             "step %d, membership epoch %d, world %d)",
             self.start_epoch, self.start_step, self._host_step,
             record.epoch, record.world)

    def _complete_join(self, record) -> None:
        """Mirror of `_execute_regroup`'s epilogue on the joiner side."""
        from tpu_dp.obs import flightrec

        # The joiner's own act, in ITS ring — "elastic_join", the grow
        # twin of the leaver's "elastic_departure"; the membership record
        # tells the corresponding "rank_joined" (like "eviction"), so the
        # timeline never double-tells one admission under one kind.
        flightrec.record("elastic_join", step=self._host_step,
                         sid=self.stable_rank,
                         membership_epoch=record.epoch, world=record.world,
                         rank=self.ctx.process_index)
        self._rebuild_observers(record)
        if self._guard_hook is not None:
            # Fresh audit baseline at the adopted step: nothing older
            # than the admission can be this incarnation's clean point.
            self._guard_hook.on_regroup()
        if self.cfg.resilience.elastic_verify_fingerprint:
            self._verify_step_fingerprint(
                tag=f"train_step@me{record.epoch}w{record.world}"
            )
        dist.membership_barrier(
            "regroup_ready", record.epoch,
            timeout_s=self.cfg.resilience.regroup_timeout_s,
        )
        log0("elastic join: membership epoch %d live — joined at world "
             "%d as dense rank %d (stable id %d)",
             record.epoch, record.world, self.ctx.process_index,
             self.stable_rank)

    def _with_residuals(self, state):
        """Attach zero-initialized error-feedback residuals when the int8
        wire codec is on (`train.collective_dtype=int8`); identity — and
        an unchanged pytree — everywhere else."""
        if not self._quant_enabled:
            return state
        from tpu_dp.parallel import quant

        return state.replace(residuals=quant.init_residuals(
            state.params, dist.data_axis_size(self.mesh),
            self.cfg.train.quant_block_size,
            bucket_bytes=self._bucket_bytes,
        ))

    def _fresh_state(self) -> Any:
        """A from-scratch TrainState for the CURRENT topology/optimizer
        layout (+ codec residuals) — init, guard-rollback-to-nothing, and
        regroup reload targets all build states through here so none can
        forget a layout-bearing field."""
        rng = jax.random.PRNGKey(self.cfg.train.seed)
        return self._with_residuals(create_train_state(
            self._init_model, rng, self.train_ds.sample_input, self.optimizer
        ))

    def _publish_quant_counters(self, window, first_step: int) -> None:
        """Publish the int8 codec's health counts for one window.

        ``quant.overflow`` (non-finite blocks entering the codec) and
        ``quant.clip_blocks`` (rail-crowded blocks) accumulate into the
        counter registry, so schema-3 metrics records and `obsctl diff`
        carry them (docs/OBSERVABILITY.md). The values are already in the
        window's metrics — the fetch rides an EXISTING fence (the guard
        hook's health fetch, or obs=full's per-window scalar fetch); this
        method never adds a host sync of its own, which is why obs=basic
        guard-off runs publish nothing. The ``first_step`` marker dedupes
        the two call sites when both fences are live.
        """
        if not self._quant_enabled or first_step <= self._quant_pub_step:
            return
        self._quant_pub_step = first_step
        overflow = clip = 0
        for m in window:
            if "quant_overflow" not in m:
                return
            overflow += int(np.asarray(m["quant_overflow"]))
            clip += int(np.asarray(m["quant_clip"]))
        # inc(0) still creates the counter: a clean run stamps an explicit
        # quant.overflow=0 into its records — "0 overflows observed" is a
        # statement, absence is not.
        _obs_counters.inc("quant.overflow", overflow)
        _obs_counters.inc("quant.clip_blocks", clip)

    def _guarded(self, name: str, step_fn):
        """Wrap a compiled step in a RecompileGuard (train.recompile_guard).

        warmup_calls=1: every call, the first too, meets the state where
        the program returns it (`train_epoch` places it first,
        `_place_state`), so the first call makes the program's one cache
        entry and any growth after it is a real retrace. Without
        drop_remainder the epoch's final partial batch
        (padded, with a weight leaf) legitimately compiles another variant
        every epoch, so guarding would cry wolf — steps run unguarded
        there, like the eval step. No logger override: retrace divergence
        is inherently per-rank, so the guard's own stderr report must fire
        on whichever rank retraced, not only on process 0.
        """
        if self._guard is None or not self.cfg.data.drop_remainder:
            return step_fn
        from tpu_dp.analysis.recompile import RecompileGuard

        return RecompileGuard(
            step_fn, name=name, on_retrace=self._guard, warmup_calls=1,
        )

    def _build_pipelines(self) -> None:
        """(Re)build the input pipelines for the current mesh/topology.

        Called at construction and again by `_execute_regroup` after the
        mesh shrank — `DataPipeline` bakes the process count into its
        sampler and the mesh into its placement specs.
        """
        cfg = self.cfg
        self.train_pipe = DataPipeline(
            self.train_ds, cfg.data.batch_size, self.mesh,
            shuffle=cfg.data.shuffle, seed=cfg.train.seed,
            drop_remainder=cfg.data.drop_remainder, prefetch=cfg.data.prefetch,
            accum_steps=cfg.optim.grad_accum_steps,
            sync_placement=cfg.data.sync_placement,
        )
        self.test_pipe = DataPipeline(
            self.test_ds, cfg.data.batch_size, self.mesh,
            shuffle=False, seed=cfg.train.seed,
            drop_remainder=False, prefetch=cfg.data.prefetch,
            sync_placement=cfg.data.sync_placement,
        )

    def _build_training(self) -> None:
        """(Re)build optimizer layout + compiled programs for the mesh.

        World-sensitive throughout: the sharded optimizer pads its flat
        shards to the data-axis size, the step factories bake the mesh
        into their shardings, the auto window size keys off steps/epoch,
        and the resident-feed budget decision is per-topology. After a
        regroup everything here is stale and rebuilt; `load_checkpoint`
        reshards the persisted optimizer state onto the new layout.
        """
        cfg = self.cfg
        us = self.update_sharding
        augment_fn = self._augment_fn
        steps_per_epoch = len(self.train_pipe)
        total_steps = steps_per_epoch * cfg.train.epochs
        if cfg.optim.name == "sgd":
            self.optimizer = SGD(
                cfg.optim.momentum,
                cfg.optim.weight_decay,
                decay_exclude_bias_and_norm=(
                    cfg.optim.decay_exclude_bias_and_norm),
            )
        elif cfg.optim.name == "adamw":
            from tpu_dp.train.optim import AdamW

            self.optimizer = AdamW(
                cfg.optim.b1, cfg.optim.b2, cfg.optim.eps,
                weight_decay=cfg.optim.weight_decay,
                clip_norm=cfg.optim.clip_norm,
                decay_exclude_bias_and_norm=(
                    cfg.optim.decay_exclude_bias_and_norm),
            )
        else:
            raise ValueError(
                f"optim.name must be sgd|adamw, got {cfg.optim.name!r}")
        # Sharded mode wraps the optimizer so its state initializes — and
        # persists — sharded over the data axis; `make_train_step` then
        # states the collectives explicitly (reduce-scatter of grads,
        # all-gather of updated params). The replicated default keeps the
        # all-reduce GSPMD infers.
        if us == "sharded":
            from tpu_dp.train.optim import shard_optimizer

            self.optimizer = shard_optimizer(
                self.optimizer, dist.data_axis_size(self.mesh)
            )
        self.schedule = make_schedule(
            cfg.optim.schedule, cfg.optim.lr, total_steps,
            int(cfg.optim.warmup_epochs * steps_per_epoch), cfg.optim.final_lr,
        )
        # Every train program is `make_train_step` over these keywords;
        # `_program` adds the feed and the window's length.
        self._step_kwargs = dict(
            model=self.model, optimizer=self.optimizer, mesh=self.mesh,
            schedule=self.schedule,
            use_pallas_xent=cfg.train.pallas_xent,
            accum_steps=cfg.optim.grad_accum_steps,
            augment_fn=augment_fn,
            sentinel=self.guard_enabled,
            update_sharding=us,
            collective_dtype=cfg.train.collective_dtype or None,
            quant_block_size=cfg.train.quant_block_size,
            bucket_mb=cfg.train.bucket_mb,
        )
        # The single-batch program, whatever the feed: the DP304
        # fingerprint, the cost analysis and the comm profiler lower it.
        self.train_step = self._guarded(
            "train_step", make_train_step(**self._step_kwargs))
        eval_input_fn = None
        if getattr(augment_fn, "in_eval", False):
            from tpu_dp.data.noise import EVAL_STEP

            eval_input_fn = functools.partial(augment_fn, EVAL_STEP)
            eval_input_fn.phase = augment_fn.phase
        self.eval_step = make_eval_step(self.model, self.mesh,
                                        update_sharding=us,
                                        input_fn=eval_input_fn)
        spc = int(cfg.train.steps_per_call)
        if spc == 0:
            # Auto: windowed dispatch whenever the pipeline shape allows.
            # 24 steps/window matches the longrun recipe — big enough to
            # amortize a high-RTT dispatch, small enough to keep the
            # log cadence and HBM batch staging reasonable.
            spc = min(24, steps_per_epoch) if cfg.data.drop_remainder else 1
        self.steps_per_call = max(1, spc)

        # Device-resident feed (VERDICT r4 next-steps #3): stage the train
        # set in HBM once; per-window dispatch ships only indices. The
        # trajectory is identical to the streaming path (same sampler
        # order, same step body — equivalence-tested); what changes is the
        # host work per step: ~KB of int32 instead of a ~MB gather+copy.
        # Staging is lazy (`resident_train` property): eval-only or tooling
        # constructions never pay the host→HBM transfer (ADVICE r5).
        self._resident_train = None
        self._programs: dict[int, _Program] = {}
        mode = cfg.data.device_resident
        self._resident_enabled = mode == "on" or (
            mode == "auto"
            and cfg.data.drop_remainder
            and self.train_pipe.dataset_bytes() <= cfg.data.resident_max_bytes
        )

    def _build_hooks(self) -> None:
        """Register the step-lifecycle hooks, in load-bearing order.

        Guard first (a triggering window must not be snapshotted before
        its rollback picks a target), snapshot cadence, fault injection
        (a kill at step K lands after the step-K snapshot — the
        kill/resume contract), heartbeats (injected delays attribute to
        the step they fired at), profiling, and the elastic/preemption
        boundary last (it raises on a transition). Hooks whose subsystem
        is off no-op per call, so the registry survives a regroup's
        observer rebuild without being rebuilt itself.
        """
        from tpu_dp.train.hooks import (
            BoundaryHook,
            CommProfilerHook,
            FaultHook,
            GuardHook,
            HeartbeatHook,
            ProfilerHook,
            SnapshotHook,
        )

        from tpu_dp.train.hooks import FlightRecorderHook

        self._guard_hook = GuardHook(self) if self.guard_enabled else None
        hooks: list = []
        if self.flightrec is not None:
            # FIRST, before anything that can raise: the black box must
            # record the very boundary a guard halt / regroup / preempt
            # is about to raise out of — later hooks in a sweep are
            # skipped after a raise, and the fatal window is exactly the
            # one the postmortem needs. (The guard-before-snapshot
            # invariant below is untouched: this hook snapshots nothing.)
            hooks.append(FlightRecorderHook(self))
        if self._guard_hook is not None:
            hooks.append(self._guard_hook)
        hooks += [SnapshotHook(self), FaultHook(self), HeartbeatHook(self),
                  ProfilerHook(self), CommProfilerHook(self),
                  BoundaryHook(self)]
        self._hooks = hooks

    def add_hook(self, hook) -> None:
        """Register an outside `StepHook` after the trainer's own: it runs
        last in every sweep, and not at a boundary an earlier hook raises
        out of (a rollback, a regroup, a preemption)."""
        self._hooks.append(hook)

    @property
    def quarantine_path(self) -> Path:
        """The quarantine.jsonl sink (guard.quarantine_path, defaulting to
        <ckpt_dir>/quarantine.jsonl; the --guard CI lane archives it)."""
        return Path(
            self.cfg.guard.quarantine_path
            or Path(self.cfg.train.ckpt_dir) / "quarantine.jsonl"
        )

    def _ckpt_write_error(self, err: BaseException) -> None:
        """Degrade one failed epoch-checkpoint/export write: loud in the
        counters, the log and the black box — never fatal to the run
        (the snapshot cadence and older epoch saves still cover resume;
        docs/RESILIENCE.md "Storage faults")."""
        from tpu_dp.obs import flightrec

        _obs_counters.inc("ckpt.write_errors")
        flightrec.record("ckpt_write_error", step=self._host_step,
                         error=str(err)[:300])
        log0("epoch-checkpoint write failed (%s) — training continues; "
             "resume falls back to the newest earlier complete save", err)

    def _take_snapshot(self, epoch: int, steps_done: int,
                       wait: bool = False) -> bool:
        """One snapshot + the ``on_snapshot`` hook sweep (cadence,
        preemption final, and elastic quiesce final all route here so
        every registered hook sees every committed snapshot).

        Returns False when the write DEGRADED (disk full/flaky — already
        logged + counted by the snapshot manager): the hooks never see a
        snapshot that did not commit, and callers whose protocol depends
        on the commit (quiesce/preempt finals) get the honest verdict.
        With ``wait=True`` an async failure surfaces here as False too.
        """
        meta = self._snapshot_meta(epoch, steps_done)
        out = self.snap_mgr.snapshot(self.state, self._host_step, meta)
        if out is None:
            return False
        if wait:
            try:
                self.snap_mgr.wait()
            except (RuntimeError, OSError) as e:
                self.snap_mgr._record_write_error(self._host_step, e)
                return False
        for hook in self._hooks:
            hook.on_snapshot(epoch, steps_done, self._host_step, meta)
        return True

    def _inject_sdc(self, plan) -> None:
        """Apply an ``sdc:`` fault: flip one HIGH bit of the matching
        params leaves on THIS rank's replica (testing only).

        The honest simulation of silent data corruption: the local copy of
        a logically-replicated parameter silently diverges — no error, no
        NaN, just a replica whose forward pass (and gradient contribution)
        is wrong from here on. The flipped bit is the top exponent bit
        (bit 30 for f32), not a low mantissa bit: a low-bit flip of a
        zero-initialized leaf makes a denormal the very next (identical
        across replicas) update arithmetically absorbs, leaving nothing
        for the audit to catch — whereas the cross-replica delta of a
        high-bit flip survives identical additive updates exactly.
        ``leaf=`` globs over the "/"-joined leaf paths; default corrupts
        the first leaf.
        """
        import fnmatch

        from tpu_dp.resilience.guard import leaf_paths

        paths = leaf_paths(self.state.params)
        targets = (
            [p for p in paths if fnmatch.fnmatch(p, plan.leaf)]
            if plan.leaf else paths[:1]
        )
        if not targets:
            raise ValueError(
                f"sdc fault leaf={plan.leaf!r} matches no params leaf; "
                f"available: {paths[:8]}..."
            )
        log0("fault injection: sdc bit-flip on rank %d at step %d "
             "(leaves %s)", self.ctx.process_index, self._host_step, targets)
        flat, treedef = jax.tree_util.tree_flatten(self.state.params)
        new_flat = []
        for path, leaf in zip(paths, flat):
            if path in targets:
                host = np.asarray(leaf).copy()
                width = host.dtype.itemsize
                view = host.reshape(-1).view(
                    {1: np.uint8, 2: np.uint16, 4: np.uint32,
                     8: np.uint64}[width]
                )
                view[0] ^= np.asarray(1 << (8 * width - 2), view.dtype)
                # STRICTLY process-local rebuild: place the mutated host
                # copy onto each addressable device and reassemble the
                # logical array from the single-device pieces. A plain
                # `device_put(host, global_sharding)` can dispatch mesh
                # work the OTHER ranks never dispatch, desyncing the
                # collective stream — the injected "corruption" would then
                # crash the job instead of silently poisoning it, which is
                # the opposite of what SDC does.
                pieces = [
                    jax.device_put(host[s.index], s.device)
                    for s in leaf.addressable_shards
                ]
                leaf = jax.make_array_from_single_device_arrays(
                    leaf.shape, leaf.sharding, pieces
                )
            new_flat.append(leaf)
        self.state = self.state.replace(
            params=jax.tree_util.tree_unflatten(treedef, new_flat)
        )

    def _quarantine_saves_after(self, clean_step: int, reason: str) -> None:
        """Mark every complete save newer than ``clean_step`` untrusted
        (rank 0 — the save writer — only; `find_candidates` then skips
        them, so no rollback or ``--resume=auto`` lands on a save that may
        carry the corruption)."""
        from tpu_dp.resilience import find_candidates, quarantine_save_dir

        for source, step in find_candidates(
            self.cfg.train.ckpt_dir, self.snapshot_dir
        ):
            if step > int(clean_step):
                quarantine_save_dir(source, reason)
                log0("guard: quarantined save %s (step %d > last clean "
                     "audit %d)", source, step, clean_step)

    def _verify_step_fingerprint(self, tag: str = "train_step") -> None:
        """Cross-rank collective-schedule check at startup (dplint DP304).

        Every rank AOT-compiles the train step it is about to run, digests
        the ordered collective sequence + replica groups of the compiled
        module, and compares against rank 0's digest — a rank running a
        stale binary / different JAX build / diverged config fails here
        instead of deadlocking the slice at the first divergent collective.
        """
        from tpu_dp.analysis.hlo import program_fingerprint

        digest = program_fingerprint(self.train_step,
                                     self._step_arg_structs())
        dist.verify_collective_fingerprint(digest, tag=tag)
        log0("collective-schedule fingerprint (%s): %s", tag, digest[:16])

    def _step_arg_structs(self):
        """Abstract (state, batch[, guard_in]) args of the shipped per-step
        program — shared by the DP304 fingerprint check and the
        cost-analysis FLOPs measurement."""
        cfg = self.cfg
        gb = cfg.data.batch_size * self.ctx.process_count
        accum = cfg.optim.grad_accum_steps
        prefix = (accum,) if accum > 1 else ()
        batch = {
            k: jax.ShapeDtypeStruct(prefix + (gb, *v.shape[1:]), v.dtype)
            for k, v in self.train_ds.arrays.items()
        }
        args = (self.state, batch)
        if self.guard_enabled:
            from tpu_dp.train.step import guard_in_struct

            args = args + (guard_in_struct(),)
        return args

    def _register_program_costs(self) -> None:
        """Stamp this topology's per-step program cost into the registry.

        One optimizer step costs the same FLOPs whether it is dispatched
        per-step, windowed or resident, so one entry is registered under
        "train_step" and `_program` aliases each program's tag to it.
        Source is the analytic per-model estimate (`tpu_dp.obs.costs`);
        ``obs.measure_flops=true`` upgrades it to XLA's cost analysis of
        the real compiled step — the exact resolution order bench.py uses
        (docs/OBSERVABILITY.md "Efficiency accounting").
        """
        from tpu_dp.obs import costs

        per_chip = self.global_batch_size / max(1, self.num_devices)
        model = self.cfg.model.name
        cost = costs.registry.register_analytic("train_step", model,
                                                per_chip)
        if self.cfg.obs.measure_flops and self.obs_mode != "off":
            try:
                lowered = self.train_step.lower(*self._step_arg_structs())
                step_flops = costs.cost_analysis_flops(lowered.compile())
            except Exception:
                log0("obs.measure_flops: cost-analysis compile failed; "
                     "keeping the analytic estimate", exc_info=True)
                step_flops = None
            if step_flops:
                resolved, source, check = costs.resolve_flops_per_step(
                    None, step_flops, 1, per_chip,
                    costs.train_flops_per_image(model),
                )
                cost = costs.registry.register("train_step", resolved,
                                               source=source, check=check)
                log0("obs: measured step cost %.3g FLOPs/step/chip "
                     "(%s, check=%s)", resolved, source, check)
        if cost is not None:
            # The world-keyed alias records which mesh shape this cost
            # belongs to — after an elastic regroup the registry carries
            # one tag per world the run passed through, so post-hoc MFU
            # questions ("was the shrunk mesh efficient?") resolve per
            # shape instead of against whatever topology ended the run.
            costs.registry.alias(
                f"train_step@w{dist.data_axis_size(self.mesh)}", "train_step")
            from tpu_dp.obs.counters import counters as _c

            _c.gauge("obs.flops_per_step_per_chip",
                     cost.flops_per_step_per_chip)

    def _build_comm_profiler(self) -> None:
        """Construct the comm-attribution capture driver (rank 0 only).

        Mutually exclusive with the whole-run trace and the plain
        step-ranged profiler — `jax.profiler` sessions cannot nest, and
        the comm window exists precisely to replace an undirected trace.
        The reconciliation's expected schedule is the per-step train
        program's static collective schedule (a scanned multi-step
        window's loop body compiles the identical schedule, counted
        once); resident-feed windows dispatch a different program, so
        reconciliation is disabled there rather than wrong.
        """
        from tpu_dp.obs.commprof import (
            CommProfiler,
            parse_comm_profile_steps,
        )

        cfg = self.cfg
        spec = parse_comm_profile_steps(cfg.obs.comm_profile_steps)
        if spec is None:
            return
        if cfg.train.profile_steps or cfg.train.profile_dir:
            raise ValueError(
                "obs.comm_profile_steps cannot combine with "
                "train.profile_steps/train.profile_dir — jax.profiler "
                "sessions cannot nest, and the comm window replaces the "
                "undirected trace"
            )
        if self.ctx.process_index != 0:  # dplint: allow(DP101) host-only profiler
            return
        trace_dir = cfg.obs.comm_profile_dir or str(
            self.obs_dir / "commprof"
        )
        local_devices = [d for d in self.mesh.devices.flat
                         if d.process_index == self.ctx.process_index]
        expected_fn = None
        if not self._resident_enabled:
            # Precomputed EAGERLY (one AOT compile at startup, like
            # verify_fingerprint): resolving it lazily at the first
            # window boundary would bill seconds of compile time to that
            # step's data_wait span and crater its goodput record.
            from tpu_dp.obs.commprof import expected_schedule

            try:
                expected = expected_schedule(self.train_step,
                                             self._step_arg_structs())
                expected_fn = lambda: expected  # noqa: E731
            except Exception:
                log0("comm profile: expected-schedule compile failed; "
                     "reconciliation disabled", exc_info=True)
        else:
            log0("comm profile: device-resident feed active — the "
                 "fingerprint reconciliation is disabled (the resident "
                 "window is a different program); counts/time still "
                 "publish")
        wire_report = None
        if self.update_sharding == "sharded":
            from tpu_dp.parallel import quant

            wire_report = quant.wire_report(
                self.state.params, dist.data_axis_size(self.mesh),
                cfg.train.quant_block_size,
                bucket_bytes=self._bucket_bytes,
            )
        from tpu_dp.obs import chips

        try:
            ici = chips.ici_gbs(jax.devices()[0].device_kind)
        except Exception:
            ici = None
        self._comm_profiler = CommProfiler(
            trace_dir, spec,
            devices=len(local_devices) or 1,
            world=dist.data_axis_size(self.mesh),
            expected_fn=expected_fn,
            wire_report=wire_report,
            wire_dtype=cfg.train.collective_dtype or "",
            ici_gbs=ici,
            publish=self._publish_comm_report,
        )
        log0("comm profile: windows %r -> %s", cfg.obs.comm_profile_steps,
             trace_dir)

    def _publish_comm_report(self, report: dict, start: int, end: int,
                             trace_dir: str) -> None:
        """One captured window's breakdown -> metrics event + report file.

        The gauges were already set by the CommProfiler (they ride the
        next records' counter snapshots and the promfile); this stamps
        the schema-3 ``comm_profile`` event and rewrites
        ``<obs dir>/comm_report.json`` (newest window wins — the file is
        a gauge, the metrics stream the history).
        """
        from tpu_dp.obs.commprof import write_comm_report

        recon = report.get("reconciliation") or {}
        self._log_metrics({
            "event": "comm_profile",
            "start_step": start,
            "end_step": end,
            "comm_ms": report["comm_ms"],
            "exposed_comm_ms": report["exposed_comm_ms"],
            "overlap_frac": report["overlap_frac"],
            "compute_ms": report["compute_ms"],
            "reconciled": recon.get("ok"),
            "by_kind": {k: v["per_step"]
                        for k, v in report["by_kind"].items()},
            "trace_dir": trace_dir,
        })
        write_comm_report(self.obs_dir / "comm_report.json", report)
        self._write_prom()
        log0("comm profile [%d, %d): comm %.3f ms/step (exposed %.3f, "
             "overlap %s), compute %.3f ms/step%s — %s",
             start, end, report["comm_ms"], report["exposed_comm_ms"],
             report["overlap_frac"], report["compute_ms"],
             "" if not recon else (
                 ", schedule reconciled" if recon.get("ok")
                 else ", RECONCILIATION MISMATCH"),
             trace_dir)

    def _write_prom(self) -> None:
        """Atomically rewrite the Prometheus textfile (obs.prom_path).

        Multi-process runs suffix the stable rank so every rank's file
        can coexist in one scraped directory; failures warn once and
        never abort training (same contract as heartbeat writes).
        """
        path = self.cfg.obs.prom_path
        if not path:
            return
        from tpu_dp.obs.promfile import write_promfile

        out = Path(path)
        if self.ctx.process_count > 1:
            out = out.with_name(out.name + f".r{self.stable_rank}")
        try:
            write_promfile(out, labels={"rank": str(self.ctx.process_index)})
        except OSError:
            if not self._prom_failed:
                self._prom_failed = True
                log0("prometheus textfile write failed (suppressing "
                     "further warnings)", exc_info=True)

    def _load_data(self, cfg: Config) -> None:
        """Process 0 materializes the dataset first; the rest then read it.

        Fixes the reference's download race — every rank extracting into the
        shared `./data` dir concurrently (`cifar_example_ddp.py:67-68,73-74`,
        SURVEY.md §5 "Race detection").
        """

        def _load():
            load = functools.partial(
                load_dataset, cfg.data.dataset, cfg.data.root,
                allow_synthetic=cfg.data.allow_synthetic,
                seed=cfg.train.seed, seq_len=cfg.data.seq_len,
                vocab_size=cfg.model.num_classes,
            )
            return (
                load(train=True,
                     synthetic_num_examples=cfg.data.synthetic_train_size),
                load(train=False,
                     synthetic_num_examples=cfg.data.synthetic_test_size),
            )

        if self.ctx.process_count == 1 or self._join is not None:
            # A joiner must not run the materialization barrier: the
            # incumbents are mid-regroup (they will next meet it at the
            # DP304 verify / regroup_ready barrier, not here), and the
            # dataset already materialized at the original launch — the
            # shared filesystem elastic requires makes it readable now.
            self.train_ds, self.test_ds = _load()
            return
        from jax.experimental import multihost_utils

        # Host-only IO stagger: rank 0 downloads, the barrier sits OUTSIDE
        # both gates so every rank reaches it.
        if self.ctx.process_index == 0:  # dplint: allow(DP101)
            self.train_ds, self.test_ds = _load()
        multihost_utils.sync_global_devices("tpu_dp_data_materialized")
        if self.ctx.process_index != 0:  # dplint: allow(DP101)
            self.train_ds, self.test_ds = _load()

    def _segment_steps(self, done: int) -> int:
        """Steps of the CURRENT world's segment out of ``done`` cumulative
        epoch steps (the part not covered by `_epoch_lineage`)."""
        return int(done) - sum(int(s) for _, s in self._epoch_lineage)

    def _membership_meta(self, epoch: int, steps_done: int) -> dict | None:
        """Membership stamp for checkpoint/snapshot manifests (elastic).

        ``lineage`` describes the interrupted epoch's full consumption —
        prior segments plus the in-flight one — so any later reader
        (a rollback regroup, a fresh incarnation resuming into the tail)
        can reconstruct the exact remaining sample set from
        ``(seed, epoch, lineage)`` via `elastic_resplit`.
        """
        if self.elastic is None:
            return None
        rec = self.elastic.record
        return {
            "epoch": rec.epoch,
            "world": self.ctx.process_count,
            "members": list(rec.members),
            "lineage": [list(map(int, seg)) for seg in self._epoch_lineage]
            + [[self.ctx.process_count, self._segment_steps(steps_done)]],
        }

    def _set_elastic_tail(self, epoch: int, lineage, skip: int = 0) -> bool:
        """Install the re-split remainder of an interrupted epoch.

        Returns False when the lineage already covers the whole epoch
        (nothing remains for this world — the caller advances to the next
        epoch). ``skip`` fast-forwards within the tail (resuming a run
        that had already progressed past the re-split point).
        """
        from tpu_dp.data.sampler import ElasticTailSampler, elastic_resplit

        cfg = self.cfg
        lineage = [list(map(int, seg)) for seg in lineage]
        per_step = cfg.data.batch_size * cfg.optim.grad_accum_steps
        idx = elastic_resplit(
            len(self.train_ds), cfg.data.shuffle, cfg.train.seed, epoch,
            per_step, lineage,
            self.ctx.process_count, self.ctx.process_index,
        )
        steps = len(idx) // per_step
        if steps - int(skip) <= 0:
            # The lineage already covers the whole epoch: the caller
            # advances to the NEXT epoch, whose consumption history is
            # empty — keeping the old lineage installed would poison every
            # later snapshot manifest with negative segment counts.
            self._elastic_tail = None
            self._epoch_lineage = []
            return False
        self._epoch_lineage = lineage
        pipe = DataPipeline(
            self.train_ds, cfg.data.batch_size, self.mesh,
            shuffle=cfg.data.shuffle, seed=cfg.train.seed,
            drop_remainder=True, prefetch=cfg.data.prefetch,
            accum_steps=cfg.optim.grad_accum_steps,
            sampler=ElasticTailSampler(idx, epoch),
            sync_placement=cfg.data.sync_placement,
        )
        from types import SimpleNamespace

        self._elastic_tail = SimpleNamespace(
            epoch=int(epoch), pipe=pipe,
            base=sum(s for _, s in lineage), skip=int(skip),
        )
        log0(
            "elastic: epoch %d re-split over world %d — %d prior step(s) "
            "across %s, %d step(s) remain (resuming %d in)",
            epoch, self.ctx.process_count, self._elastic_tail.base,
            lineage, steps, skip,
        )
        return True

    def _resume_position(self, meta: dict) -> tuple[int, int]:
        """(start_epoch, start_step) a restored state's meta encodes.

        Epoch checkpoints record the *finished* epoch → resume at the next
        one, step 0. Snapshots record the mid-epoch position → resume the
        same epoch and fast-forward the sampler by ``steps_done`` (no batch
        replayed, none skipped). A snapshot taken at the exact epoch end
        normalizes to (epoch+1, 0).
        """
        if meta.get("kind") == "snapshot":
            epoch = int(meta.get("epoch", 0))
            step = int(meta.get("steps_done", 0))
            spe = len(self.train_pipe)
            if spe and step >= spe:
                return epoch + 1, 0
            return epoch, step
        return int(meta.get("epoch", -1)) + 1, 0

    def _maybe_resume(self) -> None:
        """Resume from the newest checkpoint OR snapshot, agreed across
        processes.

        Checkpoints/snapshots are written by process 0 only; on a pod each
        host has its own disk, so the resume decision and the restored
        state must come from process 0 (otherwise replicas desync: some
        resume, some start fresh). The newest complete save wins across
        both layouts, through the self-healing `resume_latest` loop — a
        torn or checksum-corrupt best candidate (the torn:/bitrot: chaos
        signature: a rank killed right after its snapshot committed, the
        disk having lied about the commit) is marked and the next-older
        complete save restores instead; the auto-restart must not die on
        the very artifact the crash mangled. A tree where EVERY candidate
        is unreadable degrades to a fresh start — the documented
        ``--resume=auto`` semantics ("continue when a usable save exists,
        start fresh otherwise"), loudly.
        """
        cfg = self.cfg
        from tpu_dp.resilience import resume_latest

        resume_dir = None
        if self.ctx.process_count == 1:
            try:
                self.state, meta, resume_dir = resume_latest(
                    self.state, cfg.train.ckpt_dir, self.snapshot_dir
                )
            except FileNotFoundError:
                return
            except RuntimeError:
                log0("resume: every candidate unreadable — starting "
                     "fresh (auto-resume semantics)", exc_info=True)
                return
            self.start_epoch, self.start_step = self._resume_position(meta)
        else:
            from jax.experimental import multihost_utils

            # Host-only checkpoint read; the broadcasts below are outside
            # the gate, reached by every rank.
            loaded, state = False, self.state
            pos = np.zeros(2, np.int32)
            if self.ctx.process_index == 0:  # dplint: allow(DP101)
                try:
                    state, meta, resume_dir = resume_latest(
                        self.state, cfg.train.ckpt_dir, self.snapshot_dir
                    )
                    pos = np.asarray(self._resume_position(meta), np.int32)
                    loaded = True
                except FileNotFoundError:
                    pass
                except RuntimeError:
                    log0("resume: every candidate unreadable — starting "
                         "fresh (auto-resume semantics)", exc_info=True)
            loaded0 = bool(
                int(multihost_utils.broadcast_one_to_all(np.int32(loaded)))
            )
            if not loaded0:
                return
            host_state = jax.tree_util.tree_map(np.asarray, state)
            self.state = multihost_utils.broadcast_one_to_all(host_state)
            pos = multihost_utils.broadcast_one_to_all(pos)
            self.start_epoch, self.start_step = int(pos[0]), int(pos[1])
            # Non-writer ranks take rank 0's LITERAL pick, not a local
            # re-derivation: a candidate rank 0 skipped as transiently
            # unreadable leaves no quarantine marker behind, so a local
            # `find_latest` could land on a different dir and install a
            # different membership-lineage tail (replayed/dropped
            # samples, cross-rank desync).
            buf = np.zeros(4096, np.uint8)
            if self.ctx.process_index == 0:  # dplint: allow(DP101)
                if resume_dir is not None:
                    raw = str(resume_dir).encode()[:4096]
                    buf[:len(raw)] = np.frombuffer(raw, np.uint8)
            raw = multihost_utils.broadcast_one_to_all(buf)
            raw = np.asarray(raw, np.uint8).tobytes().rstrip(b"\x00")
            if self.ctx.process_index != 0:  # dplint: allow(DP101)
                resume_dir = Path(raw.decode()) if raw else None
        if self.cfg.resilience.elastic:
            self._maybe_resume_into_tail(resume_dir)
        log0("resumed from %s at epoch %d step-in-epoch %d (global step %d)",
             resume_dir, self.start_epoch, self.start_step,
             int(self.state.step))

    def _maybe_resume_into_tail(self, resume_dir) -> None:
        """Honor a snapshot's membership lineage on a full restart.

        A snapshot taken after a mid-epoch regroup describes an epoch
        consumed across *several* world sizes; the plain
        `_resume_position` skip (one world, one stride) would replay and
        drop samples. Every rank reads the manifest itself — elastic runs
        require the checkpoint tree on a shared filesystem — and installs
        the re-split tail for whatever world this incarnation launched
        with (which may differ from the world that wrote the snapshot).
        """
        if resume_dir is None:
            # This rank's local view lacked the checkpoint rank 0 found —
            # a shared-filesystem violation elastic cannot survive later
            # anyway, but resume itself already restored via broadcast.
            log0("elastic: resume source not visible on this rank's "
                 "filesystem; lineage resume unavailable")
            return
        try:
            meta = json.loads((Path(resume_dir) / "meta.json").read_text())
        except (OSError, ValueError):
            return
        lineage = (meta.get("membership") or {}).get("lineage") or []
        if meta.get("kind") != "snapshot" or not lineage:
            return
        world = self.ctx.process_count
        if len(lineage) == 1 and int(lineage[0][0]) == world:
            return  # single-world epoch: the standard skip path is exact
        epoch = int(meta.get("epoch", 0))
        if int(lineage[-1][0]) == world:
            # The last segment ran at this very world: its re-split tail is
            # this incarnation's stream too — skip what it already did.
            prior, skip = lineage[:-1], int(lineage[-1][1])
        else:
            prior, skip = lineage, 0
        if self._set_elastic_tail(epoch, prior, skip=skip):
            self.start_epoch, self.start_step = epoch, 0
        else:
            self.start_epoch, self.start_step = epoch + 1, 0

    @property
    def resident_train(self):
        """The device-resident train set, staged on first access (or None).

        Lazy so a Trainer built for eval/tooling never pays the host→HBM
        transfer (ADVICE r5); `train_epoch` touches it on its first window.
        """
        if self._resident_enabled and self._resident_train is None:
            self._resident_train = self.train_pipe.resident_data()
        return self._resident_train

    @property
    def global_batch_size(self) -> int:
        """Logical per-step batch: per-process batch × processes (the
        reference's batch-4-per-rank × world accounting, SURVEY.md §2A)."""
        return (self.cfg.data.batch_size * self.ctx.process_count
                * self.cfg.optim.grad_accum_steps)

    def _program(self, n: int) -> _Program:
        """The train program that a dispatch of ``n`` steps calls (cached;
        an epoch uses at most two sizes: steps_per_call and 1). The feed is
        decided here and nowhere else: resident when the data set is
        staged on the device, else one placed batch or a window of them."""
        prog = self._programs.get(n)
        if prog is None:
            if self._resident_enabled:
                tag = f"resident_loop[w{n}]"
                run = self._guarded(tag, self._resident_loop(n))
            elif n == 1:
                tag, run = "train_step", self.train_step
            else:
                tag = "multi_step"
                run = self._guarded(tag, make_train_step(
                    **self._step_kwargs, feed="window", num_steps=n))
            if tag != "train_step":
                from tpu_dp.obs import costs

                # One optimizer step costs the same however it is fed.
                costs.registry.alias(tag, "train_step")
            prog = self._programs[n] = _Program(run, tag)
        return prog

    def _resident_loop(self, n: int):
        """The resident feed's program for a window of ``n`` steps. A
        method of its own because `benchmark/tests/test_correct.py`
        replaces it by this name to plant a fault under the timed path;
        the loop reaches it through `_program` alone."""
        return make_train_step(
            **self._step_kwargs, feed="resident", num_steps=n,
            sample_shapes=self.train_pipe.sample_shapes)

    def train_epoch(self, epoch: int, start_step: int = 0) -> dict[str, float]:
        """One epoch of training; ``start_step`` resumes it mid-way.

        ``start_step > 0`` (a snapshot resume) fast-forwards the sampler:
        the epoch's first ``start_step`` batches were already consumed by
        the run being resumed, so iteration starts at exactly the next one
        — no batch replayed, none skipped.
        """
        cfg = self.cfg
        if self._setup is not None and self._setup.name == "caller":
            self._advance_setup()
        # Elastic tail: after a mid-epoch regroup (or a restart into one),
        # the interrupted epoch's remaining samples come from the re-split
        # pipe; `done` stays epoch-cumulative across the world change so
        # snapshot metadata and the quiesce protocol keep one step clock.
        tail = self._elastic_tail
        if tail is not None and tail.epoch != epoch:
            tail = None
        pipe = tail.pipe if tail is not None else self.train_pipe
        base = tail.base if tail is not None else 0
        if tail is not None:
            start_step = tail.skip
        pipe.set_epoch(epoch)  # `cifar_example_ddp.py:92` parity
        gbs = self.global_batch_size
        # Local to the epoch: one that a hook raises out of (a rollback, a
        # regroup) leaves no kept array behind for its re-entry.
        sums = _EpochSums()
        # The newest dispatches' losses, oldest first: what the loop's
        # bound waits on.
        inflight = collections.deque()
        run_steps = 0  # steps since the last log line
        ep_steps, ep_count = 0, 0
        step_items = gbs * self._items_per_row
        i = start_step - 1
        done = base + start_step  # epoch steps completed (snapshot meta)
        self._epoch_done = done
        # Resident: indices in, and the staged data set goes with every
        # call, never re-crossing the host→device link.
        if self.resident_train is not None:
            staged, windows = (self.resident_train,), pipe.index_windows
        else:
            staged, windows = (), pipe.windows
        items = windows(self.steps_per_call, skip_steps=start_step)
        # Telemetry (train.obs != off): `spans.begin` ends one span and
        # opens the next, so the spans tile the iteration (obs/spans.py);
        # h2d (block on the placed batch) and device (a scalar fetch, the
        # `ThroughputMeter.mark()` fence discipline) are full only — the
        # only obs mode that adds a host sync, which is why it is opt-in.
        spans = self.spans
        obs_full = self.obs_mode == "full"
        # The moment the last epoch's fence returned, once: an epoch a
        # hook raised out of leaves none behind for its re-entry.
        fence_t, self._fence_t = self._fence_t, None
        first_of_epoch, last_rec = True, None
        if spans is not None:
            spans.abandon()
        from tpu_dp.train.hooks import StepEvent

        for hook in self._hooks:
            hook.on_epoch_start(epoch)
        # The state meets the first dispatch where the programs return it,
        # whatever a caller or a restore put in its place since the last
        # epoch, so each program is made once (`_place_state`). The state
        # the last epoch's loop left is the programs' own output, in place:
        # the epoch's gap pays no check for it.
        ref = self._epoch_state
        if ref is None or ref() is not self.state:
            self.state = self._place_state(self.state)
        it = iter(items)
        while True:
            if spans is not None:
                spans.begin("data_wait", step=self._host_step + 1)
            try:
                n, item = next(it)
            except StopIteration:
                break
            if spans is not None:
                spans.begin("pre_dispatch")
            for hook in self._hooks:
                hook.on_window_start(self._host_step + 1, n)
            # The sentinel's replicated input (guard on only): armed loss
            # cap, LR ease-in scale, and the nan/spike injection seam.
            guard_args = ()
            if self._guard_hook is not None:
                guard_args = (
                    self._guard_hook.guard_in(self._host_step + 1, n),
                )
            if spans is not None:
                if obs_full:
                    spans.begin("h2d")
                    jax.block_until_ready(item)
                # Asked on arrival, before the wait: how far ahead the host
                # got, which the wait then holds to the bound.
                self._inflight.before_dispatch(first_of_epoch)
                spans.begin("inflight_wait")
            # The loop's bound, behaviour and not telemetry (made at obs=off
            # too): block, with no launch and no transfer, until the dispatch
            # about to be made is at most the `MAX_INFLIGHT`th unfinished
            # (the device retires them in order, so the oldest says it). The
            # dispatch follows the wake at once: a thread that waits on the
            # same step (a hook's watcher) gets the interpreter while the
            # runtime launches, not after the loop's own work.
            if len(inflight) == MAX_INFLIGHT:
                inflight.popleft().block_until_ready()
            if spans is not None:
                spans.begin("dispatch")
            prog = self._program(n)
            self.state, out = prog.run(self.state, *staged, item,
                                       *guard_args)
            inflight.append(out["loss"])
            if spans is not None:
                last_rec = self._window_telemetry(
                    prog, n, out, fence_t if first_of_epoch else None)
                first_of_epoch = False
                spans.begin("accumulate")
            sums.keep(out, n)
            window = prog.steps(out, n)
            for _ in range(n):
                i += 1
                run_steps += 1
                ep_steps += 1
                ep_count += gbs
                self.meter.step(step_items)
                if i % cfg.train.log_every == cfg.train.log_every - 1:
                    # Reference print format (`cifar_example.py:85-86`); the
                    # fetch here is the only sync per log interval.
                    print0("[%d, %5d] loss: %.3f"
                           % (epoch + 1, i + 1, sums.running_loss(run_steps)))
                    run_steps = 0
                    if self.health is not None:
                        # Rank 0 reads every rank's heartbeat file at the
                        # log cadence (already a sync boundary): stragglers
                        # and stale/hung ranks get named while the run is
                        # still up, not in the postmortem. The hang-dump
                        # sentinel goes out BEFORE report() — on_flag=raise
                        # must not abort past the request that makes every
                        # still-stepping rank preserve its black box.
                        issues = self.health.check()
                        if self.flightrec is not None:
                            # Aimed at the dir the recorders POLL (the
                            # launch obs root) — after a regroup the
                            # monitor's own run dir is the re-homed
                            # me<E> dir nobody stats.
                            self.health.request_dump(
                                issues, dump_dir=self.flightrec.dump_dir)
                        self.health.report(issues)
                        self._suspect_from_health(issues)
                    self._write_prom()
            # The step-lifecycle hook sweep, once per dispatched window
            # (the host-side step boundary): guardrails, snapshot cadence,
            # fault injection, heartbeats, profiling, and the
            # elastic/preemption boundary, in the registered order
            # (`_build_hooks` — ordering is load-bearing). A hook may
            # raise the loop's control-flow exceptions (_RegroupSignal,
            # _GuardRollback, PreemptedError, DivergedError).
            done += n
            self._host_step += n
            self._epoch_done = done  # regroup attribution (fit's handler)
            if spans is not None:
                spans.begin("hooks")
            ev = StepEvent(epoch=epoch, done=done, n=n, window=window)
            for hook in self._hooks:
                hook.on_step_end(ev)
        self._epoch_state = weakref.ref(self.state)
        if last_rec is not None:
            spans.begin("epoch_fence", rec=last_rec)
        sums.fetch()  # the fence: the epoch's last step has finished
        if "counters" in sums.total:
            # Published where the epoch's loss is fetched: the same fence.
            totals = dict(zip(self.model.counter_names,
                              np.asarray(sums.total["counters"], np.float64)))
            for name, value in totals.items():
                _obs_counters.inc(name, float(value))
            # What `correct` is a share of, where the model counts it.
            ep_count = int(totals.get(
                getattr(self.model, "count_counter", None), ep_count))
        stats = {
            "loss": float(sums.total["loss"]) / ep_steps if ep_steps else 0.0,
            "accuracy": (float(sums.total["correct"]) / ep_count
                         if ep_count else 0.0),
        }
        if start_step or base:
            # A resumed (or regrouped) epoch's accumulators cover only its
            # post-resume tail; label the record so loss curves explain
            # their own discontinuity instead of faking full-epoch coverage.
            stats["resumed_at_step"] = base + start_step
        self.meter.mark()  # fence: epoch stats fetched, device drained
        if self._setup is not None:
            self._advance_setup()
        if last_rec is not None:
            self._fence_t = spans.end()
        return stats

    def _advance_setup(self) -> None:
        """Set-up's phases after construction: the caller's, from the return
        of `__init__` to the first `train_epoch`'s entry, then that epoch's,
        to the return of its fence. There the compile listener's set-up
        totals freeze with the train programs' jit cache entries (one a
        program used), and one line says where set-up went."""
        span = self._setup
        span.close()
        if span.name == "caller":
            self._setup = setup_span("first_epoch")
        else:
            self._setup = None
            self._compiles.freeze(step_entries=sum(
                p.run._cache_size() for p in self._programs.values()))

    def _publish_rate(self) -> None:
        """The meter's rate as a gauge, under the name of what it counts."""
        rate = round(self.meter.items_per_sec, 1)
        if self._items_per_row == 1:
            _obs_counters.gauge("throughput.images_per_sec", rate)
        else:
            _obs_counters.gauge("throughput.items_per_sec", rate)

    def _window_telemetry(self, prog: _Program, n: int, out,
                          fence_t: float | None) -> dict:
        """The ``device`` (full only) and ``telemetry`` spans of one
        dispatched window: the fence, the window's records, the efficiency
        gauges and, at full, the per-step `metrics.jsonl` lines. ``out`` is
        the metrics of ``prog``'s dispatch of ``n`` steps;
        ``fence_t`` is when the last epoch's fence returned, given with an
        epoch's first window. Returns the window's last record."""
        spans = self.spans
        obs_full = self.obs_mode == "full"
        dispatched = spans.begin("device" if obs_full else "telemetry")
        self._inflight.dispatched(out["loss"], n)
        if obs_full:
            # An honest fence: the whole dispatch retired on every device
            # (a fetch alone reads one shard of a replicated scalar, and a
            # hook that stops a profiler would cut the others' last
            # collective), then a fetch.
            jax.device_get(jax.block_until_ready(out)["loss"])
            self.meter.mark()  # the same fence feeds the meter
            spans.begin("telemetry")
            self._publish_rate()
            from tpu_dp.obs import update_device_memory_gauges

            update_device_memory_gauges()
        # Basic mode OMITS h2d/device rather than recording 0.0: absence
        # means "not measured" — a fake zero would render as "device took
        # 0 ms" in rollups and the Perfetto trace (same principle as the
        # absent memory gauges).
        held = spans.held
        wall_ms, data_wait_ms = sum(held.values()), held["data_wait"]
        new_recs = spans.open_window(n, gen=self._rollback_gen)
        if fence_t is not None:
            # On the first step's record alone, never spread over a
            # window: the device sat drained for all of it.
            new_recs[0]["spans"]["epoch_gap"] = (dispatched - fence_t) * 1e3
        eff = None
        if self._eff is not None:
            # Live efficiency gauges, per dispatched window: MFU from the
            # cost registry (absent when the program's cost or the chip's
            # peak is unknown — never a wrong number), goodput = 1 −
            # data_wait/window. Window wall time runs from the start of
            # data_wait: at obs=full it ends on the device fence (honest
            # device time); at basic on the dispatch's return, a dispatch
            # rate (documented in OBSERVABILITY.md).
            eff = self._eff.observe(prog.tag, n, wall_ms, data_wait_ms)
            self._last_efficiency = eff
            _obs_counters.gauge("obs.step_time_ms", eff["step_time_ms"])
            _obs_counters.gauge("obs.goodput", eff["goodput"])
            if "mfu" in eff:
                _obs_counters.gauge("obs.mfu", eff["mfu"])
        if obs_full:
            # Per-step metrics.jsonl records (schema 3): the spans ended
            # so far, the window's efficiency gauges, and a counter
            # snapshot — one line per optimizer step. The int8 codec's
            # overflow/clip counts publish first (riding this block's
            # existing fence) so the same window's records carry them.
            if self._quant_enabled:
                self._publish_quant_counters(
                    prog.steps(out, n), self._host_step + 1)
            snap = _obs_counters.snapshot()
            for r in new_recs:
                rec = {
                    "step": r["step"],
                    "ts": _iso_ts(r["ts"]),
                    "spans": {k: round(v, 3)
                              for k, v in r["spans"].items()},
                    "counters": snap,
                }
                if eff is not None:
                    rec["goodput"] = eff["goodput"]
                    if "mfu" in eff:
                        rec["mfu"] = eff["mfu"]
                self._log_metrics(rec)
        return new_recs[-1]

    def _snapshot_meta(self, epoch: int, steps_done: int) -> dict[str, Any]:
        """Snapshot metadata: the mid-epoch resume position + provenance.

        Elastic runs add the membership stamp — epoch, world, members and
        the interrupted epoch's consumption lineage — so a rollback
        regroup or a fresh incarnation can reconstruct the exact remaining
        sample set (`_membership_meta`).
        """
        meta = {
            "kind": "snapshot",
            "epoch": epoch,
            "steps_done": steps_done,
            "config": self.cfg.to_dict(),
            "seed": self.cfg.train.seed,
        }
        if self._rollback_gen:
            # A post-rollback save identifies its generation, so forensic
            # tooling can align it with the tombstoned metrics/quarantine
            # records of the pass it replaced.
            meta["rollback_generation"] = self._rollback_gen
        membership = self._membership_meta(epoch, steps_done)
        if membership is not None:
            meta["membership"] = membership
        return meta

    def _preempt_exit(self, epoch: int, steps_done: int) -> None:
        """The preemption contract: final snapshot → barrier → exit 143.

        The snapshot is joined (not just dispatched) before the barrier, so
        by the time any rank exits, rank 0's final state is committed and
        an auto-restart (`--resume=auto`) loses zero steps.
        """
        from tpu_dp.obs import flightrec
        from tpu_dp.resilience import PreemptedError

        flightrec.record("preempt_exit", step=self._host_step, epoch=epoch,
                         done=steps_done)
        log0("preemption: taking final snapshot at epoch %d step %d "
             "(global step %d)", epoch, steps_done, self._host_step)
        if not self._take_snapshot(epoch, steps_done, wait=True):
            # Degrade, still honor the 143 contract: the final write
            # failed (full/flaky disk — counted + in the black box), so
            # the auto-restart resumes from the newest EARLIER complete
            # save instead; dying with a disk error here would just turn
            # a bounded work loss into a supervisor-visible failure.
            log0("preemption: final snapshot FAILED — resume will fall "
                 "back to the newest earlier complete save")
        try:
            res = self.cfg.resilience
            dist.fault_tolerant_barrier(
                self.mesh, retries=res.max_retries,
                base_delay=res.retry_base_delay_s,
            )
        except Exception:
            # A half-dead slice must not block the survivors' clean exit —
            # the snapshot is already committed.
            log0("preemption barrier failed; exiting anyway", exc_info=True)
        raise PreemptedError(
            f"preempted at epoch {epoch}, step-in-epoch {steps_done} "
            f"(global step {self._host_step}); snapshot committed to "
            f"{self.snapshot_dir}"
        )

    # -- elastic world size (tpu_dp/resilience/elastic.py) ---------------

    def _suspect_from_health(self, issues) -> None:
        """Fold rank-0's hang detection into the membership ledger.

        A stale/missing heartbeat is the "peers observe it" detection path
        (docs/RESILIENCE.md failure matrix): rank 0 publishes the suspect,
        every member's next boundary poll sees it and joins a rollback
        quiesce. Stragglers are slow, not dead — never suspected.
        """
        if self.elastic is None:
            return
        for issue in issues:
            if issue.kind in ("stale", "missing"):
                self.elastic.mark_suspect(issue.rank, issue.describe())

    def _leave_requested(self) -> bool:
        """This rank was told to go: SIGTERM (elastic semantics), the
        ``leave:`` fault injection, or the SDC audit named it corrupt
        (`GuardHook._sdc_audit` — a replica holding divergent params must
        leave before it poisons another gradient reduction)."""
        return (
            (self.preempt is not None and self.preempt.requested)
            or (self.fault is not None and self.fault.leave_requested)
            or self._guard_evict
        )

    def _elastic_boundary(self, epoch: int, done: int) -> None:
        """Window-boundary elastic hook: detect, converge, hand over.

        Detection is one rate-limited ledger glob (plus the local leave
        flags). A triggered transition then converges WITHOUT stalling:
        this rank refreshes its check-in at every boundary and keeps
        stepping (a stopped member would wedge every peer's in-flight
        collective) until the published plan's stop threshold — the first
        boundary at or past it is the same global position on every member
        (identical boundary sequences). There rank 0 commits the final
        snapshot, the ledger barrier closes, and control leaves
        `train_epoch` — as `PreemptedError` on a departing rank,
        `_RegroupSignal` on a survivor.
        """
        plan = self._quiesce_plan
        if plan is None:
            el = self.elastic
            leaving = self._leave_requested()
            if not el.quiescing:
                trigger = el.poll(self._host_step, leave_requested=leaving)
                if trigger is None:
                    return
                log0("elastic: regroup trigger %r at epoch %d step %d "
                     "(global step %d)", trigger, epoch, done,
                     self._host_step)
                from tpu_dp.obs import flightrec

                flightrec.record("elastic_trigger", step=self._host_step,
                                 trigger=str(trigger), leaving=leaving)
                # Rollback flavor: a suspected-dead peer, or an SDC
                # eviction (the corrupt rank leaves AND everyone resumes
                # from a pre-corruption save — a graceful final snapshot
                # would persist the very state the audit condemned).
                self._q_flavor = (
                    "rollback" if trigger == "suspect" or self._guard_evict
                    else "graceful"
                )
            plan = el.quiesce_step(
                epoch, self._host_step, leaving=leaving,
                flavor=self._q_flavor, window=self.steps_per_call,
            )
            if plan is None:
                return  # keep stepping; the next boundary re-converges
            self._quiesce_plan = plan
        # A rollback plan finishes immediately only when members DEPARTED
        # (the mesh is already broken — further steps are impossible);
        # a live-membered rollback (SDC eviction) converges at the common
        # stop threshold like a graceful one — stopping this rank early
        # would wedge every still-stepping peer's in-flight collective.
        if (plan.flavor == "rollback" and plan.departed) \
                or self._host_step >= plan.stop_step:
            self._finish_quiesce(epoch, done, plan)

    def _finish_quiesce(self, epoch: int, done: int, plan) -> None:
        """The quiesce epilogue: final snapshot, barrier, hand-off."""
        from tpu_dp.resilience import ElasticError, PreemptedError

        if (plan.flavor == "rollback" and not plan.departed
                and not plan.leavers):
            # Symmetric twin of `_elastic_rollback`'s no-shrink guard: a
            # rollback plan in which every member is alive and staying
            # means some rank reported a NON-membership failure (OOM, a
            # bug). The reporting rank re-raises its original error; every
            # other member must fail fast too — regrouping to the full
            # original world would only hang in bootstrap waiting for the
            # rank that is busy dying.
            self._quiesce_plan = None
            raise ElasticError(
                f"rollback quiesce e{plan.epoch} carries no membership "
                f"change — a peer reported a non-membership failure "
                f"(see its log); refusing to regroup the same world"
            )

        if plan.flavor in ("graceful", "grow"):
            # The final snapshot at the agreed step — the regroup's resume
            # point, so the world change replays and drops nothing (for a
            # grow it is also the JOINER's state source). Joined (not just
            # dispatched) before the barrier ack, like the preemption
            # contract's. A failure here (a peer died between the plan and
            # the stop step, poisoning the device state this fetch
            # materializes) must not kill the regroup: the leader's
            # pre-publish validation sees the missing snapshot and falls
            # back to a rollback resume.
            try:
                committed = self._take_snapshot(epoch, done, wait=True)
            except Exception:
                committed = False
                log0("elastic: final snapshot fetch at step %d failed",
                     self._host_step, exc_info=True)
            if not committed:
                log0("elastic: final snapshot at step %d did not commit — "
                     "the regroup will resume from the newest complete one",
                     self._host_step)
        self.elastic.ack_and_await_quiesced(plan)
        self._quiesce_plan = None
        if self.elastic.sid in plan.leavers:
            self.elastic.confirm_left(done)
            _obs_counters.inc("elastic.departures")
            from tpu_dp.obs import flightrec

            flightrec.record("elastic_departure", step=self._host_step,
                             epoch=epoch, done=done, flavor=plan.flavor,
                             membership_epoch=plan.epoch)
            raise PreemptedError(
                f"elastic departure at epoch {epoch}, step-in-epoch {done} "
                f"(global step {self._host_step}); membership epoch "
                f"{plan.epoch} forms with {len(plan.survivors)} survivor(s)"
            )
        raise _RegroupSignal(epoch, done, plan)

    def _elastic_rollback(self, epoch: int, err: BaseException) -> None:
        """A collective died under us (peer gone, no goodbye): check in
        with rollback flavor — no further steps are possible on this mesh
        — and hand over to the regroup. Raises; never returns."""
        done = self._epoch_done
        log0("elastic: collective failure at epoch %d step %d (%s) — "
             "entering rollback regroup", epoch, done, err)
        if self._quiesce_plan is None:
            self._quiesce_plan = self.elastic.quiesce_blocking(
                epoch, self._host_step, leaving=False, flavor="rollback",
                window=self.steps_per_call,
            )
        elif self._quiesce_plan.flavor == "grow":
            # A member died while a GROW plan was already adopted. The
            # plan is immutable for this epoch (exclusive-create) and its
            # survivor set — every incumbent plus the joiner — now
            # contains a dead rank, so neither the grown bootstrap nor a
            # rollback re-form of that exact set can ever rendezvous
            # (and the bootstrap failure mode is a LOG(FATAL), not an
            # error). The explicit answer (docs/RESILIENCE.md failure
            # matrix): fail fast and typed; the supervisor's full-world
            # restart — which resumes from the newest snapshot at any
            # world — is the recovery.
            from tpu_dp.resilience import ElasticError

            plan_epoch = self._quiesce_plan.epoch
            self._quiesce_plan = None
            raise ElasticError(
                f"member failure while grow plan e{plan_epoch} was in "
                f"flight ({err}); the planned membership (incumbents + "
                f"joiner) is unsatisfiable with a dead member — restart "
                f"the world"
            ) from err
        elif self._quiesce_plan.flavor == "graceful":
            # A graceful plan was adopted, then the mesh died under it
            # (e.g. the announced leaver was hard-killed before the stop
            # step). The graceful epilogue's premises are gone — this
            # rank's state is mid-failed-window and the common stop step
            # is unreachable — so it downgrades locally to rollback
            # semantics (no final snapshot; resume from the newest
            # complete one). The published record stays canonical: the new
            # leader validates the graceful snapshot before publishing and
            # falls back to a rollback resume when it never landed.
            import dataclasses

            self._quiesce_plan = dataclasses.replace(
                self._quiesce_plan, flavor="rollback"
            )
        plan = self._quiesce_plan
        if not plan.departed and not plan.leavers:
            # Every member is alive and staying: the failure is NOT a
            # membership event (OOM, a bug, a transient local error) and
            # shrinking would change nothing — surface the original error
            # instead of regrouping in a loop on the same world.
            self._quiesce_plan = None
            raise err
        self._finish_quiesce(epoch, done, plan)

    def _rollback_resume(self) -> dict:
        """The rollback resume payload: newest complete readable save.

        Computed by the new leader (every survivor computes it, only the
        leader's lands in the record): the newest complete snapshot or
        epoch checkpoint, its manifest supplying the epoch position and
        consumption lineage. With nothing on disk the job restarts from
        scratch — still on the surviving world, still without an operator.
        """
        from tpu_dp.resilience import find_candidates

        for source, step in find_candidates(
            self.cfg.train.ckpt_dir, self.snapshot_dir
        ):
            try:
                meta = json.loads((source / "meta.json").read_text())
            except (OSError, ValueError):
                log0("elastic rollback: %s has unreadable meta; skipping",
                     source)
                continue
            if meta.get("kind") == "snapshot":
                lineage = (meta.get("membership") or {}).get("lineage") or []
                return {
                    "epoch": int(meta.get("epoch", 0)),
                    "steps_done": int(meta.get("steps_done", 0)),
                    "lineage": lineage,
                    "global_step": int(meta.get("global_step", max(step, 0))),
                    "snapshot_dir": str(source),
                }
            return {  # epoch checkpoint: clean next-epoch start
                "epoch": int(meta.get("epoch", -1)) + 1,
                "steps_done": 0, "lineage": [],
                "global_step": max(step, 0), "snapshot_dir": str(source),
            }
        return {"epoch": 0, "steps_done": 0, "lineage": [],
                "global_step": 0, "snapshot_dir": None}

    def _load_rollback_state(self, resume: dict, target
                             ) -> tuple[Any, dict]:
        """Restore ``resume["snapshot_dir"]`` with the self-healing
        corrupt-candidate fallback (docs/RESILIENCE.md "Storage faults").

        A candidate that fails its checksum manifest is MARKED corrupt
        (the same quarantine marker the SDC audit drops — `find_candidates`
        then skips it forever, on every rank) and the resume payload is
        recomputed over the remaining candidates. Deterministic across
        survivors: everyone reads the same shared tree, refuses the same
        bytes, and lands on the same next-older save. Returns
        ``(state_or_None, resume)`` — None state means no usable candidate
        survived (the caller starts fresh, like an empty disk).
        """
        from tpu_dp.resilience import quarantine_save_dir

        while resume.get("snapshot_dir"):
            source = Path(resume["snapshot_dir"])
            try:
                state, _ = ckpt_lib.load_checkpoint(source, target)
                return state, resume
            except ckpt_lib.CorruptCheckpointError as e:
                _obs_counters.inc("ckpt.corrupt_candidates")
                quarantine_save_dir(source, f"checksum refusal: {e}")
                from tpu_dp.obs import flightrec

                flightrec.record("ckpt_corrupt_fallback",
                                 step=self._host_step, dir=str(source),
                                 leaves=list(e.leaves)[:8])
                log0("rollback restore: %s failed checksum verification "
                     "(%s) — marked corrupt, falling back to the "
                     "next-older complete candidate", source, e)
                resume = self._rollback_resume()
        return None, resume

    def _execute_guard_rollback(self, sig: _GuardRollback) -> tuple[int, int]:
        """Rewind to the newest complete, non-quarantined save and replay.

        The guard's auto-rollback (guard.action=rollback): every rank
        reaches the identical decision at the identical boundary (the
        policy consumes replicated values), so the rewind needs no
        coordination beyond agreeing on the resume source — local
        `_rollback_resume` where the checkpoint tree is shared (elastic /
        single process), rank-0-decides + broadcast otherwise (each host
        has its own disk; only rank 0's saves exist). Returns the
        ``(epoch, start_step)`` to continue from; the rolled-back steps'
        records are tombstoned and every later record carries the bumped
        ``rollback_generation``.
        """
        from_step = self._host_step
        hook = self._guard_hook
        # Budget check first: past max_rollbacks without progress this
        # raises DivergedError — a deterministic divergence replays
        # identically and rolling back into it forever is a livelock.
        hook.policy.on_rollback()
        if self.fault is not None:
            # The guard hook raises before the fault hook's disarm runs at
            # this boundary; without this, the replay would re-arm the
            # injected nan/spike seam and re-poison the very step being
            # rewound — an injected fault fires once per run, period.
            self.fault.disarm_device(from_step)
        log0("guard: rolling back from step %d — %s", from_step,
             sig.trigger.reason)
        if self.elastic is not None or self.ctx.process_count == 1:
            state, resume = self._load_rollback_state(
                self._rollback_resume(), self.state
            )
            if state is not None:
                self.state = self._place_state(state)
            else:
                self.state = self._place_state(self._fresh_state())
        else:
            from jax.experimental import multihost_utils

            # Non-elastic multi-process: no shared-filesystem requirement,
            # so the resume decision AND the restored state come from the
            # save writer (rank 0), like `_maybe_resume`.
            if self.ctx.process_index == 0:  # dplint: allow(DP101)
                state, resume = self._load_rollback_state(
                    self._rollback_resume(), self.state
                )
                if state is None:
                    state = self._fresh_state()
                pos = np.asarray([resume["epoch"], resume["steps_done"],
                                  resume["global_step"]], np.int32)
            else:
                state, pos = self.state, np.zeros(3, np.int32)
            host_state = jax.tree_util.tree_map(np.asarray, state)
            self.state = self._place_state(
                multihost_utils.broadcast_one_to_all(host_state)
            )
            pos = multihost_utils.broadcast_one_to_all(pos)
            resume = {"epoch": int(pos[0]), "steps_done": int(pos[1]),
                      "global_step": int(pos[2]), "lineage": []}
        self._host_step = int(resume.get("global_step", 0))
        self._epoch_done = int(resume.get("steps_done", 0))

        epoch = int(resume.get("epoch", 0))
        lineage = resume.get("lineage") or []
        if lineage:
            # The save predates (or spans) an elastic re-split: reinstall
            # the interrupted epoch's tail exactly like a regroup resume.
            has_tail = self._set_elastic_tail(epoch, lineage)
            position = (epoch, 0) if has_tail else (epoch + 1, 0)
        else:
            self._epoch_lineage = []
            self._elastic_tail = None
            position = (epoch, int(resume.get("steps_done", 0)))

        # Rewind bookkeeping: the generation bump + tombstone make the
        # rolled-back records identifiable (metrics sink, quarantine log,
        # heartbeats), and the cadence markers re-arm below the old
        # high-water step so the replay is snapshotted/beaten too.
        self._rollback_gen += 1
        # Same rewind contract as the snapshot/heartbeat/audit markers: the
        # publish marker must drop below the replay window, or the replayed
        # steps' codec overflow/clip counts — exactly the corruption signal
        # that may have caused this rollback — would be silently dropped.
        self._quant_pub_step = self._host_step
        if self.ctx.process_index == 0:  # dplint: allow(DP101) host-only IO
            hook.log.tombstone(
                from_step=from_step, to_step=self._host_step,
                reason=sig.trigger.reason,
            )
        hook.log.generation = self._rollback_gen
        if self.heartbeat is not None:
            self.heartbeat.rewind(self._host_step)
        self.snap_mgr.rewind(self._host_step)
        hook.on_rollback_rewind(self._host_step)
        if self.elastic is not None:
            # Same rewind contract for the ledger-poll cadence: its
            # crossing marker would otherwise sit at the pre-rollback
            # high-water step and suppress peer/suspect detection for the
            # whole replay window.
            self.elastic.rewind_poll(self._host_step)
        hook.arm_lr_ease(self._host_step)
        _obs_counters.inc("guard.rollbacks")
        from tpu_dp.obs import flightrec

        flightrec.record("guard_rollback", step=self._host_step,
                         from_step=from_step, to_step=self._host_step,
                         gen=self._rollback_gen,
                         reason=sig.trigger.reason)
        if self.spans is not None:
            self.spans.record_window(
                self._host_step, 1,
                {"guard_rollback": 0.0},
                gen=self._rollback_gen,
            )
        self._log_metrics({
            "event": "guard_rollback",
            "from_step": from_step,
            "to_step": self._host_step,
            "trigger": sig.trigger.reason,
            "resume_epoch": position[0],
            "resume_step": position[1],
        })
        log0("guard: rolled back %d step(s) — resuming at epoch %d step %d "
             "(global step %d, generation %d)",
             from_step - self._host_step, position[0], position[1],
             self._host_step, self._rollback_gen)
        return position

    def _execute_regroup(self, sig: _RegroupSignal) -> tuple[int, int]:
        """Re-form the mesh — shrink to the survivors or GROW to admit a
        joiner — and continue the run.

        The tentpole sequence (docs/RESILIENCE.md "Elastic world size"):
        publish/adopt the new membership record → abandon the old
        distributed context and re-`initialize` at the new world →
        rebuild pipelines and compiled programs against the re-formed
        mesh → reload the agreed state through the resharding
        `load_checkpoint` → re-split the interrupted epoch over the new
        world → re-verify the DP304 collective fingerprint — all before
        the first post-regroup step. Returns the ``(epoch, start_step)``
        to continue from. A grow whose joiner dies mid-handshake falls
        back to re-forming at world N from the same snapshot (bounded by
        the bootstrap timeout; no work lost, no rollback).
        """
        t0 = time.perf_counter()
        plan = sig.plan
        cfg = self.cfg
        if plan.flavor in ("graceful", "grow"):
            snap_dir = Path(self.snapshot_dir) / f"step_{self._host_step:010d}"
            resume = {
                "epoch": sig.epoch,
                "steps_done": sig.done,
                "lineage": [list(map(int, seg))
                            for seg in self._epoch_lineage]
                + [[self.ctx.process_count, self._segment_steps(sig.done)]],
                "global_step": self._host_step,
                "snapshot_dir": str(snap_dir),
            }
            if (self.elastic.sid == min(plan.incumbents or plan.survivors)
                    and not (snap_dir / "state.msgpack").exists()):
                # The final snapshot never landed (the writer died inside
                # its grace window): the new leader validates BEFORE
                # publishing, so every survivor follows one canonical
                # fallback instead of racing the filesystem.
                log0("elastic: final snapshot %s missing — falling back to "
                     "rollback resume", snap_dir)
                resume = self._rollback_resume()
        else:
            resume = self._rollback_resume()
        record = self.elastic.establish(plan, resume)
        if record.joined:
            # The grow gate: commit to the grown bootstrap only for
            # joiners that are demonstrably alive NOW. A coordination
            # connect with an absent party is not a catchable failure —
            # the client LOG(FATAL)s on rendezvous timeout — so "is the
            # joiner coming?" is answered on the ledger first: each
            # admitted joiner signals join_ready immediately before its
            # own connect; one that never signals within the bounded wait
            # is presumed dead mid-handshake and the incumbents re-form
            # at world N from the same snapshot (no wedge, no rollback).
            # ONE decider: the incumbent leader runs the wait and
            # publishes the verdict; everyone else follows the ledger —
            # per-incumbent timers would split the camps on a joiner that
            # signals inside the timers' skew window.
            from tpu_dp.resilience import ElasticError

            joined_sids = [int(j["sid"]) for j in record.joined]
            incumbents = [m for m in record.members
                          if m not in joined_sids]
            if self.elastic.sid == min(incumbents):
                missing = self.elastic.ledger.await_join_ready(
                    record.epoch, joined_sids,
                    timeout_s=cfg.resilience.regroup_timeout_s,
                )
                self.elastic.ledger.publish_grow_verdict(
                    record.epoch, commit=not missing,
                    reason=("" if not missing else
                            f"no join_ready from {missing}"),
                )
                commit = not missing
            else:
                verdict = self.elastic.ledger.await_grow_verdict(
                    record.epoch,
                    timeout_s=2 * cfg.resilience.regroup_timeout_s,
                )
                if verdict is None:
                    raise ElasticError(
                        f"grow e{record.epoch}: no verdict from the "
                        f"incumbent leader within "
                        f"{2 * cfg.resilience.regroup_timeout_s:.0f}s "
                        f"(leader died mid-grow)"
                    )
                commit = bool(verdict.get("commit"))
            if not commit:
                log0("elastic: admitted joiner(s) never signalled ready "
                     "within %.0fs — aborting the grow, re-forming at "
                     "world %d", cfg.resilience.regroup_timeout_s,
                     record.world - len(record.joined))
                record = self.elastic.establish_fallback(
                    record, reason="join handshake timeout (grow aborted)"
                )
        resume = record.resume  # the leader's payload is canonical
        old_world = self.ctx.process_count
        old_rank = self.ctx.process_index

        # Teardown of the old world: drop every reference into the old
        # backend (resident dataset, compiled loops, live state — the
        # agreed state is about to be reloaded from disk), then abandon
        # the old distributed context (graveyard semantics, see
        # `dist.abandon_distributed`) and bootstrap the new epoch's.
        self._resident_train = None
        self._programs = {}
        self._elastic_tail = None
        self.state = None
        if self._comm_profiler is not None:
            # Stop an armed capture BEFORE the mesh it is tracing is torn
            # down; the driver itself is topology-bound (expected
            # schedule, wire report, local-device normalization) and is
            # rebuilt against the new mesh once the state is reloaded.
            self._comm_profiler.close()
            self._comm_profiler = None
        if self.heartbeat is not None:
            self.heartbeat.close()
        try:
            self.ctx = self.elastic.reinitialize(record)
        except Exception:
            if not record.joined:
                raise
            # The admitted joiner never completed the handshake (crashed
            # between its request and the coordination connect): every
            # incumbent's bootstrap timed out symmetrically. Re-form at
            # world N from the SAME resume payload — the grow quiesce's
            # snapshot — so the aborted grow costs the bounded timeout
            # and nothing else (no wedge, no rollback).
            log0("elastic: grow bootstrap at world %d failed — joiner "
                 "presumed dead mid-handshake; re-forming at world %d",
                 record.world, record.world - len(record.joined),
                 exc_info=True)
            record = self.elastic.establish_fallback(
                record, reason="join handshake timeout (grow aborted)"
            )
            resume = record.resume
            self.ctx = self.elastic.reinitialize(record)
        self.mesh = dist.data_mesh(
            num_devices=(
                self._devices_per_process * self.ctx.process_count
                if self._devices_per_process is not None else None
            )
        )
        self.num_devices = int(self.mesh.devices.size)
        self._build_pipelines()
        self._build_training()

        # Reload through the resharding path: the target carries the NEW
        # world's optimizer layout; `load_checkpoint` re-lays out the saved
        # opt state onto it value-preserving (docs/PERF.md). A corrupt
        # agreed snapshot (checksum refusal) self-heals onto the
        # next-older complete candidate — every survivor reads the same
        # shared tree, refuses the same bytes, and recomputes the same
        # fallback resume, so the regroup stays in lockstep.
        target = self._fresh_state()
        state, resume = self._load_rollback_state(resume, target)
        if state is not None:
            # The restore yields host numpy; place it under the step's own
            # shardings (a numpy leaf behind a cross-process sharding is
            # rejected at dispatch, and the sharded-update opt state must
            # land distributed, not replicated).
            self.state = self._place_state(state)
        else:
            self.state = self._place_state(target)  # nothing on disk: init
        self._host_step = int(resume.get("global_step", 0))
        # The codec-stats publish marker rewinds with the step clock (a
        # rollback-flavor regroup replays below the old high-water mark).
        self._quant_pub_step = self._host_step
        # Program costs are per-topology (per-chip batch changed with the
        # world): re-register so post-regroup MFU/goodput gauges divide by
        # THIS mesh's cost, and the world-keyed alias tags the new shape.
        self._register_program_costs()
        # Comm-attribution driver re-keyed to this topology: the grown or
        # shrunk program's collective schedule, THIS world's wire report,
        # and the new local device count (the state is already reloaded,
        # so the wire report sees the real params).
        self._build_comm_profiler()

        # Re-split the interrupted epoch over the survivors: every
        # remaining sample visited exactly once (graceful), or the
        # rollback point's remainder re-run on the new world.
        epoch = int(resume.get("epoch", 0))
        lineage = resume.get("lineage") or []
        if lineage:
            has_tail = self._set_elastic_tail(epoch, lineage)
            position = (epoch, 0) if has_tail else (epoch + 1, 0)
        else:
            self._epoch_lineage = []
            position = (epoch, int(resume.get("steps_done", 0)))

        # Telemetry re-homing: heartbeat files are per-rank-per-epoch (a
        # reassigned dense rank must not append into another rank's
        # stream), the monitor follows the new world/leader.
        self._rebuild_observers(record)
        # Guardrail re-homing: the compiled checksum and the audit
        # baseline are topology-bound; the eviction flag (if this rank
        # survived an SDC regroup it was not the suspect) resets.
        self._guard_evict = False
        self._sdc_suspect_active = False
        if self._guard_hook is not None:
            self._guard_hook.on_regroup()

        # DP304 on the re-formed mesh, before the first post-regroup step:
        # a member about to run a different collective schedule fails
        # here, not as a deadlock at step one. The tag is keyed by BOTH
        # the membership epoch and the new world size, so the fingerprint
        # artifact names which mesh shape each verification covered.
        if cfg.resilience.elastic_verify_fingerprint:
            self._verify_step_fingerprint(
                tag=f"train_step@me{record.epoch}w{record.world}"
            )
        dist.membership_barrier(
            "regroup_ready", record.epoch,
            timeout_s=cfg.resilience.regroup_timeout_s,
        )

        dt = time.perf_counter() - t0
        joined = [int(j["sid"]) for j in record.joined]
        _obs_counters.inc("elastic.regroups")
        _obs_counters.inc("elastic.lost_ranks",
                          max(0, old_world - record.world))
        _obs_counters.inc("elastic.joined_ranks",
                          max(0, record.world - old_world))
        _obs_counters.inc("elastic.regroup_s", dt)
        from tpu_dp.obs import flightrec

        flightrec.record(
            "elastic_regroup", step=self._host_step,
            membership_epoch=record.epoch, flavor=plan.flavor,
            world=record.world,
            departed=[d.get("sid") for d in record.departed],
            joined=joined,
            regroup_s=round(dt, 3),
        )
        if joined:
            # The grow gets its own marker next to the generic regroup:
            # "capacity came back" is the signal operators grep for.
            flightrec.record(
                "elastic_grow", step=self._host_step,
                membership_epoch=record.epoch, world=record.world,
                joined=joined,
            )
        if self.spans is not None:
            self.spans.record_window(
                self._host_step, 1, {"elastic_regroup": dt * 1e3},
                gen=self._rollback_gen,
            )
        self._log_metrics({
            "event": "elastic_regroup",
            "membership_epoch": record.epoch,
            "flavor": plan.flavor,
            "world": record.world,
            "departed": [d["sid"] for d in record.departed],
            "joined": joined,
            "resume_epoch": position[0],
            "resume_step": position[1] or (
                self._elastic_tail.base if self._elastic_tail else 0
            ),
            "regroup_s": round(dt, 3),
        })
        if joined:
            self._log_metrics({
                "event": "elastic_grow",
                "membership_epoch": record.epoch,
                "world": record.world,
                "joined": joined,
            })
        log0(
            "elastic: membership epoch %d live — world %d→%d (rank %d→%d), "
            "%s resume at epoch %d step %d, regroup took %.2fs",
            record.epoch, old_world, record.world, old_rank,
            self.ctx.process_index, plan.flavor, position[0],
            (self._elastic_tail.base if self._elastic_tail else position[1]),
            dt,
        )
        return position

    def _place_state(self, state):
        """The TrainState with every leaf committed where the train
        programs return it: the current mesh + update-sharding layout
        (`train/step._state_shardings`).

        The train programs' jit keys its cache on where each argument
        sits: a state that reaches the first dispatch anywhere else (a
        fresh state, uncommitted on one device; a caller's swapped-in
        leaves) makes every program again, traced, lowered and loaded, at
        its second dispatch. Only the leaves off their target move, and a
        state already in place is returned as it is: a check of each
        leaf's placement, no device work. A leaf on its one target device
        is re-placed in its own buffer (aliased), so the state is never
        held twice; host leaves (a restore's numpy) are transferred.
        """
        from tpu_dp.train.state import TrainState
        from tpu_dp.train.step import _state_shardings

        sh = _state_shardings(self.mesh, self.update_sharding)
        if isinstance(sh, TrainState):
            sh = TrainState(
                step=sh.step,
                params=jax.tree_util.tree_map(
                    lambda _: sh.params, state.params),
                opt_state=jax.tree_util.tree_map(
                    lambda _: sh.opt_state, state.opt_state),
                batch_stats=jax.tree_util.tree_map(
                    lambda _: sh.batch_stats, state.batch_stats),
                residuals=jax.tree_util.tree_map(
                    lambda _: sh.residuals, state.residuals),
            )
        else:
            sh = jax.tree_util.tree_map(lambda _: sh, state)
        leaves, treedef = jax.tree_util.tree_flatten(state)
        targets = treedef.flatten_up_to(sh)
        off = [i for i, (x, s) in enumerate(zip(leaves, targets))
               if not (isinstance(x, jax.Array) and x.committed
                       and x.sharding == s)]
        if not off:
            return state
        for i in off:
            leaves[i] = _place_leaf(leaves[i], targets[i])
        return treedef.unflatten(leaves)

    def _rebuild_observers(self, record) -> None:
        """Re-home heartbeats/health for a new membership epoch."""
        if self.obs_mode == "off":
            return
        from tpu_dp.obs import HealthMonitor, HeartbeatWriter

        run_dir = self.obs_dir / f"me{record.epoch:04d}"
        self.heartbeat = None
        self.health = None
        if self.cfg.obs.heartbeat_every_steps > 0:
            self.heartbeat = HeartbeatWriter(
                run_dir, rank=self.ctx.process_index,
                every_steps=self.cfg.obs.heartbeat_every_steps,
                me=record.epoch,
            )
        if self.heartbeat is not None and self.ctx.process_index == 0:  # dplint: allow(DP101) host-only monitor
            self.health = HealthMonitor(
                run_dir, world=self.ctx.process_count,
                straggler_factor=self.cfg.obs.straggler_factor,
                stale_after_s=self.cfg.obs.stale_after_s,
                min_step_ms=self.cfg.obs.min_step_ms,
                on_flag=self.cfg.obs.on_straggler,
            )
            # A freshly admitted joiner has no heartbeat history; this
            # monitor is constructed AT the admission, so its own startup
            # grace (`HealthMonitor._start`) is exactly the joiner's
            # admission grace — no per-rank bookkeeping needed here.
            # `HealthMonitor.admit` exists for monitors that OUTLIVE an
            # admission (out-of-band watchers over a growing world).
        if self._metrics_file is not None and self.ctx.process_index != 0:  # dplint: allow(DP101) host-only IO
            # A demoted rank 0 keeps the sink closed; the new rank 0's
            # `_log_metrics` appends to the same shared-filesystem file.
            try:
                self._metrics_file.close()
            except OSError:
                pass

    @property
    def metrics_path(self) -> Path:
        """The metrics.jsonl sink (train.metrics_path, defaulting to the
        historical <ckpt_dir>/metrics.jsonl)."""
        return Path(
            self.cfg.train.metrics_path
            or Path(self.cfg.train.ckpt_dir) / "metrics.jsonl"
        )

    def _log_metrics(self, record: dict) -> None:
        """Append a schema-3 JSON line to the metrics sink (process 0 only).

        Structured observability the reference lacks (its only records are
        stdout prints, SURVEY.md §5 "Metrics / logging"). Every record is
        stamped with a wall-clock ``ts`` (ISO-8601 UTC), the global
        optimizer ``step``, and ``schema: 3`` — schema 2 added the three
        stamps (v1 records carried none, so two runs' logs could not even
        be aligned in time); schema 3 adds the live efficiency fields
        (``mfu``/``goodput`` on per-step records, the ``efficiency``
        rollup on epoch records). Caller-provided fields win (per-step
        span records carry their own measured ts/step).
        """
        if self.ctx.process_index != 0:  # dplint: allow(DP101) host-only IO
            return
        rec = {"ts": _iso_ts(time.time()), "step": self._host_step,
               "schema": 3}
        if self._rollback_gen:
            # Rewind guard: post-rollback records name their generation so
            # consumers can drop the tombstoned (replayed-over) steps
            # instead of double-counting them (docs/OBSERVABILITY.md).
            rec["rollback_generation"] = self._rollback_gen
        if self.elastic is not None:
            # Every record carries the membership epoch, so a metrics
            # stream that spans a shrink explains its own discontinuities
            # (throughput, steps/epoch) without cross-referencing logs.
            rec["membership_epoch"] = self.elastic.record.epoch
        rec.update(record)
        if self._metrics_file is None or self._metrics_file.closed:
            # Opened once and held (append + flush per record): obs=full
            # writes one record per optimizer step, and a per-record
            # open/close on a shared filesystem would land in the very
            # step times being recorded. Closed in fit()'s finally;
            # post-fit records (the eval line) transparently reopen.
            path = self.metrics_path
            path.parent.mkdir(parents=True, exist_ok=True)
            self._metrics_file = open(path, "a")
        self._metrics_file.write(json.dumps(rec) + "\n")
        self._metrics_file.flush()

    def evaluate(self) -> dict[str, float]:
        """Global test accuracy/loss with ONE device→host fetch.

        The per-batch sums stay device-resident (each `+` is an async
        dispatch, never a sync) — on a high-RTT transport a per-batch
        `int(...)`/`float(...)` would make eval dispatch-bound, the exact
        host-sync pattern the train loop avoids.
        """
        correct = count = loss_sum = None
        for batch in self.test_pipe:
            m = self.eval_step(self.state, batch)
            batch_loss_sum = m["loss"] * m["count"]  # mean → sum, on device
            if correct is None:
                correct, count = m["correct"], m["count"]
                loss_sum = batch_loss_sum
            else:
                correct = correct + m["correct"]
                count = count + m["count"]
                loss_sum = loss_sum + batch_loss_sum
        if count is None:
            return {"accuracy": 0.0, "loss": 0.0}
        correct, count, loss_sum = jax.device_get((correct, count, loss_sum))
        n = max(int(count), 1)
        return {"accuracy": float(correct) / n, "loss": float(loss_sum) / n}

    def export_trace(self) -> Path | None:
        """Write the Perfetto/Chrome trace JSON for this rank's spans.

        Rank 0 only (one artifact per run dir; per-rank traces would need
        per-rank paths — `obs.export.merge_traces` exists for offline
        fan-in). Returns the path, or None when obs is off / not rank 0.
        """
        if self.spans is None:
            return None
        if self.ctx.process_index != 0:  # dplint: allow(DP101) host-only IO
            return None
        from tpu_dp.obs import export_perfetto

        path = Path(
            self.cfg.obs.perfetto_path
            or self.obs_dir / "trace.perfetto.json"
        )
        out = export_perfetto(
            path, self.spans.records(), rank=self.ctx.process_index,
            counter_points=[
                {"ts": time.time(), "counters": _obs_counters.snapshot()}
            ],
        )
        log0("perfetto trace: %s (%d step records) — open in "
             "chrome://tracing or ui.perfetto.dev", out, len(self.spans))
        return out

    def obs_summary(self) -> dict[str, Any] | None:
        """Span rollup + counter snapshot for end-of-run summaries
        (train.py's JSON line); None when obs is off."""
        if self.spans is None:
            return None
        out = {
            "mode": self.obs_mode,
            "spans_ms": self.spans.rollup(),
            "counters": _obs_counters.snapshot(),
        }
        if self._eff is not None:
            eff = self._eff.rollup()
            if eff is not None:
                out["efficiency"] = eff
        cp = self._comm_profiler
        if cp is not None and cp.last_report is not None:
            r = cp.last_report
            out["comm"] = {
                "windows": cp.reports,
                "comm_ms": r["comm_ms"],
                "exposed_comm_ms": r["exposed_comm_ms"],
                "overlap_frac": r["overlap_frac"],
                "reconciled": (r.get("reconciliation") or {}).get("ok"),
            }
        return out

    def fit(self) -> dict[str, Any]:
        cfg = self.cfg
        log0(
            "training %s on %s: %d device(s), %d process(es), "
            "global batch %d (%d/process), %d epochs",
            cfg.model.name, self.train_ds.name, self.num_devices,
            self.ctx.process_count, self.global_batch_size,
            cfg.data.batch_size, cfg.train.epochs,
        )
        t0 = time.perf_counter()
        history = []
        try:
            if self.preempt is not None:
                self.preempt.install()
            # Step-ranged profiling replaces the whole-run trace: both at
            # once would nest jax.profiler sessions (an error) and the
            # ranged trace exists precisely to avoid the whole-run one.
            whole_run_profile = (
                None if self._step_profiler is not None
                else cfg.train.profile_dir
            )
            with profile_trace(whole_run_profile):
                # Peer-death signatures that trigger a rollback regroup in
                # elastic mode (empty tuple otherwise: nothing is caught).
                fatal = (_elastic_fatal_errors()
                         if self.elastic is not None else ())
                epoch, start_step = self.start_epoch, self.start_step
                while epoch < cfg.train.epochs:
                    try:
                        stats = self.train_epoch(epoch, start_step=start_step)
                    except _RegroupSignal as sig:
                        # A survivor of a completed quiesce: shrink the
                        # mesh and continue — the regroup-aware fit loop.
                        epoch, start_step = self._execute_regroup(sig)
                        continue
                    except _GuardRollback as sig:
                        # The guard policy condemned the trajectory:
                        # rewind to the newest trusted save and replay
                        # (may raise DivergedError past the budget).
                        epoch, start_step = self._execute_guard_rollback(sig)
                        continue
                    except fatal as e:
                        try:
                            self._elastic_rollback(epoch, e)
                        except _RegroupSignal as sig:
                            epoch, start_step = self._execute_regroup(sig)
                        continue
                    history.append(stats)
                    log0("epoch %d: train loss %.4f acc %.4f (%.1f %s/s)",
                         epoch + 1, stats["loss"], stats["accuracy"],
                         self.meter.items_per_sec,
                         "img" if self._items_per_row == 1 else "items")
                    epoch_rec = {"epoch": epoch + 1, **stats,
                                 self._rate_name:
                                     round(self.meter.items_per_sec, 1)}
                    if self.spans is not None:
                        # Epoch rollup: span percentiles over the ring +
                        # the counter registry — the at-a-glance record
                        # (per-step records are obs=full only).
                        self._publish_rate()
                        from tpu_dp.obs import update_device_memory_gauges

                        update_device_memory_gauges()
                        epoch_rec["spans"] = self.spans.rollup()
                        if self._eff is not None:
                            # The window-level MFU/goodput/step-time
                            # rollup obsctl diff reads back post-hoc.
                            eff_roll = self._eff.rollup()
                            if eff_roll is not None:
                                epoch_rec["efficiency"] = eff_roll
                        epoch_rec["counters"] = _obs_counters.snapshot()
                    self._log_metrics(epoch_rec)
                    self._write_prom()
                    ckpt_meta = {"epoch": epoch, "config": cfg.to_dict(),
                                 "seed": cfg.train.seed}
                    if self.elastic is not None:
                        # Manifest stamp: which membership epoch/world
                        # finished this dataset epoch (no lineage — an
                        # epoch checkpoint resumes at a clean epoch start).
                        rec = self.elastic.record
                        ckpt_meta["membership"] = {
                            "epoch": rec.epoch,
                            "world": self.ctx.process_count,
                            "members": list(rec.members),
                        }
                    try:
                        self.ckpt_mgr.save(self.state, ckpt_meta)
                    except (RuntimeError, OSError) as e:
                        # Same degrade contract as the snapshot cadence
                        # (docs/RESILIENCE.md "Storage faults"): a full
                        # disk costs durability, loudly — never the run.
                        self._ckpt_write_error(e)
                    every = cfg.train.eval_every_epochs
                    if every and (epoch + 1) % every == 0:
                        ev = self.evaluate()
                        log0("epoch %d: eval loss %.4f acc %.4f",
                             epoch + 1, ev["loss"], ev["accuracy"])
                    if self.health is not None:
                        # End-of-epoch health pass: a rank that went quiet
                        # mid-epoch is flagged here even when log_every
                        # never fired (hang-dump sentinel first, as at the
                        # log boundary).
                        issues = self.health.check()
                        if self.flightrec is not None:
                            self.health.request_dump(
                                issues, dump_dir=self.flightrec.dump_dir)
                        self.health.report(issues)
                        self._suspect_from_health(issues)
                    # A signal that lands between epochs (or during eval)
                    # still gets the snapshot-and-exit-143 contract; in
                    # elastic mode the next epoch's first boundary runs
                    # the single-rank departure protocol instead.
                    if (self.elastic is None and self.preempt is not None
                            and self.preempt.requested):
                        self._preempt_exit(epoch + 1, 0)
                    # The epoch is fully consumed: its re-split tail and
                    # consumption lineage are history.
                    self._elastic_tail = None
                    self._epoch_lineage = []
                    epoch += 1
                    start_step = 0
        finally:
            # Join any in-flight async write even when training aborts —
            # the freshest checkpoint is exactly what a crash-restart
            # needs. A write failure surfacing here DEGRADES (counted +
            # logged + in the black box): it must neither mask a
            # propagating training error nor turn a completed run into a
            # disk-error exit (docs/RESILIENCE.md "Storage faults").
            import sys

            try:
                self.ckpt_mgr.close()
            except (RuntimeError, OSError) as e:
                # Degrade (counted, logged, in the black box): the run's
                # training outcome is already decided here, and replacing
                # it — or a propagating error — with a disk error would
                # turn "lost the LAST epoch checkpoint, resume falls back
                # one save" into a supervisor-visible job failure.
                self._ckpt_write_error(e)
            try:
                self.snap_mgr.close()
            except (RuntimeError, OSError):
                log0("snapshot write failed during teardown (degraded)",
                     exc_info=True)
            if self.preempt is not None:
                self.preempt.uninstall()
            # The black box, FIRST among the telemetry teardown: every
            # exit path out of fit() — clean, PreemptedError (SIGTERM via
            # the handler's boundary raise), DivergedError,
            # PeerFailedError, HealthError, any unhandled exception —
            # leaves flightrec_r<rank>.json, and it must land before any
            # later teardown step can fail and rob it. dump() never
            # raises (it logs); the reason names the in-flight exception
            # so obsctl's timeline shows WHY the rank exited.
            if self.flightrec is not None:
                exc = sys.exc_info()
                reason = "clean" if exc[0] is None else (
                    f"{exc[0].__name__}: {exc[1]}"[:500]
                )
                self.flightrec.record("exit", step=self._host_step,
                                      reason=reason)
                self.flightrec.dump(reason=reason)
            self._write_prom()
            # Telemetry teardown runs on EVERY exit path: a crashed or
            # preempted run is exactly when the trace matters. Each step
            # is guarded separately — a failed profiler flush (disk full,
            # deleted trace dir) must neither mask the original exception
            # nor rob the Perfetto export behind it.
            if self._step_profiler is not None:
                try:
                    self._step_profiler.close()
                except Exception:
                    log0("step-profiler close failed", exc_info=True)
            if self.heartbeat is not None:
                try:
                    self.heartbeat.close()
                except Exception:
                    log0("heartbeat close failed", exc_info=True)
            if self.spans is not None and len(self.spans):
                try:
                    self.export_trace()
                except Exception:
                    log0("perfetto export failed", exc_info=True)
            if self._metrics_file is not None:
                try:
                    self._metrics_file.close()
                except OSError:
                    log0("metrics sink close failed", exc_info=True)
            for hook in self._hooks:
                try:
                    hook.close()
                except Exception:
                    log0("step hook close failed", exc_info=True)
            if self.elastic is not None:
                # Every elastic exit path — leaver, survivor, crash — pins
                # the live coordination objects so interpreter teardown
                # can't abort a peer mid-exit (see `dist.park_distributed`).
                dist.park_distributed()
        print0("Finished Training")  # `cifar_example.py:90` parity
        wall = time.perf_counter() - t0

        # End-of-training weights export (`cifar_example.py:92-93` analogue).
        try:
            ckpt_lib.save_params(
                f"{cfg.train.ckpt_dir}/final_params.msgpack",
                self.state.params)
        except OSError as e:
            self._ckpt_write_error(e)

        result: dict[str, Any] = {
            "history": history,
            "wall_time_s": wall,
            self._rate_name: self.meter.items_per_sec,
        }
        if cfg.train.eval_at_end:
            eval_stats = self.evaluate()
            result["eval"] = eval_stats
            self._log_metrics({"eval": eval_stats})
            # Reference integer-percent print (`cifar_example.py:111-112`).
            print0("Accuracy of the network on the %d test %s: %d %%"
                   % (len(self.test_ds),
                      "images" if self._items_per_row == 1 else "rows",
                      int(100 * eval_stats["accuracy"])))
        return result


def run_elastic(cfg: Config) -> tuple[Trainer, dict[str, Any]]:
    """Drive `Trainer.fit` with the ``relaunch:`` fault's in-process rejoin.

    The deterministic twin of "the preempted rank comes back"
    (docs/RESILIENCE.md "Fault-injection spec"): a fired
    ``relaunch:step=K,rank=R`` departs exactly like ``leave:`` — the full
    single-rank elastic-departure protocol, survivors shrink to world N−1
    — but instead of surfacing the `PreemptedError` this driver builds a
    JOIN-mode Trainer in the same OS process (ledger discovery, fenced
    join request, admission, state restore from the agreed snapshot) and
    keeps training to completion at the regrown world. Every other
    `PreemptedError` propagates unchanged (train.py's exit-143 contract),
    as does a departure on a non-elastic run. One rejoin per call: a
    REAL preemption of the rejoined incarnation exits 143 like any other.
    """
    from tpu_dp.resilience import PreemptedError

    tr = Trainer(cfg)
    rejoined = False
    while True:
        try:
            return tr, tr.fit()
        except PreemptedError:
            fault = tr.fault
            if rejoined or not (
                fault is not None and fault.fired_kind("relaunch")
            ):
                raise
            rejoined = True
            log0("relaunch fault: departed at global step %d — rejoining "
                 "the run in-process", tr._host_step)
            import copy

            cfg2 = copy.deepcopy(cfg)
            cfg2.resilience.fault = ""
            cfg2.resilience.elastic_join = "always"
            cfg2.train.resume = False
            tr = Trainer(cfg2)
            if tr.fault is not None and tr.fault.has_kind("relaunch"):
                # A TPU_DP_FAULT env spec survives into the rejoined
                # incarnation (cfg2 cleared only the config field); the
                # plan already fired once this process — mark it spent so
                # the rejoined rank does not immediately leave again.
                tr.fault.spend("relaunch")
