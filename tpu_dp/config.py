"""Dataclass config system with CLI overrides and named presets.

The reference's "config system" is one dead argparse flag (`--world_size`,
overwritten from env — `/root/reference/cifar_example_ddp.py:139-144,44`) and
hardcoded hyperparameters: batch_size=4, lr=0.001/momentum=0.9, epochs=2,
normalize=0.5, ckpt path `./cifar_net.pth`, rendezvous `127.0.0.1:29500`
(SURVEY.md §5 "Config"). Here those hardcoded values are the *defaults* of a
structured config, and BASELINE.json's five target configs are presets, not
code forks. Override syntax: ``--section.field=value`` on any entry script.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Sequence


@dataclass
class ModelConfig:
    name: str = "net"  # net | resnet18 | resnet50
    num_classes: int | None = None  # None = derive from dataset; set = must agree
    bf16: bool = False  # compute dtype bfloat16 (params stay f32)
    # Pallas fused-conv stages for ResNet blocks (BasicBlock chains,
    # Bottleneck middle-3x3s): "" (off), "all",
    # or comma-separated stage indices, e.g. "0" = stage 1 only
    # (tpu_dp/ops/conv_block.py; checkpoint-compatible with the unfused model).
    # Note: fused activations round through bfloat16 inside the kernel, so
    # with bf16=false a fused model computes slightly below full-f32
    # precision (fused/unfused chains stay mutually consistent either way).
    fused_stages: str = ""
    fused_block_b: int = 0  # images per Pallas grid step; 0 = auto from VMEM budget
    fused_bwd: bool = False  # route the backward input-grad conv through it too
    # Shapes of the block-diffusion mixture-of-experts decoder
    # (model.name=sdar_moe; tpu_dp/models/sdar.py). The defaults are the
    # published widths of SDAR-30B-A3B-Chat and one chip's share of an
    # eight-chip expert group; num_classes is the vocabulary held here.
    # The image classifiers read none of them.
    hidden_size: int = 2048
    num_layers: int = 4
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 128  # the router's width
    experts_per_token: int = 8
    experts_held: int = 16  # this chip's experts of every layer ...
    share_index: int = 0  # ... from share_index * experts_held
    block_length: int = 4  # tokens a diffusion block
    rope_theta: float = 1e6


@dataclass
class DataConfig:
    dataset: str = "cifar10"  # cifar10 | cifar100 | synthetic | synthetic_tokens
    seq_len: int = 0  # tokens a row (synthetic_tokens; the vocabulary is model.num_classes)
    root: str = "./data"  # reference's `./data` (`cifar_example.py:44`)
    batch_size: int = 4  # per-process; reference parity (`cifar_example.py:42`)
    shuffle: bool = True
    augment: bool = False  # on-device random crop+flip (reference has none)
    drop_remainder: bool = True
    prefetch: int = 2  # replaces num_workers=2 (`cifar_example.py:47`)
    synthetic_train_size: int | None = None
    synthetic_test_size: int | None = None
    allow_synthetic: bool = True
    # Stage the whole train set in HBM once and feed the compiled window
    # only int32 indices (~KB/step instead of ~MB/step host gather +
    # transfer — the reference's per-step DataLoader feed,
    # `cifar_example.py:46-52`, replaced by on-device indexing).
    # "auto": on when the train set fits resident_max_bytes and
    # drop_remainder holds; "on"/"off" force it.
    device_resident: str = "auto"  # auto | on | off
    resident_max_bytes: int = 512 * 1024 * 1024
    # Per-batch host sync after device placement (debugging/measurement
    # escape hatch — the before-world of the async double-buffered feed).
    # Default off: `jax.device_put` is dispatch-only and the pipeline
    # keeps the next batch's placement in flight while the current one is
    # consumed, so the h2d copy overlaps the step (docs/PERF.md). True
    # blocks on every placed batch — the honest comparator the
    # `data_wait`-shrinks test measures against.
    sync_placement: bool = False


@dataclass
class OptimConfig:
    name: str = "sgd"  # sgd | adamw
    lr: float = 0.001  # `cifar_example.py:64`
    momentum: float = 0.9  # `cifar_example.py:64` (sgd)
    b1: float = 0.9  # adamw
    b2: float = 0.95  # adamw
    eps: float = 1e-8  # adamw
    clip_norm: float = 0.0  # adamw: global gradient norm, 0 = no clipping
    weight_decay: float = 0.0  # sgd: L2 into the gradient; adamw: decoupled
    # Exclude biases + norm scale/bias from decay (common high-accuracy
    # recipe); off by default for torch SGD parity (decays everything).
    decay_exclude_bias_and_norm: bool = False
    schedule: str = "constant"  # constant | cosine
    warmup_epochs: float = 0.0
    final_lr: float = 0.0
    grad_accum_steps: int = 1  # microbatches per optimizer update (lax.scan)


@dataclass
class TrainConfig:
    epochs: int = 2  # `cifar_example.py:66`
    log_every: int = 2000  # `cifar_example.py:84`
    seed: int = 0
    eval_at_end: bool = True
    eval_every_epochs: int = 0  # 0 = only at end
    # Steps fused into one device dispatch via the scanned loop (1 = the
    # plain per-step path; 0 = auto — up-to-24-step windows whenever the
    # pipeline shape allows). Amortizes launch latency; composes with
    # grad_accum_steps (scan-of-scan). The epoch's trailing steps run
    # per-step.
    steps_per_call: int = 1
    ckpt_dir: str = "./checkpoints"
    ckpt_keep: int = 3       # retained step checkpoints (0 = keep all)
    ckpt_async: bool = True  # write checkpoints on a worker thread
    resume: bool = False
    profile_dir: str | None = None  # enable jax.profiler traces when set
    pallas_xent: bool = False  # fused Pallas softmax-xent kernel (TPU)
    # RecompileGuard (tpu_dp/analysis/recompile.py): count retraces of the
    # compiled train-step programs after warmup — a silent recompile is a
    # step-time cliff. "warn" logs, "raise" aborts (CI), "off" disables.
    recompile_guard: str = "warn"
    # Cross-rank collective-schedule fingerprint check at startup (dplint
    # DP304): every rank digests the compiled train step's collective
    # sequence and compares against rank 0 — desynced binaries fail fast
    # instead of deadlocking mid-step. Costs one AOT compile; off by default.
    verify_fingerprint: bool = False
    # Cross-replica sharded weight update (Xu et al., PAPERS.md;
    # docs/PERF.md): "replicated" = gradient all-reduce + full update on
    # every replica (the default, GSPMD path); "sharded" = reduce-scatter
    # the grads, update 1/N of the params + optimizer state per replica,
    # all-gather the updated params (explicit-collectives shard_map path;
    # opt state persists sharded over the data axis).
    update_sharding: str = "replicated"
    # Wire format for the gradient reduce-scatter in sharded mode ("" =
    # reduce in the leaf dtype; "bf16" halves the bytes on the wire at
    # bf16 rounding cost; "int8" is the EQuARX-style blockwise-absmax-
    # scaled codec with error-feedback residuals — ~4x fewer wire bytes,
    # near-f32 short-run parity, docs/PERF.md "Quantized collectives").
    collective_dtype: str = ""
    # Scaling-block length of the int8 wire codec: one f32 scale per this
    # many elements. Smaller blocks track outliers tighter (better
    # accuracy) at more scale overhead on the wire; 256 ≈ 1.6% overhead.
    quant_block_size: int = 256
    # Bucketed, overlap-scheduled gradient collectives (sharded mode only;
    # docs/PERF.md "Overlapped collectives"): target MB of f32 gradient
    # payload per bucket. Leaves are bucketed in reverse production order
    # and each bucket's reduce-scatter (f32/bf16/int8 wire alike) issues
    # as soon as its gradients are produced, so XLA's latency-hiding
    # scheduler can overlap wire time with the remaining backward compute
    # (the reference DDP's ~25 MB gradient-hook buckets). 0 = off — the
    # historical single monolithic reduction. Error-feedback residuals
    # become per-bucket; dplint DP301 verifies the K-bucket schedule.
    bucket_mb: float = 0.0
    # Runtime telemetry (tpu_dp/obs/, docs/OBSERVABILITY.md). "off": the
    # hot loop is exactly the untelemetered path (benched within noise,
    # HLO identical). "basic": per-step data_wait/dispatch spans, counter
    # snapshots at log boundaries, cross-rank heartbeats — no added host
    # syncs. "full": adds the h2d and fence-to-fence device spans (one
    # device→host scalar fetch per window — honest per-step latency at a
    # measured pipelining cost) and per-step metrics.jsonl records.
    obs: str = "off"  # off | basic | full
    # metrics.jsonl sink ("" = <train.ckpt_dir>/metrics.jsonl).
    metrics_path: str = ""
    # Step-ranged profiling: "START:END" global steps traced to
    # train.profile_dir (which must be set) instead of the whole run.
    profile_steps: str = ""
    # Path of the tuned.json this run loaded via --profile ("" = none).
    # Informational: parse_cli records it after applying the profile so
    # checkpoint meta / flight-recorder dumps name the profile a run's
    # knobs came from. The knobs themselves land in their own fields.
    profile: str = ""


@dataclass
class ObsConfig:
    """Telemetry tuning (tpu_dp/obs/; enabled by ``train.obs``)."""

    # Shared telemetry dir ("" = <train.ckpt_dir>/obs): heartbeat files
    # land here (every rank writes its own; multi-host needs this on a
    # shared filesystem for cross-host aggregation) and the Perfetto
    # export defaults into it.
    run_dir: str = ""
    # Span ring-buffer length (per-step records kept for rollups/export).
    span_capacity: int = 4096
    # Heartbeat cadence in optimizer steps (crossing discipline, like
    # snapshots); 0 disables heartbeats while keeping spans/counters.
    heartbeat_every_steps: int = 1
    # Straggler threshold: flagged when a rank's step time exceeds this
    # factor x the cross-rank median at the same observation.
    straggler_factor: float = 3.0
    # Hang threshold: a heartbeat older than this is a stale/hung rank.
    stale_after_s: float = 60.0
    # Median floor (ms) for the straggler ratio denominator — µs-scale
    # smoke steps jitter past any factor; below this nothing is flagged.
    min_step_ms: float = 1.0
    # What rank 0 does when the monitor flags an issue: warn logs (and
    # keeps training), raise aborts — the CI / supervised-fleet mode.
    on_straggler: str = "warn"  # warn | raise
    # Perfetto trace output ("" = <run_dir>/trace.perfetto.json), written
    # by rank 0 at the end of fit().
    perfetto_path: str = ""
    # Flight recorder (tpu_dp/obs/flightrec.py): ring size of the always-on
    # structured-event black box, dumped to <run_dir>/flightrec_r<rank>.json
    # on every fit() exit path (clean, preempted, diverged, crashed) and on
    # a hang-dump request. 0 disables recording AND dumps. Independent of
    # train.obs — crash forensics must not require live telemetry on.
    flightrec_capacity: int = 2048
    # Prometheus text-format exporter ("" = off): the counter registry is
    # atomically rewritten to this path at log boundaries, epoch ends and
    # exit — a node scraper (textfile collector) picks it up; no HTTP
    # server. Multi-process runs suffix the file with .r<rank>.
    prom_path: str = ""
    # Peak FLOP/s override for MFU (0 = derive from the device kind via
    # tpu_dp.obs.costs.peak_flops; unknown kinds publish no MFU). Lets CPU
    # smokes and exotic chips get a defined utilization denominator.
    peak_flops_override: float = 0.0
    # AOT-compile the train step once at startup and register its XLA
    # cost-analysis FLOPs in the cost registry (exact MFU for any model,
    # at one extra compile); off = analytic per-model estimates only.
    measure_flops: bool = False
    # In-run comm/compute attribution (tpu_dp/obs/commprof.py,
    # docs/OBSERVABILITY.md "Comm/compute attribution"): "START:END"
    # captures one jax.profiler window over those global steps,
    # "every:N[:W]" a W-step window (default 1) at every N-step boundary.
    # Each captured window is auto-parsed into a per-collective
    # comm/compute/overlap breakdown, reconciled against the DP304
    # fingerprint schedule, and published as the obs.comm_ms /
    # obs.exposed_comm_ms / obs.overlap_frac gauges + a comm_profile
    # metrics event + <obs dir>/comm_report.json. Mutually exclusive
    # with train.profile_steps / train.profile_dir (jax.profiler
    # sessions cannot nest). Rank 0 only.
    comm_profile_steps: str = ""
    # Capture-window trace root ("" = <obs run dir>/commprof); each
    # window lands in its own w<START> subdir.
    comm_profile_dir: str = ""


@dataclass
class ResilienceConfig:
    """Preemption-aware fault tolerance (tpu_dp/resilience/, docs/RESILIENCE.md)."""

    # Async TrainState snapshot cadence in optimizer steps; 0 = off (the
    # per-epoch checkpoint in Trainer.fit still runs either way).
    snapshot_every_steps: int = 0
    snapshot_keep: int = 2       # retained step snapshots (GC'd beyond this)
    snapshot_dir: str = ""       # "" = <train.ckpt_dir>/snapshots
    # SIGTERM/SIGINT → final snapshot → barrier → exit 143 during fit().
    handle_signals: bool = True
    # Bounded exponential backoff for resilient collectives (ResilientRing).
    max_retries: int = 2
    retry_base_delay_s: float = 0.05
    # Deterministic fault injection spec (testing/chaos only; see
    # tpu_dp/resilience/faultinject.py), e.g. "kill:step=13,rank=1" or a
    # ';'-composed schedule "bitrot:step=4;spike:step=8,scale=1e6".
    fault: str = ""
    # Unified total-backoff budget (seconds) for shared-filesystem IO:
    # the membership ledger's jittered retries AND checkpoint/snapshot
    # writes derive their exponential schedule from this one knob
    # (tpu_dp/resilience/retry.py io_retry_schedule; default reproduces
    # the historical 0.1+0.2+0.4+0.8+1.6s ledger schedule). Exhaustion
    # stays typed: ledger writes raise ElasticError, snapshot writes
    # degrade (snapshot.write_errors) per docs/RESILIENCE.md.
    io_retry_s: float = 3.1
    # Elastic world size (tpu_dp/resilience/elastic.py, docs/RESILIENCE.md
    # "Elastic world size"): a preempted rank triggers a regroup onto the
    # survivors (shrink the mesh, reshard, re-split the epoch) instead of
    # ending the run. Requires data.drop_remainder and a shared filesystem
    # under train.ckpt_dir. SIGTERM then means "THIS rank leaves" rather
    # than "the whole job exits".
    elastic: bool = False
    # Membership-ledger directory ("" = <train.ckpt_dir>/membership).
    membership_dir: str = ""
    # Bound on every regroup phase (quiesce collection, epoch-record wait,
    # re-bootstrap): a member silent past this is declared departed.
    regroup_timeout_s: float = 60.0
    # Ledger-poll cadence in optimizer steps (crossing discipline, like
    # snapshots): how often a window boundary globs the membership dir.
    elastic_poll_every_steps: int = 1
    # Refuse to regroup below this world size (survivors raise instead).
    elastic_min_world: int = 1
    # Host the new leader advertises for the regrouped coordinator
    # ("" = keep loopback on single-host topologies, else hostname).
    elastic_coordinator_host: str = ""
    # Re-verify the DP304 collective-schedule fingerprint on the re-formed
    # mesh before the first post-regroup step (one AOT compile per regroup).
    elastic_verify_fingerprint: bool = True
    # Grow-flavor regroups (docs/RESILIENCE.md "Grow"): whether a starting
    # process tries to JOIN a live run through the membership ledger
    # instead of bootstrapping a fresh one. "auto": join when the newest
    # generation's current membership excludes this rank's stable id (the
    # relaunched-after-preemption signature); "always": join or die with a
    # typed error (the explicit supervisor relaunch command); "never":
    # classic bootstrap only.
    elastic_join: str = "auto"  # auto | always | never
    # Bound on the joiner's admission wait per attempt (0 = use
    # regroup_timeout_s). The member side bounds the handshake with
    # regroup_timeout_s either way — a half-dead joiner cannot wedge the
    # quiesce (its bootstrap times out and the incumbents re-form at
    # world N).
    elastic_join_timeout_s: float = 0.0
    # Refuse to grow beyond this world size (0 = unbounded): a join that
    # would exceed it is refused with a typed reason in the ledger.
    elastic_max_world: int = 0


@dataclass
class GuardConfig:
    """Training guardrails (tpu_dp/resilience/guard.py, docs/RESILIENCE.md
    "Guardrails"): on-device NaN/divergence sentinel, bad-batch quarantine,
    cross-replica SDC audit, auto-rollback."""

    # Master switch: compiles the sentinel (on-device health summary +
    # guarded update) into the step programs and runs the policy engine at
    # window boundaries. Off (default), every compiled program is
    # bit-for-bit the unguarded one (DP304 digests identical) and zero
    # host work is added.
    enabled: bool = False
    # Response to a triggered detector: "skip" quarantines the batch (the
    # update is withheld on-device — non-finite always, spiking when the
    # armed loss cap catches it — and the sampler schedule stays
    # exactly-once); "rollback" rewinds to the newest complete snapshot;
    # "halt" raises DivergedError (exit 65, distinct from the preemption
    # 143 so supervisors do NOT auto-restart into the same divergence);
    # "warn" records and keeps going.
    action: str = "skip"  # warn | skip | rollback | halt
    # Spike detector: robust z-score (|x - median| / (1.4826 * MAD)) on
    # loss and grad-norm over the trailing window of applied steps;
    # detection arms after spike_min_steps observations.
    spike_window: int = 64
    spike_z: float = 8.0
    spike_min_steps: int = 16
    # Under action=skip, also arm the on-device loss cap (median + z*MAD
    # from the previous window) so a spiking batch's update is withheld
    # inside the compiled step instead of detected after it applied.
    device_cap: bool = True
    # Consecutive rollbacks without progress past the previous high-water
    # step before the policy escalates to halt (a deterministic divergence
    # replays identically; rolling back into it forever is a livelock).
    max_rollbacks: int = 3
    # LR ease-in after a rollback: scale the scheduled LR from
    # lr_ease_start back to 1.0 linearly over lr_ease_steps replayed
    # steps (0 = replay at full LR).
    lr_ease_steps: int = 0
    lr_ease_start: float = 0.1
    # Cross-replica SDC audit cadence in optimizer steps (0 = off): params
    # bit-checksummed on-device and compared across ranks over the DP304
    # fingerprint transport; a mismatching rank is attributed by majority
    # vote (and, when resilience.elastic is on, evicted through the
    # membership ledger with a rollback resume past its corruption).
    sdc_every_steps: int = 0
    # Non-elastic response to an SDC mismatch: "halt" (default — corrupt
    # replicas poison every peer through the gradient collective) or
    # "warn" (record and keep going; for diagnosis only).
    sdc_action: str = "halt"  # warn | halt
    # quarantine.jsonl sink ("" = <train.ckpt_dir>/quarantine.jsonl).
    quarantine_path: str = ""


@dataclass
class ServeConfig:
    """Batched-inference serving (tpu_dp/serve/, docs/SERVING.md)."""

    # Padded batch-size ladder: every formed batch is zero-padded up to
    # one of these sizes, each with its own pre-compiled donated-buffer
    # forward — fixed shapes, so the RecompileGuard stays silent.
    buckets: str = "1,2,4,8,16,32"
    # Dynamic-batching latency cap: dispatch when the pending work fills
    # the largest bucket OR the oldest request has waited this long.
    max_wait_ms: float = 5.0
    # Queue bound (requests): past this depth `submit` sheds with reason
    # "queue_full" instead of converting overload into deadline misses —
    # lowest SLO class first (serve/queue.py).
    max_queue: int = 256
    # Per-request latency target; attainment (fraction of completed
    # requests within it) is reported from the obs spans.
    slo_ms: float = 50.0
    # Admission headroom: a request whose deadline budget is already below
    # this is shed immediately (reason "deadline") — it cannot be served
    # in time, so reject-now beats serve-late.
    shed_headroom_ms: float = 0.0
    # Heartbeat/span directory ("" = disabled): per-batch heartbeats land
    # here so serve stragglers are attributable with obs.HealthMonitor.
    # Single-engine only — the multi-replica tier uses run_dir below.
    obs_dir: str = ""
    # Replica fan-out (tpu_dp/serve/router.py): N ServeReplica workers
    # over disjoint device subsets behind one shared admission queue,
    # with heartbeat-derived health, failover, drain/rejoin and hot swap.
    replicas: int = 1
    # Serving artifact root ("" = disabled): per-replica heartbeats land
    # under <run_dir>/obs, the serving membership ledger under
    # <run_dir>/membership/serve — the tree `obsctl timeline` rebuilds
    # the drain → failover → swap story from.
    run_dir: str = ""
    # A replica whose heartbeat is older than this WHILE it holds an
    # in-flight batch is quarantined (the router stops feeding it) until
    # it beats again; a dead one fails over.
    stale_after_s: float = 2.0
    # Failover budget: how many times a dead replica's in-flight request
    # is retried on a survivor before shedding "replica_failed".
    max_retries: int = 1
    # Per-SLO-class latency targets, highest class (0) first, e.g.
    # "50,100,250" — classes beyond the list fall back to slo_ms.
    # Per-class attainment lands in the serve report and obsctl diff.
    class_slo_ms: str = ""
    # Per-class attainment floors, "0:0.9,1:0.5" — the serve CLI exits 1
    # when a listed class completes below its floor (chaos acceptance).
    class_floors: str = ""
    # Batch-ranged serving capture (the training comm-profile window's
    # serving twin): "START:END" batch indices traced to profile_dir by
    # each replica — per-bucket device time becomes xplane-inspectable
    # (python -m tpu_dp.obs.xplane) exactly like a training window.
    profile_batches: str = ""
    # Trace root for profile_batches ("" = required off); replicas write
    # into per-sid subdirs so fan-out captures never collide.
    profile_dir: str = ""


def parse_class_slo_ms(spec: str) -> dict[int, float]:
    """Parse `ServeConfig.class_slo_ms`: per-class targets, class 0 first."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    try:
        return {i: float(s) for i, s in enumerate(spec.split(","))}
    except ValueError:
        raise ValueError(
            f"class_slo_ms must be comma-separated milliseconds, got {spec!r}"
        ) from None


def parse_class_floors(spec: str) -> dict[int, float]:
    """Parse `ServeConfig.class_floors`: ``class:attainment`` pairs."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    out = {}
    for item in spec.split(","):
        cls, sep, floor = item.partition(":")
        try:
            if not sep:
                raise ValueError
            out[int(cls)] = float(floor)
        except ValueError:
            raise ValueError(
                f"class_floors must be class:attainment pairs, got {spec!r}"
            ) from None
    return out


@dataclass
class ParallelConfig:
    num_devices: int | None = None  # None = all visible devices
    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None


@dataclass
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    guard: GuardConfig = field(default_factory=GuardConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)

    def override(self, dotted: str, value: str) -> None:
        """Apply one ``section.field=value`` override, coercing to field type."""
        section_name, _, field_name = dotted.partition(".")
        if not field_name:
            raise ValueError(f"override {dotted!r} must be section.field")
        section = getattr(self, section_name)
        if not hasattr(section, field_name):
            raise ValueError(f"no field {field_name!r} in {section_name}")
        current = getattr(section, field_name)
        setattr(section, field_name, _coerce(value, current))

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "Config":
        """Rebuild a Config from `to_dict` output (e.g. checkpoint meta).

        Unknown sections/fields raise, and values are type-checked/coerced
        against the field defaults — a silently-dropped or silently-mistyped
        setting would make a "reproduced" run quietly diverge from the
        original (e.g. the string ``"false"`` loading as truthy).
        """
        cfg = cls()
        for section_name, fields in d.items():
            if not hasattr(cfg, section_name):
                raise ValueError(f"unknown config section {section_name!r}")
            if not isinstance(fields, dict):
                raise ValueError(
                    f"config section {section_name!r} must be an object, "
                    f"got {type(fields).__name__}"
                )
            section = getattr(cfg, section_name)
            for field_name, value in fields.items():
                if not hasattr(section, field_name):
                    raise ValueError(
                        f"unknown field {field_name!r} in {section_name}"
                    )
                current = getattr(section, field_name)
                if isinstance(value, str) and not isinstance(current, str) \
                        and current is not None:
                    value = _coerce(value, current)
                elif (isinstance(current, int) and not isinstance(current, bool)
                        and isinstance(value, float) and value.is_integer()):
                    value = int(value)  # JSON round-trips may float-ify ints
                _check_field_type(section_name, field_name, current, value)
                setattr(section, field_name, value)
        return cfg


def _check_field_type(section: str, name: str, current: Any, value: Any):
    """Reject mistyped config values (bool-for-int, list-for-scalar, ...).

    Defaults define the schema: a value must match its field's default type
    (int accepted where float is expected; fields defaulting to None accept
    any JSON scalar)."""
    where = f"{section}.{name}"
    if current is None or value is None:
        if isinstance(value, (dict, list)):
            raise ValueError(f"{where}: expected a scalar, got {value!r}")
        return
    if isinstance(current, bool) or isinstance(value, bool):
        if not (isinstance(current, bool) and isinstance(value, bool)):
            raise ValueError(f"{where}: expected {type(current).__name__}, "
                             f"got {value!r}")
        return
    if isinstance(current, int) and not isinstance(value, int):
        raise ValueError(f"{where}: expected int, got {value!r}")
    if isinstance(current, float) and not isinstance(value, (int, float)):
        raise ValueError(f"{where}: expected float, got {value!r}")
    if isinstance(current, str) and not isinstance(value, str):
        raise ValueError(f"{where}: expected str, got {value!r}")


def _coerce(value: str, current: Any):
    if isinstance(current, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(value)
    if isinstance(current, float):
        return float(value)
    if current is None:
        for cast in (int, float):
            try:
                return cast(value)
            except ValueError:
                pass
        return None if value.lower() in ("none", "null") else value
    return value


#: The coupled-knob regime one shared rule warns about (used verbatim by
#: the Trainer's config validation, the tune search space, and dplint
#: DP105 — three surfaces, ONE threshold definition).
COUPLING_BUCKET_MB = 4.0
COUPLING_QUANT_BLOCK = 256


def coupling_warning(bucket_mb, quant_block_size,
                     collective_dtype) -> str | None:
    """The bucket/quant coupling guard (docs/TUNE.md "Coupled knobs").

    ``train.bucket_mb`` and ``train.quant_block_size`` interact under the
    int8 codec: each bucket quantizes independently (per-bucket absmax
    scales and error-feedback residuals), so a large bucket quantized
    with large scaling blocks couples many MB of gradient payload to a
    few coarse scales — one outlier leaf in the bucket widens the scale
    for everything sharing its block, and the residual feedback that
    would absorb the rounding now spans the whole bucket. Measured as a
    quality cliff, not a perf cliff, which is exactly why a
    throughput-ranked tuner needs the warning: the fenced trial cannot
    see it. Returns the warning string, or None when the combination is
    fine.
    """
    try:
        bucket = float(bucket_mb or 0.0)
        block = int(quant_block_size or 0)
    except (TypeError, ValueError):
        return None
    if (str(collective_dtype) in ("int8", "i8")
            and bucket >= COUPLING_BUCKET_MB
            and block >= COUPLING_QUANT_BLOCK):
        return (
            f"train.bucket_mb={bucket:g} with "
            f"train.quant_block_size={block} under the int8 codec: "
            f"buckets >= {COUPLING_BUCKET_MB:g} MB quantized with blocks "
            f">= {COUPLING_QUANT_BLOCK} share coarse absmax scales across "
            f"a large payload (outlier-widened scales + bucket-wide "
            f"error feedback); shrink quant_block_size or bucket_mb "
            f"(docs/TUNE.md \"Coupled knobs\")"
        )
    return None


# BASELINE.json's five target configs as presets (SURVEY.md §6).
def _preset_reference_single() -> Config:
    """Config 1 analogue + exact reference parity: `Net`, batch 4, 2 epochs."""
    return Config()


def _preset_resnet18_cifar10() -> Config:
    """Config 1/2: ResNet-18 on CIFAR-10 (mesh size sets the parallelism)."""
    c = Config()
    c.model = ModelConfig(name="resnet18", num_classes=10)
    c.data.batch_size = 128
    c.optim = OptimConfig(lr=0.1, momentum=0.9, weight_decay=5e-4,
                          schedule="cosine", warmup_epochs=1.0)
    c.data.augment = True  # needed for the 93% top-1 north star
    c.train.epochs = 30
    return c


def _preset_resnet50_cifar100() -> Config:
    """Config 3: ResNet-50 on CIFAR-100."""
    c = _preset_resnet18_cifar10()
    c.model = ModelConfig(name="resnet50", num_classes=100)
    c.data.dataset = "cifar100"
    return c


def _preset_resnet18_8chip_gb1024() -> Config:
    """Config 4: 8-chip DP ResNet-18, global batch 1024."""
    c = _preset_resnet18_cifar10()
    c.data.batch_size = 1024  # global; sharded 128/chip over an 8-chip mesh
    c.optim.lr = 0.4  # linear-scaling rule vs batch-128 base 0.05/...
    c.optim.warmup_epochs = 5.0
    c.train.epochs = 50
    return c


def _preset_bf16_cosine_gb4096() -> Config:
    """Config 5: bf16 mixed precision + cosine LR, global batch 4096."""
    c = _preset_resnet18_8chip_gb1024()
    c.model.bf16 = True
    c.data.batch_size = 4096
    c.optim.lr = 1.6
    c.optim.warmup_epochs = 10.0
    c.train.epochs = 60
    return c


def _preset_sdar_30b_a3b_ep8() -> Config:
    """SDAR-30B-A3B-Chat's layer at its published widths, cut to one chip
    of an eight-chip expert group: 4 of 48 layers, 16 of 128 experts a
    layer, 18,992 of 151,936 vocabulary rows; rows of 4,096 tokens, AdamW.
    (`benchmark/configs/sdar-30b-a3b-ep8.json` states the cut.)"""
    c = Config()
    c.model = ModelConfig(name="sdar_moe", num_classes=18992, bf16=True)
    c.data.dataset = "synthetic_tokens"
    c.data.seq_len = 4096
    c.data.batch_size = 4
    c.data.synthetic_train_size = 128
    c.data.synthetic_test_size = 8
    c.optim = OptimConfig(name="adamw", lr=1e-4, b1=0.9, b2=0.95, eps=1e-8,
                          clip_norm=1.0, weight_decay=0.1,
                          decay_exclude_bias_and_norm=True)
    c.train.epochs = 4
    c.train.steps_per_call = 1
    return c


PRESETS = {
    "sdar_30b_a3b_ep8": _preset_sdar_30b_a3b_ep8,
    "reference": _preset_reference_single,
    "resnet18_cifar10": _preset_resnet18_cifar10,
    "resnet50_cifar100": _preset_resnet50_cifar100,
    "resnet18_8chip_gb1024": _preset_resnet18_8chip_gb1024,
    "bf16_cosine_gb4096": _preset_bf16_cosine_gb4096,
}


def parse_cli(argv: Sequence[str]) -> Config:
    """`--preset=name` / `--config=file.json`, then `--section.field=value`.

    ``--config`` loads a JSON config file — either a bare `to_dict` dump or
    checkpoint metadata (`meta.json`, whose ``config`` key is used), so a
    run is reproducible straight from its checkpoint:
    ``train.py --config=.../step_0000000042/meta.json --train.ckpt_dir=NEW``.
    The ``parallel`` section is *not* restored — coordinator address and
    process ids describe the original launch environment, not the
    experiment, and would hang or collide a new launch. Reproducing from
    checkpoint meta additionally requires an explicit
    ``--train.ckpt_dir``/``--train.resume`` decision: writing (and pruning)
    inside the source run's checkpoint directory would destroy the very
    checkpoints being reproduced.
    ``--preset``/``--config`` are mutually exclusive; overrides apply last.
    """
    cfg: Config | None = None
    from_meta = False
    profile_path = ""
    overrides: list[tuple[str, str]] = []
    for arg in argv:
        if not arg.startswith("--"):
            raise ValueError(f"unexpected argument {arg!r}")
        key, _, value = arg[2:].partition("=")
        if key in ("preset", "config") and cfg is not None:
            raise ValueError("give at most one of --preset / --config")
        if key == "profile":
            # --profile=tuned.json: a tpu_dp.tune profile overlay. Applied
            # BEFORE the override loop below, so any explicit
            # --section.field flag the user typed wins over the profile
            # (tuned defaults fill gaps; they never clobber intent).
            if not value:
                raise ValueError("--profile needs a tuned.json path")
            if profile_path:
                raise ValueError("give at most one --profile")
            profile_path = value
            continue
        if key == "preset":
            if value not in PRESETS:
                raise ValueError(
                    f"unknown preset {value!r}; available: {sorted(PRESETS)}"
                )
            cfg = PRESETS[value]()
        elif key == "config":
            import json
            from pathlib import Path

            payload = json.loads(Path(value).read_text())
            if "config" in payload and isinstance(payload["config"], dict):
                payload = payload["config"]  # checkpoint meta.json layout
                from_meta = True
            payload.pop("parallel", None)  # environment, not experiment
            cfg = Config.from_dict(payload)
        elif key == "resume":
            # `--resume=auto` (or bare `--resume`): continue from the newest
            # checkpoint/snapshot when one exists, start fresh otherwise —
            # the restart command an auto-restarting supervisor can always
            # pass (docs/RESILIENCE.md "Auto-resume").
            if value not in ("", "auto", "true", "1", "latest"):
                raise ValueError(
                    f"--resume takes auto|true|latest, got {value!r}"
                )
            overrides.append(("train.resume", "true"))
        else:
            overrides.append((key, value))
    resume_on = any(
        k == "train.resume" and v.lower() in ("1", "true", "yes", "on")
        for k, v in overrides
    )
    new_ckpt_dir = any(k == "train.ckpt_dir" for k, _ in overrides)
    if from_meta and not (new_ckpt_dir or resume_on):
        raise ValueError(
            "reproducing from checkpoint meta.json writes checkpoints; pass "
            "--train.ckpt_dir=<new dir> (fresh reproduction) or "
            "--train.resume=true (continue in place) explicitly"
        )
    cfg = cfg or Config()
    if profile_path:
        # Lazy import: tune.profile is stdlib-only, but config stays
        # importable even if the tune package is stripped from a deploy.
        from tpu_dp.tune.profile import apply_profile, load_profile

        profile = load_profile(profile_path)
        apply_profile(cfg, profile)
        cfg.train.profile = profile_path
        # Key enforcement (workload/mesh/backend) happens in the Trainer,
        # which can see the live mesh; parse_cli only guarantees the file
        # is a valid, untampered profile.
    for key, value in overrides:
        cfg.override(key, value)
    return cfg
