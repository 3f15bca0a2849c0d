"""Fault-tolerance suite (`tpu_dp/resilience/`, docs/RESILIENCE.md).

The headline property: a training run killed mid-epoch by deterministic
fault injection auto-resumes from its latest async snapshot and reaches
final params **bitwise-identical** to an uninterrupted run — proved both
in-process (SIGTERM preemption through `Trainer.fit`) and across real
process boundaries (`train.py` subprocesses: `os._exit(137)` kill, exit
143 preemption, `--resume=auto` restart). Around it, unit coverage of each
resilience piece: fault-spec parsing, snapshot cadence/double-buffering/GC,
retry backoff, typed peer failure, and the mid-epoch sampler fast-forward.

All CPU (`tests/conftest.py` forces the backend); spawned subprocesses run
a single virtual device so their trajectories are self-consistent.
"""

import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from tpu_dp.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from tpu_dp.resilience import (
    KILL_EXIT_CODE,
    PREEMPTED_EXIT_CODE,
    FaultInjector,
    FaultPlan,
    PeerFailedError,
    PreemptedError,
    PreemptionHandler,
    ResilientRing,
    SnapshotManager,
    backoff_delays,
    find_latest,
    resume_latest,
    retry_call,
)

pytestmark = pytest.mark.resilience


# --------------------------------------------------------------------------
# faultinject
# --------------------------------------------------------------------------

def test_fault_plan_parse():
    p = FaultPlan.parse("kill:step=13")
    assert (p.kind, p.step, p.rank) == ("kill", 13, -1)
    p = FaultPlan.parse("kill:step=13,rank=1")
    assert (p.kind, p.step, p.rank) == ("kill", 13, 1)
    p = FaultPlan.parse("delay:step=5,ms=250")
    assert (p.kind, p.step, p.delay_ms) == ("delay", 5, 250.0)
    assert FaultPlan.parse("") is None
    assert FaultPlan.parse("  ") is None
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("explode:step=1")
    with pytest.raises(ValueError, match="needs step"):
        FaultPlan.parse("kill:rank=1")
    with pytest.raises(ValueError, match="bad fault field"):
        FaultPlan.parse("kill:step=1,when=now")


def test_fault_injector_rank_filter_and_one_shot():
    # A kill plan for rank 1 must never fire on rank 0 (or this test dies).
    inj = FaultInjector(FaultPlan(kind="kill", step=0, rank=1), rank=0)
    inj.on_step(100)
    assert not inj.fired

    inj = FaultInjector(FaultPlan(kind="delay", step=5, delay_ms=1), rank=0)
    inj.on_step(4)
    assert not inj.fired  # boundary not reached
    inj.on_step(6)        # first boundary past step 5
    assert inj.fired
    inj.on_step(7)        # exactly once: no second fire
    assert inj.fired


def test_fault_injector_drop_arms_once():
    inj = FaultInjector(FaultPlan(kind="drop", step=1), rank=0)
    assert not inj.take_drop()
    inj.on_step(1)
    assert inj.take_drop()      # consume the armed drop
    assert not inj.take_drop()  # one-shot


def test_fault_injector_from_spec_env(monkeypatch):
    assert FaultInjector.from_spec("", rank=0) is None
    monkeypatch.setenv("TPU_DP_FAULT", "delay:step=3,ms=1")
    inj = FaultInjector.from_spec("", rank=2)
    assert inj is not None and inj.plan.kind == "delay" and inj.rank == 2


# --------------------------------------------------------------------------
# retry
# --------------------------------------------------------------------------

def test_backoff_delays_deterministic_and_capped():
    assert backoff_delays(4, 0.05, 2.0) == [0.05, 0.1, 0.2, 0.4]
    assert backoff_delays(8, 0.05, 2.0)[-1] == 2.0  # capped
    assert backoff_delays(0) == []


def test_retry_call_retries_then_succeeds():
    calls, slept = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert retry_call(flaky, retries=3, base_delay=0.05,
                      sleep=slept.append) == "ok"
    assert len(calls) == 3
    assert slept == [0.05, 0.1]  # deterministic schedule, no jitter


def test_retry_call_exhaustion_reraises_last():
    slept = []

    def dead():
        raise RuntimeError("peer gone")

    with pytest.raises(RuntimeError, match="peer gone"):
        retry_call(dead, retries=2, base_delay=0.01, sleep=slept.append)
    assert len(slept) == 2  # retries, not attempts


def test_retry_call_terminal_errors_propagate_immediately():
    calls = []

    def typed():
        calls.append(1)
        raise PeerFailedError("already attributed", rank=0, world=2)

    with pytest.raises(PeerFailedError):
        retry_call(typed, retries=5, sleep=lambda s: None)
    assert len(calls) == 1  # no re-wrapping of a terminal error

    def unexpected():
        calls.append(1)
        raise ValueError("not retryable")

    calls.clear()
    with pytest.raises(ValueError):
        retry_call(unexpected, retries=5, sleep=lambda s: None)
    assert len(calls) == 1


class _FakeRing:
    """hostlib.Ring stand-in: scriptable rendezvous/collective failures."""

    rendezvous_failures = 0
    collective_failures = 0
    instances = 0

    def __init__(self, host, base_port, rank, world, timeout_ms):
        type(self).instances += 1
        if type(self).rendezvous_failures > 0:
            type(self).rendezvous_failures -= 1
            raise RuntimeError("connection refused")
        self.calls = 0

    def allreduce(self, x):
        self.calls += 1
        if type(self).collective_failures > 0:
            type(self).collective_failures -= 1
            raise RuntimeError("recv failed: peer closed")
        return x

    def close(self):
        pass


@pytest.fixture()
def fake_ring(monkeypatch):
    from tpu_dp.ops.native import hostlib

    _FakeRing.rendezvous_failures = 0
    _FakeRing.collective_failures = 0
    _FakeRing.instances = 0
    monkeypatch.setattr(hostlib, "Ring", _FakeRing)
    return _FakeRing


def test_resilient_ring_retries_rendezvous(fake_ring):
    fake_ring.rendezvous_failures = 2  # ranks of a preempted pod restart late
    ring = ResilientRing("127.0.0.1", 9000, rank=0, world=2, retries=2,
                         base_delay=0.0)
    assert fake_ring.instances == 3
    ring.close()


def test_resilient_ring_rendezvous_exhaustion_is_typed(fake_ring):
    fake_ring.rendezvous_failures = 99
    with pytest.raises(PeerFailedError) as ei:
        ResilientRing("127.0.0.1", 9000, rank=0, world=2, retries=1,
                      base_delay=0.0)
    assert ei.value.rank == 0 and ei.value.world == 2
    assert ei.value.suspect_ranks == (1,)  # 2-rank ring: one neighbor


def test_resilient_ring_collective_retry_and_attribution(fake_ring):
    ring = ResilientRing("127.0.0.1", 9000, rank=1, world=4, retries=2,
                         base_delay=0.0)
    fake_ring.collective_failures = 1  # transient: retried, then succeeds
    assert ring.allreduce("payload") == "payload"

    fake_ring.collective_failures = 99  # persistent: typed terminal failure
    with pytest.raises(PeerFailedError) as ei:
        ring.allreduce("payload")
    assert ei.value.rank == 1 and ei.value.world == 4
    assert ei.value.suspect_ranks == (0, 2)  # the ring neighbors
    assert "allreduce" in str(ei.value)


def test_resilient_ring_injected_drop_is_retried(fake_ring):
    inj = FaultInjector(FaultPlan(kind="drop", step=1), rank=0)
    inj.on_step(1)  # arm the one-shot drop
    ring = ResilientRing("127.0.0.1", 9000, rank=0, world=2, retries=2,
                         base_delay=0.0, injector=inj)
    assert ring.allreduce("x") == "x"
    # First attempt was dropped before reaching the transport; the retry
    # went through — exactly one real collective call.
    assert ring._ring.calls == 1


def test_fault_tolerant_barrier(mesh8, monkeypatch):
    from tpu_dp.parallel import dist

    dist.fault_tolerant_barrier(mesh8)  # healthy mesh: plain success

    def broken(mesh=None):
        raise RuntimeError("coordination service unavailable")

    monkeypatch.setattr(dist, "barrier", broken)
    with pytest.raises(PeerFailedError) as ei:
        dist.fault_tolerant_barrier(mesh8, retries=1, base_delay=0.0)
    assert ei.value.rank == 0


# --------------------------------------------------------------------------
# snapshot
# --------------------------------------------------------------------------

def _state(v: float):
    return {"w": np.full((4, 4), v, np.float32),
            "m": np.full((4, 4), -v, np.float32)}


def test_snapshot_cadence_crossing_semantics(tmp_path):
    snap = SnapshotManager(tmp_path, every_steps=50)
    assert not snap.due(49)
    assert snap.due(50)
    assert snap.due(72)  # multi-step windows: boundary crossing, not equality
    snap.snapshot(_state(1.0), 72)
    assert not snap.due(99)   # still inside the same cadence interval
    assert snap.due(100)
    snap.close()

    off = SnapshotManager(tmp_path / "off", every_steps=0)
    assert not off.due(10_000)  # cadence off...
    assert off.maybe(_state(1.0), 10_000) is None
    assert off.snapshot(_state(1.0), 7) is not None  # ...explicit still works
    off.close()


def test_snapshot_double_buffer_isolation_and_gc(tmp_path):
    src = _state(1.0)
    with SnapshotManager(tmp_path, every_steps=1, keep=2) as snap:
        snap.snapshot(src, 1)
        src["w"][:] = 2.0  # mutate AFTER the snapshot: buffer must not alias
        snap.snapshot(src, 2)
        snap.wait()
        s1, _ = load_checkpoint(tmp_path / "step_0000000001", _state(0.0))
        s2, meta2 = load_checkpoint(tmp_path / "step_0000000002", _state(0.0))
        assert s1["w"][0, 0] == 1.0  # pre-mutation value: a real copy
        assert s2["w"][0, 0] == 2.0
        assert meta2["kind"] == "snapshot" and meta2["global_step"] == 2

        # Retention: keep=2 prunes the oldest after a third save.
        snap.snapshot(src, 3)
        snap.wait()
        names = sorted(p.name for p in tmp_path.glob("step_*"))
        assert names == ["step_0000000002", "step_0000000003"]
        assert snap.latest_dir().name == "step_0000000003"

        restored = snap.restore(_state(0.0))[0]
        np.testing.assert_array_equal(restored["w"], src["w"])


# --------------------------------------------------------------------------
# preempt
# --------------------------------------------------------------------------

def test_preemption_handler_flag_and_restore():
    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionHandler() as h:
        assert not h.requested
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5
        while not h.requested and time.monotonic() < deadline:
            time.sleep(0.01)
        assert h.requested
        assert h.last_signal == signal.SIGTERM
        os.kill(os.getpid(), signal.SIGTERM)  # repeated signal: still a flag
        assert h.requested
    assert signal.getsignal(signal.SIGTERM) is prev  # restored on exit


def test_find_latest_across_layouts(tmp_path):
    assert find_latest(tmp_path / "nothing") is None
    with pytest.raises(FileNotFoundError):
        resume_latest(_state(0.0), tmp_path / "nothing")

    ck_dir, snap_dir = tmp_path / "ck", tmp_path / "ck" / "snapshots"
    ck = CheckpointManager(ck_dir, async_save=False)
    ck.save(_state(8.0), {"epoch": 0}, step=8)
    with SnapshotManager(snap_dir) as snap:
        snap.snapshot(_state(9.0), 9)
        snap.wait()
        # Snapshot at step 9 beats the epoch checkpoint at step 8.
        found, step = find_latest(ck_dir, snap_dir)
        assert step == 9 and found == snap.latest_dir()

        state, meta, src = resume_latest(_state(0.0), ck_dir, snap_dir)
        assert meta["kind"] == "snapshot" and state["w"][0, 0] == 9.0

        # Ties go to the epoch checkpoint (clean epoch-start resume).
        ck.save(_state(9.5), {"epoch": 1}, step=9)
        found, step = find_latest(ck_dir, snap_dir)
        assert step == 9 and found == ck.latest_dir()

    # Flat pre-manager layout: the fallback of last resort.
    flat = tmp_path / "flat"
    save_checkpoint(flat, _state(3.0), {"epoch": 0})
    found, step = find_latest(flat)
    assert found == flat and step == -1


# --------------------------------------------------------------------------
# mid-epoch fast-forward (data pipeline)
# --------------------------------------------------------------------------

def test_pipeline_skip_steps_no_replay_no_skip(mesh8):
    from tpu_dp.data.cifar import make_synthetic
    from tpu_dp.data.pipeline import DataPipeline

    ds = make_synthetic(64, 10, seed=0, name="skiptest")
    pipe = DataPipeline(ds, batch_size=8, mesh=mesh8, shuffle=True, seed=3,
                        prefetch=0)
    pipe.set_epoch(1)
    full = [np.asarray(item["image"]) for _, item in pipe.windows(1)]
    assert len(full) == 8
    pipe.set_epoch(1)
    tail = [np.asarray(item["image"])
            for _, item in pipe.windows(1, skip_steps=3)]
    assert len(tail) == 5
    for a, b in zip(full[3:], tail):
        np.testing.assert_array_equal(a, b)  # step s drew the same examples

    # The resident twin: same invariant on the index stream.
    def steps_of(windows):
        out = []
        for n, idx in windows:
            arr = np.asarray(idx).reshape(n, -1)
            out.extend(arr[i] for i in range(n))
        return out

    pipe.set_epoch(1)
    full_idx = steps_of(pipe.index_windows(2))
    pipe.set_epoch(1)
    tail_idx = steps_of(pipe.index_windows(2, skip_steps=3))
    assert len(full_idx) == 8 and len(tail_idx) == 5
    for a, b in zip(full_idx[3:], tail_idx):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# Trainer integration: preempt → snapshot → resume, bitwise (in-process)
# --------------------------------------------------------------------------

def _tiny_cfg(tmp_path, **overrides):
    from tpu_dp.config import Config

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 8  # 8 steps/epoch over the 8-device mesh
    c.data.prefetch = 1
    c.train.epochs = 2
    c.train.log_every = 100
    c.train.eval_at_end = False
    c.train.ckpt_dir = str(tmp_path / "ck")
    c.optim.lr = 0.05
    for k, v in overrides.items():
        section, name = k.split(".")
        setattr(getattr(c, section), name, v)
    return c


def _leaves_bytes(tree):
    return [(np.asarray(x).dtype.str, np.asarray(x).tobytes())
            for x in jax.tree_util.tree_leaves(tree)]


def test_preempt_mid_epoch_resume_bitwise_identical(tmp_path):
    """SIGTERM mid-epoch-1 → PreemptedError + final snapshot; a resumed
    Trainer fast-forwards the sampler and finishes with the full TrainState
    (params, momentum, step) bitwise-equal to an uninterrupted run."""
    from tpu_dp.train.trainer import Trainer

    control = Trainer(_tiny_cfg(tmp_path / "control"))
    control.fit()
    assert int(control.state.step) == 16

    cfg = _tiny_cfg(tmp_path / "run")
    cfg.resilience.snapshot_every_steps = 3
    cfg.resilience.fault = "preempt:step=11"  # SIGTERM to self, mid-epoch 1
    with pytest.raises(PreemptedError):
        Trainer(cfg).fit()
    snap_dirs = list((tmp_path / "run" / "ck" / "snapshots").glob("step_*"))
    assert snap_dirs, "preemption left no final snapshot"

    cfg2 = _tiny_cfg(tmp_path / "run")
    cfg2.resilience.snapshot_every_steps = 3
    cfg2.train.resume = True
    resumed = Trainer(cfg2)
    # Resumed mid-epoch from the snapshot, not at the epoch-0 boundary.
    assert resumed.start_epoch == 1 and resumed.start_step >= 3
    resumed.fit()
    assert int(resumed.state.step) == 16
    assert _leaves_bytes(resumed.state) == _leaves_bytes(control.state)


@pytest.mark.shard_update
def test_sharded_opt_state_snapshot_roundtrip(tmp_path):
    """SnapshotManager round-trips a TrainState whose optimizer state is
    sharded over the 8-device mesh (`train.update_sharding=sharded`): the
    double-buffered host copy assembles the global layout and a restore
    into a fresh sharded target is bitwise-complete."""
    from tpu_dp.models import Net
    from tpu_dp.train import SGD, create_train_state, shard_optimizer
    from tpu_dp.train.step import make_train_step
    from tpu_dp.train.schedule import constant_lr
    from tpu_dp.parallel import dist
    from tpu_dp.data.cifar import make_synthetic, normalize

    mesh = dist.data_mesh()
    sopt = shard_optimizer(SGD(momentum=0.9), 8)
    state = create_train_state(
        Net(), jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32),
        sopt,
    )
    step = make_train_step(Net(), sopt, mesh, constant_lr(0.05),
                           update_sharding="sharded")
    ds = make_synthetic(16, 10, seed=0, name="snap")
    # One real step so the momentum shards are nonzero and device-committed
    # in their sharded layout.
    state, _ = step(state, {"image": normalize(ds.images),
                            "label": ds.labels})
    with SnapshotManager(tmp_path, every_steps=1) as snap:
        snap.snapshot(state, 1)
        snap.wait()
        target = create_train_state(
            Net(), jax.random.PRNGKey(1),
            np.zeros((1, 32, 32, 3), np.float32), sopt,
        )
        restored, meta = snap.restore(target)
    assert meta["global_step"] == 1
    assert _leaves_bytes(restored) == _leaves_bytes(state)


@pytest.mark.shard_update
def test_preempt_resume_with_sharded_opt_state(tmp_path):
    """Kill + auto-resume with the sharded weight update: a preempted
    sharded-mode run resumes from its snapshot (sharded opt state included)
    and finishes bitwise-identical to an uninterrupted sharded run."""
    from tpu_dp.train.trainer import Trainer

    def sharded_cfg(sub, **kw):
        c = _tiny_cfg(tmp_path / sub, **kw)
        c.train.update_sharding = "sharded"
        return c

    control = Trainer(sharded_cfg("control"))
    control.fit()
    assert int(control.state.step) == 16

    cfg = sharded_cfg("run")
    cfg.resilience.snapshot_every_steps = 3
    cfg.resilience.fault = "preempt:step=11"
    with pytest.raises(PreemptedError):
        Trainer(cfg).fit()
    assert list((tmp_path / "run" / "ck" / "snapshots").glob("step_*"))

    cfg2 = sharded_cfg("run")
    cfg2.resilience.snapshot_every_steps = 3
    cfg2.train.resume = True
    resumed = Trainer(cfg2)
    assert resumed.start_epoch == 1 and resumed.start_step >= 3
    resumed.fit()
    assert int(resumed.state.step) == 16
    assert _leaves_bytes(resumed.state) == _leaves_bytes(control.state)


# --------------------------------------------------------------------------
# End-to-end over real process boundaries: train.py + fault injection
# --------------------------------------------------------------------------

_CLI_COMMON = [
    "--data.dataset=synthetic",
    "--data.synthetic_train_size=64",
    "--data.synthetic_test_size=16",
    "--data.batch_size=8",
    "--train.epochs=2",
    "--train.log_every=100",
    "--train.eval_at_end=false",
    "--optim.lr=0.05",
    "--resilience.snapshot_every_steps=3",
]


def _run_train(ckpt_dir, *extra, timeout=240):
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("TPU_DP_FAULT", None)
    env["PYTHONPATH"] = (f"{repo}{os.pathsep}{env['PYTHONPATH']}"
                         if env.get("PYTHONPATH") else str(repo))
    proc = subprocess.run(
        [sys.executable, str(repo / "train.py"),
         f"--train.ckpt_dir={ckpt_dir}", *_CLI_COMMON, *extra],
        cwd=repo, env=env, capture_output=True, text=True, timeout=timeout,
    )
    return proc


@pytest.fixture(scope="module")
def control_run(tmp_path_factory):
    """One uninterrupted train.py run; returns its final params bytes."""
    ckpt_dir = tmp_path_factory.mktemp("resilience_control") / "ck"
    proc = _run_train(ckpt_dir)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return (ckpt_dir / "final_params.msgpack").read_bytes()


def test_kill_and_auto_resume_bitwise_identical(tmp_path, control_run):
    """The acceptance property: a worker hard-killed (`os._exit(137)`) at a
    mid-epoch step auto-resumes via `--resume=auto` from the latest async
    snapshot and reaches final params bitwise-identical to an uninterrupted
    run."""
    ckpt_dir = tmp_path / "ck"
    killed = _run_train(ckpt_dir, "--resilience.fault=kill:step=11")
    assert killed.returncode == KILL_EXIT_CODE, killed.stdout + killed.stderr
    assert not (ckpt_dir / "final_params.msgpack").exists()
    # The async snapshots survived the hard kill (cadence 3: step 9 landed).
    assert list((ckpt_dir / "snapshots").glob("step_*"))

    resumed = _run_train(ckpt_dir, "--resume=auto")
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert "resumed from" in resumed.stdout
    assert "snapshots" in resumed.stdout  # resumed from the snapshot layout
    assert (ckpt_dir / "final_params.msgpack").read_bytes() == control_run


def test_preempt_exits_143_and_resume_matches(tmp_path, control_run):
    """The preemption contract end-to-end: SIGTERM (injected to self) →
    final snapshot → exit 143; the supervisor's restart command
    (`--resume=auto`) completes bitwise-identical to uninterrupted."""
    ckpt_dir = tmp_path / "ck"
    preempted = _run_train(ckpt_dir, "--resilience.fault=preempt:step=5",
                           "--resilience.snapshot_every_steps=0")
    assert preempted.returncode == PREEMPTED_EXIT_CODE, (
        preempted.stdout + preempted.stderr)
    assert "preempted" in preempted.stdout
    # Even with periodic snapshotting off, the final snapshot landed.
    assert list((ckpt_dir / "snapshots").glob("step_*"))

    resumed = _run_train(ckpt_dir, "--resume=auto")
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert (ckpt_dir / "final_params.msgpack").read_bytes() == control_run


def test_resume_cli_flag():
    from tpu_dp.config import parse_cli

    cfg = parse_cli(["--resume=auto", "--data.dataset=synthetic"])
    assert cfg.train.resume is True
    assert parse_cli(["--data.dataset=synthetic"]).train.resume is False
    with pytest.raises(ValueError, match="--resume"):
        parse_cli(["--resume=never"])
