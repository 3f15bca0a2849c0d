"""Multi-process end-to-end: 2 JAX processes over a loopback coordinator.

The TPU-native analogue of the reference's `torchrun --nproc_per_node=2`
NCCL run (`cifar_example_ddp.py:55-57`'s `127.0.0.1:29500` rendezvous):
two OS processes bootstrap via `jax.distributed.initialize`, build a shared
2-device mesh (1 CPU device each), feed *disjoint host shards* of the global
batch (`make_array_from_process_local_data`), and run the compiled DP train
step. Asserts: identical loss on both ranks (replicated output), identical
updated params (replica lockstep — the DDP guarantee), disjoint sampler
shards, and — the reference's own correctness signal (SURVEY.md §3.5) — that
the 2-process trajectory equals a single-process run on the concatenated
global batches, across a real process boundary.
"""

import json
import os
import pickle
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

_WORKER = r"""
import os, pickle, sys
rank = int(sys.argv[1]); world = int(sys.argv[2]); port = sys.argv[3]
out_path = sys.argv[4]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
# gloo: the CPU client has no cross-process collectives by default
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=world,
                           process_id=rank)
import numpy as np
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.data.sampler import ShardedSampler
from tpu_dp.models import Net
from tpu_dp.parallel import dist
from tpu_dp.parallel.sharding import shard_batch
from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

assert jax.process_count() == world and jax.process_index() == rank
mesh = dist.data_mesh()
assert mesh.shape[dist.DATA_AXIS] == world  # one device per process

ds = make_synthetic(32, 10, seed=0, name="mp")  # identical on both ranks
sampler = ShardedSampler(len(ds), num_shards=world, shard_id=rank,
                         shuffle=True, seed=7)
idx = sampler.shard_indices()

model, opt = Net(), SGD(0.9)
state = create_train_state(model, jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32), opt)
step = make_train_step(model, opt, mesh, constant_lr(0.05))

losses = []
for k in range(2):  # two steps through this rank's shard
    sel = idx[k * 8:(k + 1) * 8]
    local = {"image": normalize(ds.images[sel]), "label": ds.labels[sel]}
    batch = shard_batch(local, mesh)  # assembles the 16-example global batch
    state, metrics = step(state, batch)
    losses.append(float(metrics["loss"]))

# Params are replicated; a jitted scalar digest is identical on every
# process iff the replicas are in lockstep.
import jax.numpy as jnp
digest_fn = jax.jit(lambda p: sum(
    jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(p)))
param_digest = float(digest_fn(state.params))
host_params = jax.tree_util.tree_map(np.asarray, state.params)
result = dict(rank=rank, loss=losses[-1], losses=losses,
              count=int(metrics["count"]), idx=idx.tolist(),
              param_digest=param_digest, params=host_params)
with open(out_path, "wb") as f:
    pickle.dump(result, f)
jax.distributed.shutdown()
"""


def _free_port() -> str:
    """OS-assigned free port for the loopback coordinator — hardcoded ports
    collide across re-runs (TIME_WAIT) and concurrent pytest invocations."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def _spawn_workers(tmp_path, script_text, argv_per_rank, name, timeout=300):
    """Run one subprocess per rank; return their stdout logs.

    On timeout, every child is killed and all drained logs surface in the
    failure — a hung rank must produce diagnostics, never leaked processes
    (the coordinator blocks in `jax.distributed.initialize` when a peer
    dies early, so the first `communicate` timing out is the common case).
    """
    script = tmp_path / f"{name}.py"
    script.write_text(script_text)
    repo_root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{repo_root}{os.pathsep}{env['PYTHONPATH']}"
        if env.get("PYTHONPATH") else str(repo_root)
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), *map(str, argv)],
            cwd=repo_root, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for argv in argv_per_rank
    ]
    logs = []
    try:
        for p in procs:
            logs.append(p.communicate(timeout=timeout)[0].decode())
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
        drained = logs + [
            p.communicate()[0].decode() for p in procs[len(logs):]
        ]
        pytest.fail(
            f"{name} timed out after {timeout}s; logs:\n"
            + "\n--- next rank ---\n".join(t[-3000:] for t in drained)
        )
    for p, log in zip(procs, logs):
        assert p.returncode == 0, f"{name} failed:\n{log[-3000:]}"
    return logs


@pytest.mark.slow
def test_two_process_dp_train_step(tmp_path):
    world, port = 2, _free_port()
    outs = [tmp_path / f"out{rank}.pkl" for rank in range(world)]
    _spawn_workers(
        tmp_path, _WORKER,
        [(rank, world, port, outs[rank]) for rank in range(world)],
        name="dp_worker", timeout=240,
    )
    results = [pickle.loads(o.read_bytes()) for o in outs]

    # Replicated outputs agree across processes.
    assert results[0]["loss"] == pytest.approx(results[1]["loss"], rel=1e-6)
    # Global batch count = 8 per process × 2.
    assert all(r["count"] == 16 for r in results)
    # Disjoint shards covering 32 examples.
    merged = set(results[0]["idx"]) | set(results[1]["idx"])
    assert not (set(results[0]["idx"]) & set(results[1]["idx"]))
    assert len(merged) == 32
    # Replicas hold identical updated params (lockstep).
    assert results[0]["param_digest"] == pytest.approx(
        results[1]["param_digest"], rel=1e-6
    )

    # Single-process oracle (SURVEY.md §3.5): one process, one device,
    # trained on the concatenated global batches in device order, must
    # reproduce the 2-process trajectory — the DDP-equivalence property
    # across a real process boundary, not just an in-process mesh.
    import jax

    from tpu_dp.data.cifar import make_synthetic, normalize
    from tpu_dp.models import Net
    from tpu_dp.parallel import dist
    from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

    ds = make_synthetic(32, 10, seed=0, name="mp")
    idx0 = np.asarray(results[0]["idx"])
    idx1 = np.asarray(results[1]["idx"])
    mesh1 = dist.data_mesh(num_devices=1)
    model, opt = Net(), SGD(0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step = make_train_step(model, opt, mesh1, constant_lr(0.05))
    oracle_losses = []
    for k in range(2):
        sel = np.concatenate([idx0[k * 8:(k + 1) * 8], idx1[k * 8:(k + 1) * 8]])
        batch = {"image": normalize(ds.images[sel]), "label": ds.labels[sel]}
        state, metrics = step(state, batch)
        oracle_losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(
        np.asarray(results[0]["losses"]), np.asarray(oracle_losses), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(results[0]["params"]),
        jax.tree_util.tree_leaves(state.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


_RESUME_WORKER = r"""
import os, pickle, sys
rank = int(sys.argv[1]); world = int(sys.argv[2]); port = sys.argv[3]
ckpt_dir = sys.argv[4]; phase = sys.argv[5]; out_path = sys.argv[6]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpu_dp.config import Config
from tpu_dp.train.trainer import Trainer

cfg = Config()
cfg.data.dataset = "synthetic"
cfg.data.synthetic_train_size = 64
cfg.data.synthetic_test_size = 16
cfg.data.batch_size = 8             # global batch 16 across 2 processes
cfg.train.epochs = 1
cfg.train.log_every = 100
cfg.train.eval_at_end = False
cfg.train.ckpt_dir = ckpt_dir
cfg.train.ckpt_async = False        # checkpoint durable before exit
cfg.train.resume = phase == "resume"
cfg.parallel.coordinator_address = f"127.0.0.1:{port}"
cfg.parallel.num_processes = world
cfg.parallel.process_id = rank

tr = Trainer(cfg)
if phase == "train":
    tr.fit()   # 4 steps; epoch-0 checkpoint written by process 0 only
# In the resume phase Trainer.__init__ already ran _maybe_resume: process 0
# loaded the checkpoint from disk and broadcast_one_to_all'd the TrainState
# (trainer.py) — capture exactly what each process holds at that point.
state = tr.state
leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]
with open(out_path, "wb") as f:
    pickle.dump(dict(rank=rank, start_epoch=tr.start_epoch,
                     step=int(state.step),
                     leaves=[(l.dtype.str, l.tobytes()) for l in leaves]), f)
jax.distributed.shutdown()
"""


def _spawn_resume_workers(tmp_path, phase, ckpt_dir):
    port = _free_port()
    outs = [tmp_path / f"{phase}_out{rank}.pkl" for rank in range(2)]
    _spawn_workers(
        tmp_path, _RESUME_WORKER,
        [(rank, 2, port, ckpt_dir, phase, outs[rank]) for rank in range(2)],
        name=f"resume_{phase}",
    )
    return [pickle.loads(o.read_bytes()) for o in outs]


@pytest.mark.slow
def test_two_process_checkpoint_resume(tmp_path):
    """Resume across a real restart: 2 processes train and checkpoint, a
    fresh pair of processes resumes, and both hold bit-identical state.

    Exercises the one distributed code path previously untested
    (VERDICT r2 missing #4): `Trainer._maybe_resume`'s multi-process
    branch, where process 0 alone reads the checkpoint (on a pod each host
    has its own disk) and `broadcast_one_to_all`s the restored TrainState
    and epoch — the guard against the silent replica-desync failure class
    (some ranks resume, some start fresh). The reference can't do any of
    this: it saves from every rank, last writer wins, and has no load path
    (`cifar_example_ddp.py:118-119`, SURVEY.md §5 "Checkpoint / resume").
    """
    ckpt_dir = tmp_path / "ck"
    trained = _spawn_resume_workers(tmp_path, "train", ckpt_dir)
    resumed = _spawn_resume_workers(tmp_path, "resume", ckpt_dir)

    # Both fresh processes resumed at the epoch after the checkpointed one.
    assert [r["start_epoch"] for r in resumed] == [1, 1]
    # Optimizer step counter restored (4 steps ran in the train phase).
    assert resumed[0]["step"] == trained[0]["step"] == 4
    # Bit-identical restored state on BOTH ranks — params, momentum
    # buffers, and step all broadcast from process 0's checkpoint — and
    # equal to what the training run ended with.
    for a, b, t in zip(resumed[0]["leaves"], resumed[1]["leaves"],
                       trained[0]["leaves"]):
        assert a == b    # rank 0 == rank 1 (dtype + raw bytes)
        assert a == t    # resumed == end-of-training state
    # The checkpoint layout honors the proc-0-write contract: exactly the
    # single-writer manager layout — one step dir for the one epoch, the
    # atomic `latest` pointer, proc-0's metrics log, the always-on
    # flight-recorder home (`obs/`, every rank dumps on exit), and the
    # final-weights export. Any rank-suffixed duplicate or torn .tmp
    # residue (the reference's all-ranks-write-one-path mode) changes
    # this set.
    assert sorted(p.name for p in ckpt_dir.iterdir()) == [
        "final_params.msgpack", "latest", "metrics.jsonl", "obs",
        "step_0000000004",
    ]


@pytest.mark.slow
def test_unreachable_coordinator_fails_fast(tmp_path):
    """Failure detection: a dead coordinator surfaces a contextual error
    within the timeout instead of hanging (SURVEY.md §5 — the reference's
    init_process_group has no timeout)."""
    script = tmp_path / "fail.py"
    # Note: jax's coordination client aborts the process (LOG(FATAL)) on
    # rendezvous timeout rather than raising, so "surfacing" here means a
    # bounded, diagnosable exit — not a Python exception.
    script.write_text(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from tpu_dp.parallel import dist\n"
        "dist.initialize('127.0.0.1:1', num_processes=2, process_id=1,\n"
        "                initialization_timeout=5)\n"
    )
    repo_root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{repo_root}{os.pathsep}{env['PYTHONPATH']}"
        if env.get("PYTHONPATH") else str(repo_root)
    )
    import time

    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=repo_root, env=env,
        capture_output=True, timeout=120, text=True,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode != 0  # died, did not hang
    assert elapsed < 90  # bounded by the timeout, not indefinite
    # Diagnosable: the coordination error names the failure class.
    assert "DEADLINE_EXCEEDED" in (proc.stdout + proc.stderr)


_FUSED_WORKER = r"""
import os, sys
rank, world, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
# gloo: the CPU client has no cross-process collectives by default
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=world,
                           process_id=rank)
import numpy as np
import jax.numpy as jnp
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import build_model
from tpu_dp.parallel import dist
from tpu_dp.parallel.sharding import shard_batch
from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

mesh = dist.data_mesh()
model = build_model("resnet18", num_classes=10, num_filters=8,
                    dtype=jnp.bfloat16, fused_stages=(0,), fused_block_b=2)
opt = SGD(0.9)
state = create_train_state(model, jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32), opt)
step = make_train_step(model, opt, mesh, constant_lr(0.05))
ds = make_synthetic(8 * world, 10, seed=0, name="fusedmp")
lo = rank * 8
local = {"image": normalize(ds.images[lo:lo + 8]),
         "label": ds.labels[lo:lo + 8]}
state, metrics = step(state, shard_batch(local, mesh))
print("FUSEDMP_OK", rank, repr(float(metrics["loss"])), flush=True)
jax.distributed.shutdown()
"""


_RESIDENT_WORKER = r"""
import os, pickle, sys
rank = int(sys.argv[1]); world = int(sys.argv[2]); port = sys.argv[3]
out_path = sys.argv[4]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
# gloo: the CPU client has no cross-process collectives by default
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=world,
                           process_id=rank)
import numpy as np
from tpu_dp.data.cifar import make_synthetic
from tpu_dp.data.pipeline import DataPipeline
from tpu_dp.models import Net
from tpu_dp.parallel import dist
from tpu_dp.train import SGD, constant_lr, create_train_state

mesh = dist.data_mesh()
ds = make_synthetic(64, 10, seed=0, name="mpres")  # identical on both ranks
model, opt = Net(), SGD(0.9)

def fresh_state():
    return create_train_state(model, jax.random.PRNGKey(0),
                              np.zeros((1, 32, 32, 3), np.float32), opt)

pipe = DataPipeline(ds, batch_size=8, mesh=mesh, shuffle=True, seed=7,
                    prefetch=0)
# Resident: dataset assembled replicated from both processes, windows fed
# by process-locally assembled sharded indices.
rdata = pipe.resident_data()
rloop = make_train_step(model, opt, mesh, constant_lr(0.05),
                        feed="resident", num_steps=2,
                                 sample_shapes=pipe.sample_shapes)
pipe.set_epoch(0)
state = fresh_state()
for n, idx in pipe.index_windows(2):   # 4 steps -> 2 windows of 2
    assert n == 2
    state, m = rloop(state, rdata, idx)
res_loss = float(m["loss"][-1])

# Streaming control: same sampler order, same body.
sloop = make_train_step(model, opt, mesh, constant_lr(0.05),
                        feed="window", num_steps=2)
pipe.set_epoch(0)
sstate = fresh_state()
for n, item in pipe.windows(2):
    assert n == 2, "control loop expects full windows only"
    sstate, sm = sloop(sstate, item)

import jax.numpy as jnp
digest_fn = jax.jit(lambda p: sum(
    jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(p)))
res_digest = float(digest_fn(state.params))
stream_digest = float(digest_fn(sstate.params))
with open(out_path, "wb") as f:
    pickle.dump(dict(rank=rank, res_loss=res_loss,
                     stream_loss=float(sm["loss"][-1]),
                     res_digest=res_digest,
                     stream_digest=stream_digest), f)
jax.distributed.shutdown()
"""


@pytest.mark.slow
def test_two_process_resident_feed(tmp_path):
    """The device-resident feed under a true multi-process mesh: replicated
    dataset assembly + process-locally assembled sharded index windows must
    reproduce the streaming trajectory exactly, with replicated outputs in
    lockstep across processes."""
    world, port = 2, _free_port()
    outs = [tmp_path / f"res{rank}.pkl" for rank in range(world)]
    _spawn_workers(
        tmp_path, _RESIDENT_WORKER,
        [(rank, world, port, outs[rank]) for rank in range(world)],
        name="resident_mp",
    )
    results = [pickle.loads(o.read_bytes()) for o in outs]
    # Resident ≡ streaming on each rank (same examples, same order).
    for r in results:
        assert r["res_loss"] == pytest.approx(r["stream_loss"], rel=1e-6)
        assert r["res_digest"] == pytest.approx(r["stream_digest"], rel=1e-6)
    # Replicated outputs agree across processes.
    assert results[0]["res_loss"] == pytest.approx(
        results[1]["res_loss"], rel=1e-6)
    assert results[0]["res_digest"] == pytest.approx(
        results[1]["res_digest"], rel=1e-6)


_HEALTH_WORKER = r"""
import sys, time
rank = int(sys.argv[1]); world = int(sys.argv[2]); run_dir = sys.argv[3]
from tpu_dp.obs.health import HeartbeatWriter
from tpu_dp.resilience.faultinject import FaultInjector

inj = FaultInjector.from_spec("", rank=rank)  # plan from TPU_DP_FAULT env
with HeartbeatWriter(run_dir, rank=rank) as hb:
    for step in range(1, 7):
        t0 = time.perf_counter()
        time.sleep(0.03)           # uniform simulated step work
        if inj is not None:
            inj.on_step(step)      # the injected straggler delay
        hb.beat(step, (time.perf_counter() - t0) * 1e3)
print("HEALTH_OK", rank, flush=True)
"""


@pytest.mark.obs
def test_two_process_straggler_and_hang_detection(tmp_path, monkeypatch):
    """Cross-rank straggler attribution over a real process boundary: two
    OS processes heartbeat into a shared run dir; the deterministic fault
    injector (`TPU_DP_FAULT` delay, the same spec production uses) slows
    rank 1 at step 3 only. The monitor must name exactly that rank and
    step with the measured lag factor — and a stale-heartbeat check on the
    same files must flag a hang per the configured ``on_flag``."""
    import time

    from tpu_dp.obs.health import HealthError, HealthMonitor

    monkeypatch.setenv("TPU_DP_FAULT", "delay:step=3,rank=1,ms=300")
    run_dir = tmp_path / "obs"
    logs = _spawn_workers(
        tmp_path, _HEALTH_WORKER,
        [(rank, 2, run_dir) for rank in range(2)],
        name="health_mp", timeout=120,
    )
    assert all("HEALTH_OK" in log for log in logs)

    mon = HealthMonitor(run_dir, world=2, straggler_factor=3.0,
                        stale_after_s=3600.0)
    stragglers = [i for i in mon.scan() if i.kind == "straggler"]
    assert stragglers, "injected delay not flagged"
    # The worst offender is the injected-delay rank at the injected step.
    worst = max(stragglers, key=lambda i: i.ratio)
    assert (worst.rank, worst.step) == (1, 3)
    assert worst.ratio >= 3.0          # the measured lag factor
    assert worst.step_ms >= 300.0      # carries the delay
    # Latest beats are healthy — the live check stays quiet…
    assert mon.check(now=time.time()) == []

    # …until the heartbeats go stale (simulated hang): warn mode reports,
    # raise mode aborts with the flagged ranks attached.
    later = time.time() + 10.0
    lax = HealthMonitor(run_dir, world=2, stale_after_s=5.0,
                        logger=(logged := []).append)
    issues = lax.report(lax.check(now=later))
    assert {i.rank for i in issues} == {0, 1}
    assert all(i.kind == "stale" for i in issues) and len(logged) == 2
    strict = HealthMonitor(run_dir, world=2, stale_after_s=5.0,
                           on_flag="raise")
    with pytest.raises(HealthError):
        strict.report(strict.check(now=later))


_ELASTIC_WORKER = r"""
import os, pickle, sys
rank = int(sys.argv[1]); world = int(sys.argv[2]); port = sys.argv[3]
ckpt = sys.argv[4]; out_path = sys.argv[5]; fault = sys.argv[6]
update_sharding = sys.argv[7]; train_size = int(sys.argv[8])
guard = len(sys.argv) > 9 and sys.argv[9] == "guard"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpu_dp.config import Config
from tpu_dp.train.trainer import Trainer
from tpu_dp.resilience import PreemptedError

cfg = Config()
cfg.data.dataset = "synthetic"
cfg.data.synthetic_train_size = train_size
cfg.data.synthetic_test_size = 16
cfg.data.batch_size = 4            # per process: global batch 12 -> 8
cfg.train.epochs = 2
cfg.train.log_every = 100
cfg.train.eval_at_end = False
cfg.train.steps_per_call = 1
cfg.train.ckpt_dir = ckpt
cfg.train.ckpt_async = False
cfg.train.obs = "basic"
cfg.train.update_sharding = update_sharding
cfg.resilience.elastic = True
cfg.resilience.fault = fault
cfg.resilience.regroup_timeout_s = 60
cfg.parallel.coordinator_address = f"127.0.0.1:{port}"
cfg.parallel.num_processes = world
cfg.parallel.process_id = rank
if guard:
    # Guardrail twin of the elastic run: per-step snapshots give the SDC
    # rollback a trusted pre-corruption resume point, the per-step audit
    # bounds detection latency to one boundary, and spike detection stays
    # unarmed (min_steps > run length) so only the audit drives events.
    cfg.guard.enabled = True
    cfg.guard.action = "skip"
    cfg.guard.sdc_every_steps = 1
    cfg.guard.spike_min_steps = 64
    cfg.resilience.snapshot_every_steps = 1

from tpu_dp.train.trainer import run_elastic
try:
    # run_elastic == Trainer(cfg).fit() everywhere except a fired
    # `relaunch:` fault, which rejoins the run in-process (the
    # deterministic twin of "the preempted rank comes back").
    tr, result = run_elastic(cfg)
except PreemptedError as e:
    print("ELASTIC_LEFT", rank, repr(str(e)), flush=True)
    sys.exit(143)
from tpu_dp.obs.counters import counters
host_params = jax.tree_util.tree_map(np.asarray, tr.state.params)
with open(out_path, "wb") as f:
    pickle.dump(dict(
        rank=rank, sid=tr.stable_rank, new_rank=tr.ctx.process_index,
        world=tr.ctx.process_count, params=host_params,
        record=tr.elastic.record.to_json(), counters=counters.snapshot(),
        history=result["history"], step=int(tr.state.step),
    ), f)
print("ELASTIC_OK", rank, flush=True)
sys.exit(0)
"""


def _elastic_oracle_params(record: dict, *, world0=3, num_examples,
                           batch=4, epochs=2, seed=0, sampler_seed=0):
    """Single-device oracle of the elastic run's exact batch sequence.

    Reconstructs, from the published membership record alone, every global
    batch the 3-then-2-rank run consumed — `ShardedSampler` streams for
    the pre-regroup segments, `elastic_resplit` for the re-split tail —
    and trains the same model on them one step at a time. Matching final
    params prove the trainer consumed exactly the predicted samples in
    exactly the predicted order across the world change (the
    DDP-equivalence oracle of `test_two_process_dp_train_step`, extended
    over a membership transition).
    """
    import jax

    from tpu_dp.config import Config
    from tpu_dp.data.cifar import load_dataset
    from tpu_dp.data.sampler import ShardedSampler, elastic_resplit
    from tpu_dp.models import Net
    from tpu_dp.parallel import dist
    from tpu_dp.train import SGD, create_train_state, make_train_step
    from tpu_dp.train.schedule import make_schedule

    defaults = Config()
    resume = record["resume"]
    interrupted, lineage = int(resume["epoch"]), resume["lineage"]
    world1 = int(record["world"])
    ds = load_dataset("synthetic", "./data", train=True,
                      allow_synthetic=True,
                      synthetic_num_examples=num_examples, seed=seed)

    def segment_streams(epoch, prior, world):
        if not prior:
            out = []
            for r in range(world):
                s = ShardedSampler(len(ds), world, r, shuffle=True,
                                   seed=sampler_seed)
                s.set_epoch(epoch)
                out.append(s.shard_indices())
            return out
        return [elastic_resplit(len(ds), True, sampler_seed, epoch, batch,
                                prior, world, r) for r in range(world)]

    mesh1 = dist.data_mesh(num_devices=1)
    model, opt = Net(), SGD(defaults.optim.momentum)
    state = create_train_state(model, jax.random.PRNGKey(seed),
                               np.zeros((1, 32, 32, 3), np.float32), opt)
    step = make_train_step(model, opt, mesh1, make_schedule(
        "constant", defaults.optim.lr, 1, 0, 0.0))
    consumed_counts = np.zeros(len(ds), np.int64)
    for epoch in range(epochs):
        if epoch < interrupted:
            segments = [([], world0, None)]
        elif epoch == interrupted:
            segments = [([], world0, int(lineage[0][1])),
                        (lineage, world1, None)]
        else:
            segments = [([], world1, None)]
        for prior, world, steps in segments:
            streams = segment_streams(epoch, prior, world)
            n = (min(len(s) for s in streams) // batch
                 if steps is None else steps)
            for k in range(n):
                sel = np.concatenate(
                    [s[k * batch:(k + 1) * batch] for s in streams])
                consumed_counts[np.asarray(sel)] += 1
                state, _ = step(state, {"image": ds.images[sel],
                                        "label": ds.labels[sel]})
    return state, consumed_counts


def _run_elastic_workers(tmp_path, fault, update_sharding="replicated",
                         train_size=48, guard=False):
    port = _free_port()
    outs = [tmp_path / f"el{rank}.pkl" for rank in range(3)]
    script = tmp_path / "elastic_worker.py"
    script.write_text(_ELASTIC_WORKER)
    repo_root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{repo_root}{os.pathsep}{env['PYTHONPATH']}"
        if env.get("PYTHONPATH") else str(repo_root)
    )
    env.pop("TPU_DP_FAULT", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), "3", port,
             str(tmp_path / "ck"), str(outs[rank]), fault, update_sharding,
             str(train_size)] + (["guard"] if guard else []),
            cwd=repo_root, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for rank in range(3)
    ]
    return procs, outs


def _assert_elastic_outcome(procs, outs, victim=2):
    logs = []
    try:
        for p in procs:
            logs.append(p.communicate(timeout=240)[0].decode())
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
        drained = logs + [
            p.communicate()[0].decode() for p in procs[len(logs):]
        ]
        pytest.fail(
            "elastic workers timed out; logs:\n"
            + "\n--- next rank ---\n".join(t[-3000:] for t in drained)
        )
    # The preempted rank exits 143 (terminated-by-request); the survivors
    # finish the job with exit 0 and NO operator action.
    for rank, (p, log) in enumerate(zip(procs, logs)):
        want = 143 if rank == victim else 0
        assert p.returncode == want, (
            f"rank {rank}: rc {p.returncode} != {want}\n{log[-3000:]}"
        )
    assert f"ELASTIC_LEFT {victim}" in logs[victim]
    results = {}
    for rank, out in enumerate(outs):
        if rank != victim:
            results[rank] = pickle.loads(out.read_bytes())
    return results, logs


def _assert_elastic_run(results, victim=2, num_examples=48):
    """The shared elastic acceptance block (record, coverage, oracle)."""
    import jax

    survivors = sorted(results)
    a = results[survivors[0]]
    record = a["record"]
    # Membership epoch 1: survivors only, the victim attributed departed.
    assert record["epoch"] == 1
    assert record["members"] == survivors
    assert [d["sid"] for d in record["departed"]] == [victim]
    assert a["world"] == 2
    # Dense ranks reassigned in stable-id order.
    for sid, r in zip(survivors, range(2)):
        assert results[sid]["new_rank"] == r
    # The regroup is attributed in the obs counters.
    for sid in survivors:
        c = results[sid]["counters"]
        assert c["elastic.regroups"] == 1
        assert c["elastic.lost_ranks"] == 1
        assert c["elastic.regroup_s"] > 0
        assert c["elastic.membership_epoch"] == 1
    # Survivors hold bit-identical params (replica lockstep survived the
    # reshard)...
    for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                    jax.tree_util.tree_leaves(
                        results[survivors[1]]["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # ... equal to the single-device oracle built from the membership
    # record alone — proving the exact post-regroup sample schedule.
    oracle_state, counts = _elastic_oracle_params(
        record, num_examples=num_examples)
    for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                    jax.tree_util.tree_leaves(oracle_state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
    # Exactly-once coverage: in the interrupted epoch every sample was
    # consumed once, except up to one seam batch (< new-world × batch)
    # shed by the same drop_remainder policy every epoch end applies.
    total_epochs = 2
    dropped = int((counts < total_epochs).sum())
    assert dropped < 2 * 4 * 2, f"{dropped} samples dropped"
    assert (counts <= total_epochs).all(), "a sample was consumed twice"
    return record


@pytest.mark.slow
@pytest.mark.elastic
def test_three_process_elastic_preempt_rank2(tmp_path):
    """The elastic acceptance run (ISSUE 7): 3 CPU processes, rank 2 gets
    a (self-delivered, deterministic) SIGTERM at step 2 via
    ``TPU_DP_FAULT=preempt:`` — the survivors quiesce at a common step,
    snapshot, re-`initialize` at world 2, reshard, re-split the epoch,
    re-verify the DP304 fingerprint, and finish BOTH epochs with final
    params matching the single-device oracle of the exact predicted
    sample schedule."""
    procs, outs = _run_elastic_workers(tmp_path, "preempt:step=2,rank=2")
    results, logs = _assert_elastic_outcome(procs, outs, victim=2)
    record = _assert_elastic_run(results, victim=2)
    assert record["reason"] == "graceful"
    # DP304 re-verification ran on the shrunk mesh before the first
    # post-regroup step (logged by the new rank 0; the check itself is an
    # allgather-compare on every rank). The tag is keyed by membership
    # epoch AND world size (ISSUE 12 satellite).
    new_rank0 = next(s for s in results if results[s]["new_rank"] == 0)
    assert ("collective-schedule fingerprint (train_step@me1w2)"
            in logs[new_rank0])


@pytest.mark.slow
@pytest.mark.elastic
def test_three_process_elastic_external_sigterm_rank0(tmp_path):
    """Same protocol under a REAL external SIGTERM, aimed at rank 0 — the
    hardest seat: the membership leader, the snapshot writer, and the
    metrics owner all hand over. The kill lands at an arbitrary step
    (driver waits for training to be underway via the heartbeat file),
    and the oracle is reconstructed from whatever stop step the protocol
    agreed on. The sharded weight update rides along, so the regroup
    reshards real cross-process optimizer state."""
    import signal
    import time

    # The one-shot delay parks rank 0 for 3s at its step-2 boundary — a
    # deterministic window for the EXTERNAL signal to land mid-training
    # (the run is otherwise milliseconds per step; an unpinned kill races
    # past the end of the job and the leaver legitimately finishes).
    procs, outs = _run_elastic_workers(
        tmp_path, "delay:step=2,rank=0,ms=3000",
        update_sharding="sharded", train_size=96)
    hb = tmp_path / "ck" / "obs" / "heartbeat_r00000.jsonl"
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if hb.exists() and hb.read_text().count("\n") >= 1:
            break
        if any(p.poll() is not None for p in procs):
            break  # a worker died early; the outcome assert will report
        time.sleep(0.05)
    procs[0].send_signal(signal.SIGTERM)
    results, logs = _assert_elastic_outcome(procs, outs, victim=0)
    record = _assert_elastic_run(results, victim=0, num_examples=96)
    assert record["reason"] == "graceful"
    # The demoted-into-oblivion rank 0's successor owns rank-0 duties:
    # the post-regroup metrics records carry the new membership epoch.
    metrics = [json.loads(line) for line in
               (tmp_path / "ck" / "metrics.jsonl").read_text().splitlines()]
    regroups = [m for m in metrics if m.get("event") == "elastic_regroup"]
    assert len(regroups) == 1
    assert regroups[0]["membership_epoch"] == 1
    assert regroups[0]["world"] == 2
    assert [m["membership_epoch"] for m in metrics
            if "epoch" in m and m.get("membership_epoch") == 1]


@pytest.mark.slow
@pytest.mark.elastic
@pytest.mark.guard
def test_three_process_sdc_audit_names_rank2_and_regroups(tmp_path):
    """The guardrail SDC acceptance run (ISSUE 8): 3 CPU processes, a
    deterministic single-bit param flip on rank 2 at step 2
    (``TPU_DP_FAULT=sdc:step=2,rank=2``). The per-boundary cross-replica
    audit catches the divergence at the next boundary and NAMES rank 2
    (majority vote over the bit-checksums, down to the leaf); rank 2
    hands itself to the membership ledger (leave + rollback flavor) and
    exits 143, the survivors regroup to world 2, resume from the newest
    snapshot that PREDATES the corruption (post-detection snapshots are
    suppressed, pre-detection ones quarantine-marked), and finish both
    epochs matching the single-device oracle — corruption detected,
    attributed, evicted, and rewound away with zero operator action."""
    procs, outs = _run_elastic_workers(
        tmp_path, "sdc:step=2,rank=2", train_size=96, guard=True)
    results, logs = _assert_elastic_outcome(procs, outs, victim=2)
    record = _assert_elastic_run(results, victim=2, num_examples=96)
    # Rollback regroup (never graceful: a graceful final snapshot would
    # persist the corrupt state), resumed at or before the flip step.
    assert record["reason"] == "rollback"
    assert record["resume"]["lineage"][0][1] <= 2
    # The audit named rank 2 (the attribution line is rank-0-gated; every
    # rank's detection is asserted via its counters below).
    assert any("suspect rank(s) [2]" in log for log in logs)
    # ... and the survivors' counters carry the audit trail.
    for sid in sorted(results):
        c = results[sid]["counters"]
        assert c["guard.sdc_mismatches"] >= 1
        assert c["guard.sdc_audits"] >= 1
    # The eviction is attributed in the membership record's suspect reason.
    assert any("sdc" in d.get("reason", "").lower()
               for d in record["departed"])
    # The quarantine ledger holds the finding with rank attribution.
    recs = [json.loads(line) for line in
            (tmp_path / "ck" / "quarantine.jsonl").read_text().splitlines()]
    sdc = [r for r in recs if r["kind"] == "sdc"]
    assert sdc and sdc[0]["suspects"] == [2]
    assert sdc[0]["leaves"]["2"]  # leaf-level attribution present
    # The guard_sdc event reached the metrics stream too.
    metrics = [json.loads(line) for line in
               (tmp_path / "ck" / "metrics.jsonl").read_text().splitlines()]
    ev = [m for m in metrics if m.get("event") == "guard_sdc"]
    assert ev and ev[0]["suspects"] == [2]

    # --- ISSUE 9 acceptance: black boxes + the obsctl timeline ---------
    # Every rank left a flight-recorder dump — the evicted rank's exit
    # path (PreemptedError, 143) AND the survivors' clean completions.
    from tpu_dp.obs import flightrec, obsctl

    ck = tmp_path / "ck"
    dumps = {}
    for d in sorted((ck / "obs").glob("flightrec_r*.json")):
        payload = flightrec.read_dump(d)
        dumps[payload["rank"]] = payload
    assert sorted(dumps) == [0, 1, 2], "a rank left no black box"
    assert "PreemptedError" in dumps[2]["reason"]
    assert all(dumps[r]["reason"] == "clean" for r in (0, 1))
    assert any(e["kind"] == "guard_evict" for e in dumps[2]["events"])

    # `obsctl timeline` over NOTHING but the artifacts directory
    # reconstructs the ordered story: divergence detected -> rank
    # attributed -> eviction -> rollback resume -> completion.
    out = obsctl.build_timeline(obsctl.RunArtifacts(ck),
                                include_steps=True)
    events = out["events"]
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    kinds = [e["kind"] for e in events]
    story = ["guard_sdc", "eviction", "elastic_regroup", "epoch_complete"]
    positions = [kinds.index(k) for k in story]
    assert positions == sorted(positions), (
        f"story out of order: {list(zip(story, positions))}"
    )
    sdc_ev = events[kinds.index("guard_sdc")]
    assert sdc_ev["detail"]["suspects"] == [2]  # rank attributed
    evict = next(e for e in events if e["kind"] == "eviction")
    assert evict["rank"] == 2 and "sdc" in evict["detail"]["reason"]
    regroup = next(e for e in events if e["kind"] == "elastic_regroup")
    assert regroup["detail"]["flavor"] == "rollback"  # rollback resume
    exits = [e for e in events if e["kind"] == "exit"]
    assert sum(1 for e in exits
               if e["detail"]["reason"] == "clean") == 2  # completion
    # No duplicate replayed-step events: the post-eviction world replayed
    # steps past the rollback point, yet each optimizer step appears
    # exactly once (the surviving membership-epoch attempt wins).
    steps = [e["step"] for e in events if e["kind"] == "step"]
    assert steps and len(steps) == len(set(steps))
    assert out["stats"]["steps"]["replayed_beats_deduped"] > 0


def _read_ledger_records(ckpt_dir: Path) -> list[dict]:
    """All membership-epoch records of the run's (single) generation."""
    gens = sorted((ckpt_dir / "membership").iterdir())
    assert len(gens) == 1, gens
    return [json.loads(p.read_text())
            for p in sorted(gens[0].glob("epoch_*.json"))]


def _elastic_ledger_oracle_params(records, *, num_examples, batch=4,
                                  epochs=2, seed=0, sampler_seed=0):
    """Single-device oracle over an ARBITRARY graceful/grow transition
    history, reconstructed from the membership ledger alone.

    Generalizes `_elastic_oracle_params` (one shrink) to any sequence of
    graceful shrinks and grows: for each dataset epoch, the newest record
    whose resume targets it supplies the full consumption lineage (each
    prefix is a segment: ``steps_i`` optimizer steps at ``world_i``), the
    remainder runs re-split at that record's world; epochs no transition
    touched run wholly at the world current when they started. Rollback
    flavors rewind the clock and are out of scope here (asserted absent).
    """
    import jax

    from tpu_dp.config import Config
    from tpu_dp.data.cifar import load_dataset
    from tpu_dp.data.sampler import ShardedSampler, elastic_resplit
    from tpu_dp.models import Net
    from tpu_dp.parallel import dist
    from tpu_dp.train import SGD, create_train_state, make_train_step
    from tpu_dp.train.schedule import make_schedule

    assert all(r.get("reason") in ("initial", "graceful", "grow")
               for r in records), [r.get("reason") for r in records]
    defaults = Config()
    ds = load_dataset("synthetic", "./data", train=True,
                      allow_synthetic=True,
                      synthetic_num_examples=num_examples, seed=seed)

    def segment_streams(epoch, prior, world):
        if not prior:
            out = []
            for r in range(world):
                s = ShardedSampler(len(ds), world, r, shuffle=True,
                                   seed=sampler_seed)
                s.set_epoch(epoch)
                out.append(s.shard_indices())
            return out
        return [elastic_resplit(len(ds), True, sampler_seed, epoch, batch,
                                prior, world, r) for r in range(world)]

    def segments_for_epoch(e):
        touching = [r for r in records[1:]
                    if (r.get("resume") or {}).get("epoch") == e]
        if touching:
            last = touching[-1]
            lineage = [list(map(int, seg))
                       for seg in last["resume"]["lineage"]]
            segs = []
            for i, (world, steps) in enumerate(lineage):
                segs.append((lineage[:i], world, steps))
            segs.append((lineage, int(last["world"]), None))
            return segs
        # Untouched epoch: the world current when it started = the newest
        # record whose transition predates it (resume.epoch < e).
        world = int(records[0]["world"])
        for r in records[1:]:
            if (r.get("resume") or {}).get("epoch", 10**9) < e:
                world = int(r["world"])
        return [([], world, None)]

    mesh1 = dist.data_mesh(num_devices=1)
    model, opt = Net(), SGD(defaults.optim.momentum)
    state = create_train_state(model, jax.random.PRNGKey(seed),
                               np.zeros((1, 32, 32, 3), np.float32), opt)
    step = make_train_step(model, opt, mesh1, make_schedule(
        "constant", defaults.optim.lr, 1, 0, 0.0))
    consumed_counts = np.zeros(len(ds), np.int64)
    for epoch in range(epochs):
        for prior, world, steps in segments_for_epoch(epoch):
            streams = segment_streams(epoch, prior, world)
            n = (min(len(s) for s in streams) // batch
                 if steps is None else steps)
            for k in range(n):
                sel = np.concatenate(
                    [s[k * batch:(k + 1) * batch] for s in streams])
                consumed_counts[np.asarray(sel)] += 1
                state, _ = step(state, {"image": ds.images[sel],
                                        "label": ds.labels[sel]})
    return state, consumed_counts


@pytest.mark.slow
@pytest.mark.elastic
def test_three_process_elastic_grow_relaunch_rank2(tmp_path):
    """The grow acceptance run (ISSUE 12): 3 CPU processes, rank 2
    departs at step 2 via the ``relaunch:`` fault (the deterministic
    in-process twin of a preemption), the survivors shrink to world 2 —
    and then rank 2 COMES BACK: it discovers the live run through the
    membership ledger, publishes a fenced join request, the members run a
    grow-flavor quiesce, and the mesh regrows to world 3, resharding real
    cross-process flat-sharded optimizer state upward. All three ranks
    finish BOTH epochs, hold bitwise-identical params, and match the
    single-device oracle of the exact 3→2→3 sample schedule reconstructed
    from the ledger alone — elasticity as capacity tracking availability,
    not monotone decay."""
    import jax

    procs, outs = _run_elastic_workers(
        tmp_path, "relaunch:step=2,rank=2",
        update_sharding="sharded", train_size=96)
    logs = []
    try:
        for p in procs:
            logs.append(p.communicate(timeout=300)[0].decode())
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
        drained = logs + [
            p.communicate()[0].decode() for p in procs[len(logs):]
        ]
        pytest.fail(
            "grow workers timed out; logs:\n"
            + "\n--- next rank ---\n".join(t[-4000:] for t in drained)
        )
    # EVERY rank exits 0: the departed rank rejoined and completed.
    for rank, (p, log) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, (
            f"rank {rank}: rc {p.returncode}\n{log[-4000:]}"
        )
    results = {r: pickle.loads(outs[r].read_bytes()) for r in range(3)}

    # World regrew: every rank reports world 3 at the final epoch.
    assert [results[r]["world"] for r in range(3)] == [3, 3, 3]
    final = results[0]["record"]
    assert final["members"] == [0, 1, 2]
    assert final["reason"] == "grow"
    assert [j["sid"] for j in final["joined"]] == [2]
    # The service stayed pinned to the incumbent leader.
    assert final["service_sid"] == 0

    # Ledger story: 3 → 2 (graceful departure) → 3 (grow).
    records = _read_ledger_records(tmp_path / "ck")
    assert [r["world"] for r in records] == [3, 2, 3]
    assert records[1]["reason"] == "graceful"
    assert [d["sid"] for d in records[1]["departed"]] == [2]
    assert records[2]["reason"] == "grow"

    # Counters: survivors saw both transitions; the rejoiner counts its
    # departure AND its join (process-global registry spans incarnations).
    for sid in (0, 1):
        c = results[sid]["counters"]
        assert c["elastic.regroups"] == 2
        assert c["elastic.lost_ranks"] == 1
        assert c["elastic.joined_ranks"] == 1
        assert c["elastic.membership_epoch"] == 2
    c2 = results[2]["counters"]
    assert c2["elastic.departures"] == 1
    assert c2["elastic.joins"] == 1

    # All three ranks hold bitwise-identical params (lockstep survived
    # shrink-reshard AND grow-reshard of the flat-sharded opt state)...
    for r in (1, 2):
        for x, y in zip(jax.tree_util.tree_leaves(results[0]["params"]),
                        jax.tree_util.tree_leaves(results[r]["params"])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # ... equal to the ledger-reconstructed single-device oracle.
    oracle_state, counts = _elastic_ledger_oracle_params(
        records, num_examples=96)
    for x, y in zip(jax.tree_util.tree_leaves(results[0]["params"]),
                    jax.tree_util.tree_leaves(oracle_state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
    # Exactly-once across the union of shrink AND grow segments: nothing
    # consumed twice; seam shedding bounded by one global batch per
    # re-split (two re-splits happened).
    assert (counts <= 2).all(), "a sample was consumed twice in one epoch"
    dropped = int((counts < 2).sum())
    assert dropped < 2 * 3 * 4 * 2, f"{dropped} samples dropped"

    # DP304 re-verified on BOTH re-formed meshes, world-keyed tags.
    joined_logs = "\n".join(logs)
    assert "collective-schedule fingerprint (train_step@me1w2)" in joined_logs
    assert "collective-schedule fingerprint (train_step@me2w3)" in joined_logs

    # The obsctl timeline, from artifacts alone, tells
    # departure → shrink-regroup → join → grow-regroup → completion.
    from tpu_dp.obs import obsctl

    out = obsctl.build_timeline(obsctl.RunArtifacts(tmp_path / "ck"))
    kinds = [e["kind"] for e in out["events"]]
    story = ["elastic_departure", "elastic_regroup", "rank_joined",
             "elastic_grow"]
    positions = [kinds.index(k) for k in story]
    # The run's FINAL completion comes after the whole round trip (an
    # intermediate epoch may legitimately complete before the grow lands).
    positions.append(len(kinds) - 1 - kinds[::-1].index("epoch_complete"))
    story.append("epoch_complete(last)")
    assert positions == sorted(positions), (
        f"story out of order: {list(zip(story, positions))}"
    )
    grow_ev = next(e for e in out["events"] if e["kind"] == "elastic_grow")
    assert grow_ev["detail"]["world"] == 3
    joined_ev = next(e for e in out["events"] if e["kind"] == "rank_joined")
    assert joined_ev.get("rank") == 2 or (
        joined_ev.get("detail", {}).get("sid") == 2)


@pytest.mark.slow
@pytest.mark.elastic
def test_two_process_joiner_crash_mid_handshake_no_wedge(tmp_path):
    """A joiner that dies mid-handshake must cost the incumbents only the
    bounded bootstrap timeout (ISSUE 12 acceptance): 2 processes train,
    the driver forges a valid join request for sid 2 and never shows up —
    the members quiesce, publish the grow plan, admit, time out waiting
    for the ghost at the coordination connect, and RE-FORM at world 2
    from the very snapshot the grow quiesce committed (no wedge, no
    rollback, both epochs complete)."""
    import time

    port = _free_port()
    outs = [tmp_path / f"jc{rank}.pkl" for rank in range(2)]
    script = tmp_path / "jc_worker.py"
    script.write_text(_JOINER_CRASH_WORKER)
    repo_root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{repo_root}{os.pathsep}{env['PYTHONPATH']}"
        if env.get("PYTHONPATH") else str(repo_root)
    )
    env.pop("TPU_DP_FAULT", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), port,
             str(tmp_path / "ck"), str(outs[rank])],
            cwd=repo_root, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for rank in range(2)
    ]
    # Wait for training to be underway (the delay: fault pins rank 0 at
    # its step-2 boundary for 3s — a deterministic window), then forge
    # the ghost joiner's request into the live generation.
    mem_root = tmp_path / "ck" / "membership"
    deadline = time.monotonic() + 120
    gen_dir = None
    while time.monotonic() < deadline:
        gens = sorted(mem_root.iterdir()) if mem_root.exists() else []
        if gens and (gens[0] / "epoch_0000.json").exists():
            gen_dir = gens[0]
            break
        if any(p.poll() is not None for p in procs):
            break
        time.sleep(0.05)
    assert gen_dir is not None, "no membership generation appeared"
    from tpu_dp.resilience.elastic import MembershipLedger

    ghost = MembershipLedger(gen_dir, 2)
    assert ghost.publish_join(1, 2, token="ghost-token",
                              generation=gen_dir.name)
    logs = []
    try:
        for p in procs:
            logs.append(p.communicate(timeout=300)[0].decode())
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
        drained = logs + [
            p.communicate()[0].decode() for p in procs[len(logs):]
        ]
        pytest.fail(
            "joiner-crash workers timed out (wedged?); logs:\n"
            + "\n--- next rank ---\n".join(t[-4000:] for t in drained)
        )
    for rank, (p, log) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, (
            f"rank {rank}: rc {p.returncode}\n{log[-4000:]}"
        )
    results = {r: pickle.loads(outs[r].read_bytes()) for r in range(2)}
    # The incumbents ended at world 2 — grow attempted, aborted, no loss.
    assert [results[r]["world"] for r in range(2)] == [2, 2]
    records = _read_ledger_records(tmp_path / "ck")
    # epoch 1 admitted the ghost (world 3), epoch 2 is the corrective
    # re-form at world 2 with the handshake-timeout attribution.
    assert [r["world"] for r in records] == [2, 3, 2]
    assert records[1]["reason"] == "grow"
    assert [j["sid"] for j in records[1]["joined"]] == [2]
    assert records[2]["reason"] == "grow_aborted"
    assert records[2]["departed"][0]["sid"] == 2
    assert "handshake timeout" in records[2]["departed"][0]["reason"]
    # Same resume payload on both: the aborted grow lost no work.
    assert records[2]["resume"] == records[1]["resume"]
    # Params stayed in lockstep through the abort.
    import jax

    for x, y in zip(jax.tree_util.tree_leaves(results[0]["params"]),
                    jax.tree_util.tree_leaves(results[1]["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


_JOINER_CRASH_WORKER = r"""
import os, pickle, sys
rank = int(sys.argv[1]); port = sys.argv[2]; ckpt = sys.argv[3]
out_path = sys.argv[4]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tpu_dp.config import Config
from tpu_dp.train.trainer import run_elastic

cfg = Config()
cfg.data.dataset = "synthetic"
cfg.data.synthetic_train_size = 64
cfg.data.synthetic_test_size = 16
cfg.data.batch_size = 4
cfg.train.epochs = 2
cfg.train.log_every = 100
cfg.train.eval_at_end = False
cfg.train.steps_per_call = 1
cfg.train.ckpt_dir = ckpt
cfg.train.ckpt_async = False
cfg.train.obs = "basic"
cfg.resilience.elastic = True
# Short bound: the ghost joiner never connects; the grow bootstrap must
# fail within this and fall back to world 2.
cfg.resilience.regroup_timeout_s = 8
# One-shot delay pins rank 0 at its step-2 boundary for 3s so the driver
# can forge the ghost join while training is underway.
cfg.resilience.fault = "delay:step=2,rank=0,ms=3000"
cfg.parallel.coordinator_address = f"127.0.0.1:{port}"
cfg.parallel.num_processes = 2
cfg.parallel.process_id = rank

tr, result = run_elastic(cfg)
from tpu_dp.obs.counters import counters
host_params = jax.tree_util.tree_map(np.asarray, tr.state.params)
with open(out_path, "wb") as f:
    pickle.dump(dict(rank=rank, world=tr.ctx.process_count,
                     record=tr.elastic.record.to_json(),
                     params=host_params,
                     counters=counters.snapshot()), f)
print("JOINER_CRASH_OK", rank, flush=True)
sys.exit(0)
"""


@pytest.mark.slow
def test_two_process_fused_conv_step(tmp_path):
    """The fused Pallas-conv model under a true multi-process mesh: the
    custom-partitioned kernel must compose with the process-local input
    assembly (`make_array_from_process_local_data`), and the replicated
    loss must agree bitwise across processes and match a single-process
    run of the same global batch."""
    port = _free_port()
    logs = _spawn_workers(
        tmp_path, _FUSED_WORKER,
        [(rank, 2, port) for rank in range(2)],
        name="fused_mp",
    )
    losses = []
    for log in logs:
        for line in log.splitlines():
            if line.startswith("FUSEDMP_OK"):
                losses.append(float(line.split()[2]))
    assert len(losses) == 2, f"missing OK lines:\n{logs}"
    assert losses[0] == losses[1], losses

    # Single-process oracle on the concatenated global batch.
    import jax
    import jax.numpy as jnp

    from tpu_dp.data.cifar import make_synthetic, normalize
    from tpu_dp.models import build_model
    from tpu_dp.parallel import dist
    from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

    mesh = dist.data_mesh(devices=jax.devices()[:1])
    model = build_model("resnet18", num_classes=10, num_filters=8,
                        dtype=jnp.bfloat16, fused_stages=(0,), fused_block_b=2)
    opt = SGD(0.9)
    state = create_train_state(model, jax.random.PRNGKey(0),
                               np.zeros((1, 32, 32, 3), np.float32), opt)
    step = make_train_step(model, opt, mesh, constant_lr(0.05))
    ds = make_synthetic(16, 10, seed=0, name="fusedmp")
    _, metrics = step(state, {"image": normalize(ds.images),
                              "label": ds.labels})
    assert losses[0] == pytest.approx(float(metrics["loss"]), rel=2e-5)
