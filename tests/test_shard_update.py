"""Cross-replica sharded weight update (`train.update_sharding=sharded`).

The correctness property of the sharded update (Xu et al., "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training" —
PAPERS.md; docs/PERF.md): reduce-scatter(grads) → 1/world optimizer update →
all-gather(params) is *the same computation* as all-reduce(grads) → full
replicated update, element for element — so for f32 SGD the two paths must
produce **bitwise-identical** parameter trajectories, including momentum
state, across gradient accumulation and leaves whose element counts do not
divide the mesh (`Net`'s f32[5,5,3,6] on 8 devices pads 450 → 456).

Around that headline property: the collective wrappers' pad/unpad round
trip, the ~1/world optimizer-state memory claim, the windowed and
device-resident sharded loops, checkpoint resharding across topology/mode
changes, the EQuARX-style bf16 wire knob, factory validation, and
end-to-end Trainer parity.

Fast lane: ``pytest -m shard_update``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.parallel import collectives
from tpu_dp.train import (
    SGD,
    ShardedUpdate,
    constant_lr,
    create_train_state,
    make_train_step,
    shard_optimizer,
)

pytestmark = pytest.mark.shard_update

WORLD = 8


def _make_batch(seed, n):
    ds = make_synthetic(n, 10, seed=seed, name="synthetic")
    return {"image": normalize(ds.images), "label": ds.labels}


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _sample():
    return np.zeros((1, 32, 32, 3), np.float32)


def _states(momentum=0.9):
    model = Net()
    opt = SGD(momentum=momentum)
    sopt = shard_optimizer(SGD(momentum=momentum), WORLD)
    rng = jax.random.PRNGKey(0)
    state_r = create_train_state(model, rng, _sample(), opt)
    state_s = create_train_state(model, rng, _sample(), sopt)
    return model, opt, sopt, state_r, state_s


def _gathered_opt(sharded_opt_state, replicated_opt_state):
    """Sharded opt leaves (flat, padded) trimmed onto the replicated shapes."""
    return jax.tree_util.tree_map(
        lambda s, r: np.asarray(s)[: r.size].reshape(r.shape),
        sharded_opt_state, replicated_opt_state,
    )


# --------------------------------------------------------------------------
# collective wrappers: pad/unpad round trip
# --------------------------------------------------------------------------

def test_psum_scatter_all_gather_is_bitwise_pmean(mesh8):
    """all_gather(psum_scatter(t, mean=True), t) == pmean(t), bitwise,
    including leaves that do not divide the world size."""
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS
    from tpu_dp.train.step import _shard_map

    tree = {
        "odd": jnp.asarray(
            np.random.default_rng(0).normal(size=(5, 5, 3, 6)).astype(np.float32)
        ),  # 450 elements: pads to 456 on 8 devices
        "even": jnp.asarray(
            np.random.default_rng(1).normal(size=(16,)).astype(np.float32)
        ),
        "tiny": jnp.asarray(np.float32([3.0])),  # 1 element: pads to 8
    }

    def via_scatter(t):
        shards = collectives.psum_scatter(t, DATA_AXIS, world=WORLD, mean=True)
        return collectives.all_gather(shards, t, DATA_AXIS)

    def via_pmean(t):
        return collectives.pmean(t, DATA_AXIS)

    args = jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (i + 1) for i in range(WORLD)]), tree
    )
    spec_in, spec_out = (P(DATA_AXIS),), P()
    f_s = jax.jit(_shard_map(via_scatter, mesh8, spec_in, spec_out))
    f_p = jax.jit(_shard_map(via_pmean, mesh8, spec_in, spec_out))
    out_s, out_p = f_s(args), f_p(args)
    for a, b in zip(jax.tree_util.tree_leaves(out_s),
                    jax.tree_util.tree_leaves(out_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_invariant_all_gather_is_there_and_typed_replicated(mesh8):
    """`collectives.all_gather` rests on a JAX-internal primitive: 0.9.0
    ships `all_gather_invariant` in `jax._src.lax.parallel` with no public
    spelling (`lax.all_gather` is typed varying). A JAX bump that moves it
    breaks the import in `collectives.py` for every mode; one that gives
    `lax.all_gather` an invariant result makes the private import
    needless. Either way this test says so by name."""
    from jax import lax
    from jax._src.lax import parallel
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS

    assert collectives.all_gather_invariant is getattr(
        parallel, "all_gather_invariant", None), (
        "jax._src.lax.parallel.all_gather_invariant moved: "
        "tpu_dp/parallel/collectives.py imports it at module load")
    seen = {}

    def gather(x):
        ours = collectives.all_gather_invariant(x, DATA_AXIS, axis=0,
                                                tiled=True)
        public = lax.all_gather(x, DATA_AXIS, axis=0, tiled=True)
        seen["ours"], seen["public"] = (jax.typeof(ours).vma,
                                        jax.typeof(public).vma)
        return ours

    # Replication checking on (the default): out_specs=P() is only
    # accepted because the gather itself types its result invariant.
    out = jax.jit(jax.shard_map(gather, mesh=mesh8, in_specs=P(DATA_AXIS),
                                out_specs=P()))(jnp.arange(16.0))
    np.testing.assert_array_equal(np.asarray(out), np.arange(16.0))
    assert seen["ours"] == frozenset(), seen
    assert seen["public"] == {DATA_AXIS}, (
        f"lax.all_gather is no longer typed varying ({seen}): use it and "
        f"drop the private import")


def test_shard_slice_matches_scatter_layout(mesh8):
    """shard_slice hands replica i exactly the slice psum_scatter would:
    gathering the slices reconstructs the original leaf."""
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel.dist import DATA_AXIS
    from tpu_dp.train.step import _shard_map

    x = jnp.arange(450, dtype=jnp.float32).reshape(5, 90)

    def roundtrip(v):
        shards = collectives.shard_slice(v, DATA_AXIS, world=WORLD)
        return collectives.all_gather(shards, v, DATA_AXIS)

    f = jax.jit(_shard_map(roundtrip, mesh8, (P(),), P()))
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


def test_padded_and_shard_size():
    assert collectives.padded_size(450, 8) == 456
    assert collectives.shard_size(450, 8) == 57
    assert collectives.padded_size(16, 8) == 16
    assert collectives.shard_size(1, 8) == 1


# --------------------------------------------------------------------------
# the headline parity property
# --------------------------------------------------------------------------

@pytest.mark.parametrize("accum_steps", [1, 4])
def test_sharded_update_bitwise_matches_replicated(mesh8, accum_steps):
    """f32 SGD: sharded and replicated updates are the same computation —
    params AND momentum bitwise-identical over a multi-step trajectory,
    accum ∈ {1,4}, with non-divisible leaf sizes (Net on 8 devices)."""
    model, opt, sopt, state_r, state_s = _states()
    step_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             accum_steps=accum_steps, explicit=True)
    step_s = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             accum_steps=accum_steps,
                             update_sharding="sharded")
    sr, ss = _copy(state_r), _copy(state_s)
    n = 16 * accum_steps
    for i in range(3):
        flat = _make_batch(i, n)
        if accum_steps > 1:
            batch = {
                "image": flat["image"].reshape(accum_steps, 16, 32, 32, 3),
                "label": flat["label"].reshape(accum_steps, 16),
            }
        else:
            batch = flat
        sr, mr = step_r(sr, batch)
        ss, ms = step_s(ss, batch)
        assert float(mr["loss"]) == float(ms["loss"])
        assert int(mr["correct"]) == int(ms["correct"])
        assert int(mr["count"]) == int(ms["count"])
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(sr.opt_state),
        jax.tree_util.tree_leaves(_gathered_opt(ss.opt_state, sr.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_weight_decay_and_exclusion_bitwise(mesh8):
    """Weight decay — including the path-keyed bias/scale exclusion mask —
    works unchanged on shard trees (the shard layout preserves key paths),
    bitwise vs the replicated update."""
    model = Net()
    kw = dict(momentum=0.9, weight_decay=5e-4,
              decay_exclude_bias_and_norm=True)
    opt = SGD(**kw)
    sopt = shard_optimizer(SGD(**kw), WORLD)
    rng = jax.random.PRNGKey(0)
    state_r = create_train_state(model, rng, _sample(), opt)
    state_s = create_train_state(model, rng, _sample(), sopt)
    step_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             explicit=True)
    step_s = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             update_sharding="sharded")
    sr, ss = _copy(state_r), _copy(state_s)
    for i in range(2):
        batch = _make_batch(i, 16)
        sr, _ = step_r(sr, batch)
        ss, _ = step_s(ss, batch)
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gspmd_factory_rejects_sharded_optimizer(mesh8):
    sopt = shard_optimizer(SGD(momentum=0.9), WORLD)
    with pytest.raises(ValueError, match="incompatible"):
        make_train_step(Net(), sopt, mesh8, constant_lr(0.05))


def test_sharded_matches_gspmd_path(mesh8):
    """Sharded explicit-collectives path vs the GSPMD-inferred path: the
    two ends of the implementation spectrum agree bitwise for f32 SGD."""
    model, opt, sopt, state_r, state_s = _states()
    step_g = make_train_step(model, opt, mesh8, constant_lr(0.05))
    step_s = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             update_sharding="sharded")
    sg, ss = _copy(state_r), _copy(state_s)
    for i in range(3):
        batch = _make_batch(i, 16)
        sg, _ = step_g(sg, batch)
        ss, _ = step_s(ss, batch)
    for a, b in zip(jax.tree_util.tree_leaves(sg.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_opt_state_memory_is_one_over_world(mesh8):
    """The memory claim: every optimizer-state leaf is laid out flat over
    the data axis — per-replica shard = padded_size/world elements, ~1/world
    of the replicated layout (exactly 1/world + padding)."""
    _, opt, sopt, state_r, state_s = _states()
    repl_leaves = jax.tree_util.tree_leaves(state_r.opt_state)
    shard_leaves = jax.tree_util.tree_leaves(state_s.opt_state)
    assert len(repl_leaves) == len(shard_leaves)
    repl_elems = sum(x.size for x in repl_leaves)
    per_replica = 0
    for r, s in zip(repl_leaves, shard_leaves):
        assert s.ndim == 1
        assert s.size == collectives.padded_size(r.size, WORLD)
        per_replica += s.size // WORLD

    # Laid onto the mesh by the step's in_shardings, each device addresses
    # exactly its shard.
    step_s = make_train_step(Net(), sopt, mesh8, constant_lr(0.05),
                             update_sharding="sharded")
    new_state, _ = step_s(_copy(state_s), _make_batch(0, 16))
    for r, leaf in zip(repl_leaves,
                       jax.tree_util.tree_leaves(new_state.opt_state)):
        shards = leaf.addressable_shards
        assert len(shards) == WORLD
        assert shards[0].data.size == collectives.shard_size(r.size, WORLD)
    assert per_replica <= repl_elems // WORLD + len(repl_leaves)  # pad slack


def test_bf16_collective_dtype_close_to_f32(mesh8):
    """EQuARX-style wire compression: bf16 reduce-scatter tracks the f32
    trajectory within bf16 tolerance (and is NOT bitwise — it really ran
    through the compressed path)."""
    model, opt, sopt, state_r, state_s = _states()
    step_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             explicit=True)
    step_b = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             update_sharding="sharded",
                             collective_dtype="bf16")
    sr, sb = _copy(state_r), _copy(state_s)
    for i in range(2):
        batch = _make_batch(i, 16)
        sr, _ = step_r(sr, batch)
        sb, _ = step_b(sb, batch)
    identical = True
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.02, atol=2e-3)
        identical &= bool(np.array_equal(np.asarray(a), np.asarray(b)))
    assert not identical, "bf16 wire dtype produced bitwise-f32 results?"


# --------------------------------------------------------------------------
# windowed + device-resident sharded loops
# --------------------------------------------------------------------------

def test_sharded_multi_step_matches_replicated_multi_step(mesh8):
    """The windowed sharded loop vs the windowed replicated loop: the
    headline bitwise property holds inside the scanned dispatch too (the
    scan-vs-host-loop comparison itself is only ulp-close — XLA fuses scan
    bodies differently — and is already covered for the shared body by
    test_step.test_scanned_multi_step_matches_host_loop)."""
    model, opt, sopt, state_r, state_s = _states()
    K, n = 4, 16
    sched = constant_lr(0.05)
    loop_r = make_train_step(model, opt, mesh8, sched,
                             feed="window", num_steps=K)
    loop_s = make_train_step(model, sopt, mesh8, sched,
                             feed="window", num_steps=K,
                             update_sharding="sharded")
    batches = [_make_batch(100 + i, n) for i in range(K)]
    pool = {
        "image": np.stack([b["image"] for b in batches]),
        "label": np.stack([b["label"] for b in batches]),
    }
    sr, mr = loop_r(_copy(state_r), pool)
    ss, ms = loop_s(_copy(state_s), pool)
    assert int(sr.step) == int(ss.step) == K
    np.testing.assert_array_equal(np.asarray(mr["loss"]),
                                  np.asarray(ms["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(sr.opt_state),
        jax.tree_util.tree_leaves(_gathered_opt(ss.opt_state, sr.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_resident_loop_matches_replicated(mesh8):
    """Device-resident feed + sharded update ≡ resident feed + replicated
    update: the feed redesign and the update redesign compose."""
    from tpu_dp.data.pipeline import DataPipeline

    model, opt, sopt, state_r, state_s = _states()
    K, n = 3, 16
    sched = constant_lr(0.05)
    ds = make_synthetic(K * n, 10, seed=7, name="res")
    pipe = DataPipeline(ds, batch_size=n, mesh=mesh8)
    data = pipe.resident_data()
    idx = np.arange(K * n, dtype=np.int32).reshape(K, n)

    loop_r = make_train_step(model, opt, mesh8, sched,
                             feed="resident", num_steps=K,
                             sample_shapes=pipe.sample_shapes)
    loop_s = make_train_step(model, sopt, mesh8, sched,
                             feed="resident", num_steps=K,
                             sample_shapes=pipe.sample_shapes,
                             update_sharding="sharded")
    sr, _ = loop_r(_copy(state_r), data, idx)
    ss, _ = loop_s(_copy(state_s), data, idx)
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(ss.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# checkpoint resharding: topology & mode changes
# --------------------------------------------------------------------------

def test_checkpoint_reshards_across_world_sizes(tmp_path):
    """A sharded checkpoint written under world=8 restores into a world=4
    layout (and back), values preserved — preemption on one topology,
    resume on another."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model = Net()
    rng = jax.random.PRNGKey(0)
    opt8 = shard_optimizer(SGD(momentum=0.9), 8)
    opt4 = shard_optimizer(SGD(momentum=0.9), 4)
    state8 = create_train_state(model, rng, _sample(), opt8)
    # Fill momentum with recognizable values (init is zeros everywhere) —
    # keeping the padding region zero, as any real trajectory does (padded
    # grads are zero, so padded momentum stays zero).
    true_sizes = [p.size for p in jax.tree_util.tree_leaves(state8.params)]
    state8 = state8.replace(opt_state=jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state8.opt_state),
        [
            jnp.where(jnp.arange(s.size) < n,
                      jnp.arange(s.size, dtype=s.dtype) + 1.0, 0.0)
            for s, n in zip(jax.tree_util.tree_leaves(state8.opt_state),
                            true_sizes)
        ],
    ))
    save_checkpoint(tmp_path / "w8", state8, {"epoch": 0})

    target4 = create_train_state(model, rng, _sample(), opt4)
    restored4, _ = load_checkpoint(tmp_path / "w8", target4)
    for s8, s4, p in zip(
        jax.tree_util.tree_leaves(state8.opt_state),
        jax.tree_util.tree_leaves(restored4.opt_state),
        jax.tree_util.tree_leaves(state8.params),
    ):
        n = p.size
        assert s4.size == collectives.padded_size(n, 4)
        # True elements preserved; any new tail is zero padding.
        np.testing.assert_array_equal(np.asarray(s4)[:n], np.asarray(s8)[:n])
        np.testing.assert_array_equal(np.asarray(s4)[n:], 0)


def test_checkpoint_reshards_across_update_modes(tmp_path):
    """replicated ↔ sharded transitions restore value-preserving: a run can
    turn the sharded update on (or off) at a checkpoint boundary."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model = Net()
    rng = jax.random.PRNGKey(0)
    opt = SGD(momentum=0.9)
    sopt = shard_optimizer(SGD(momentum=0.9), 8)
    state_r = create_train_state(model, rng, _sample(), opt)
    state_r = state_r.replace(opt_state=jax.tree_util.tree_map(
        lambda s: jnp.arange(s.size, dtype=s.dtype).reshape(s.shape),
        state_r.opt_state,
    ))
    save_checkpoint(tmp_path / "repl", state_r, {"epoch": 0})

    # replicated → sharded
    target_s = create_train_state(model, rng, _sample(), sopt)
    restored_s, _ = load_checkpoint(tmp_path / "repl", target_s)
    for r, s in zip(jax.tree_util.tree_leaves(state_r.opt_state),
                    jax.tree_util.tree_leaves(restored_s.opt_state)):
        np.testing.assert_array_equal(np.asarray(s)[: r.size],
                                      np.asarray(r).reshape(-1))

    # sharded → replicated
    save_checkpoint(tmp_path / "shard", restored_s, {"epoch": 0})
    restored_r, _ = load_checkpoint(tmp_path / "shard",
                                    create_train_state(model, rng, _sample(),
                                                       opt))
    for a, b in zip(jax.tree_util.tree_leaves(state_r.opt_state),
                    jax.tree_util.tree_leaves(restored_r.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_same_layout_unchanged(tmp_path):
    """The fast path: matching layouts round-trip untouched (regression
    guard on the reshard hook)."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model = Net()
    rng = jax.random.PRNGKey(0)
    sopt = shard_optimizer(SGD(momentum=0.9), 8)
    state = create_train_state(model, rng, _sample(), sopt)
    save_checkpoint(tmp_path, state, {"epoch": 0})
    restored, _ = load_checkpoint(
        tmp_path, create_train_state(model, rng, _sample(), sopt))
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# factory validation
# --------------------------------------------------------------------------

def test_factory_rejects_mismatched_optimizer(mesh8):
    opt = SGD(momentum=0.9)
    sopt = shard_optimizer(SGD(momentum=0.9), 8)
    with pytest.raises(ValueError, match="ShardedUpdate"):
        make_train_step(Net(), opt, mesh8, constant_lr(0.05),
                        update_sharding="sharded")
    with pytest.raises(ValueError, match="incompatible"):
        make_train_step(Net(), sopt, mesh8, constant_lr(0.05), explicit=True)
    with pytest.raises(ValueError, match="update_sharding"):
        make_train_step(Net(), opt, mesh8, constant_lr(0.05),
                        update_sharding="diagonal", explicit=True)
    with pytest.raises(ValueError, match="collective_dtype"):
        make_train_step(Net(), sopt, mesh8, constant_lr(0.05),
                        update_sharding="sharded",
                        collective_dtype="int4")
    # A wire dtype on the replicated path would be silently ignored —
    # rejected at the factory boundary instead.
    with pytest.raises(ValueError, match="collective_dtype"):
        make_train_step(Net(), opt, mesh8, constant_lr(0.05),
                        collective_dtype="bf16", explicit=True)
    with pytest.raises(ValueError, match="world"):
        ShardedUpdate(opt, 0)


def test_trainer_validates_update_sharding(tmp_path):
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    def cfg(**kw):
        c = Config()
        c.data.dataset = "synthetic"
        c.data.synthetic_train_size = 64
        c.data.synthetic_test_size = 16
        c.data.batch_size = 16
        c.train.ckpt_dir = str(tmp_path / "ck")
        for k, v in kw.items():
            sec, name = k.split(".")
            setattr(getattr(c, sec), name, v)
        return c

    with pytest.raises(ValueError, match="update_sharding"):
        Trainer(cfg(**{"train.update_sharding": "maybe"}))
    with pytest.raises(ValueError, match="collective_dtype"):
        Trainer(cfg(**{"train.collective_dtype": "bf16"}))


# --------------------------------------------------------------------------
# end to end: Trainer parity
# --------------------------------------------------------------------------

def test_trainer_sharded_parity(tmp_path):
    """Two Trainers, identical config except update_sharding: bitwise-equal
    final params after a full fit() (steps, checkpointing, eval included).
    Covers the trainer wiring: sharded step factory selection, sharded
    opt-state init, windowed dispatch, and checkpoint save of the sharded
    state."""
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    def cfg(mode, sub):
        c = Config()
        c.data.dataset = "synthetic"
        c.data.synthetic_train_size = 64
        c.data.synthetic_test_size = 16
        c.data.batch_size = 16
        c.data.prefetch = 1
        c.train.epochs = 1
        c.train.log_every = 100
        c.train.eval_at_end = True
        c.train.steps_per_call = 2
        c.train.ckpt_dir = str(tmp_path / sub)
        c.train.update_sharding = mode
        c.optim.lr = 0.05
        return c

    t_r = Trainer(cfg("replicated", "repl"))
    r_res = t_r.fit()
    t_s = Trainer(cfg("sharded", "shard"))
    s_res = t_s.fit()

    assert isinstance(t_s.optimizer, ShardedUpdate)
    assert int(t_r.state.step) == int(t_s.state.step) == 4
    for a, b in zip(jax.tree_util.tree_leaves(t_r.state.params),
                    jax.tree_util.tree_leaves(t_s.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert r_res["eval"]["accuracy"] == s_res["eval"]["accuracy"]


def test_trainer_sharded_batchnorm_model(tmp_path):
    """BatchNorm model (ResNet-18) through the sharded trainer path: the
    model is rebuilt with axis_name=DATA_AXIS (sync-BN inside shard_map),
    init uses the axis-free twin, and the trajectory tracks the replicated
    GSPMD run (global-batch stats) to sync-BN tolerance."""
    from tpu_dp.config import Config
    from tpu_dp.parallel.dist import DATA_AXIS
    from tpu_dp.train.trainer import Trainer

    def cfg(mode, sub):
        c = Config()
        c.model.name = "resnet18"
        c.model.num_classes = 10
        c.data.dataset = "synthetic"
        c.data.synthetic_train_size = 32
        c.data.synthetic_test_size = 16
        c.data.batch_size = 16
        c.data.prefetch = 1
        c.train.epochs = 1
        c.train.log_every = 100
        # Eval on: the sync-BN model must also evaluate (train=False uses
        # running stats — no axis collective, so plain jit works).
        c.train.eval_at_end = mode == "sharded"
        c.train.ckpt_dir = str(tmp_path / sub)
        c.train.update_sharding = mode
        c.optim.lr = 0.01
        return c

    t_s = Trainer(cfg("sharded", "shard"))
    assert getattr(t_s.model, "axis_name", None) == DATA_AXIS
    assert getattr(t_s._init_model, "axis_name", None) is None
    res = t_s.fit()
    assert "eval" in res
    t_r = Trainer(cfg("replicated", "repl"))
    assert getattr(t_r.model, "axis_name", None) is None
    t_r.fit()
    assert int(t_r.state.step) == int(t_s.state.step) == 2
    for a, b in zip(jax.tree_util.tree_leaves(t_r.state.params),
                    jax.tree_util.tree_leaves(t_s.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(t_r.state.batch_stats),
                    jax.tree_util.tree_leaves(t_s.state.batch_stats)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
