"""Compiled-step tests — the DDP-equivalence property and training dynamics.

SURVEY.md §4: "N-device grads == single-device grads on the concatenated
batch" is *the* correctness property of gradient-averaging data parallelism
(what DDP's allreduce guarantees, `cifar_example_ddp.py:83`), and loss
decrease is the reference's only in-band training signal
(`cifar_example.py:84-87`).
"""

import jax
import numpy as np
import pytest

from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.train import (
    SGD,
    constant_lr,
    create_train_state,
    make_eval_step,
    make_train_step,
)


def _make_batch(seed, n):
    ds = make_synthetic(n, 10, seed=seed, name="synthetic")
    return {"image": normalize(ds.images), "label": ds.labels}


def _copy(state):
    # The train step donates its input state; tests that reuse a state
    # across two step functions must pass fresh buffers.
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.array, state)


@pytest.fixture(scope="module")
def setup():
    model = Net()
    opt = SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    return model, opt, state


def test_dp_equivalence_8_vs_1(setup, mesh8, mesh1):
    """Same global batch ⇒ same updated params on a 1-mesh and an 8-mesh."""
    model, opt, state = setup
    batch = _make_batch(0, 16)

    step8 = make_train_step(model, opt, mesh8, constant_lr(0.01))
    step1 = make_train_step(model, opt, mesh1, constant_lr(0.01))

    s8, m8 = step8(_copy(state), batch)
    s1, m1 = step1(_copy(state), batch)

    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-5)
    assert int(m8["correct"]) == int(m1["correct"])
    for a, b in zip(
        jax.tree_util.tree_leaves(s8.params), jax.tree_util.tree_leaves(s1.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_compiled_dp_step_contains_gradient_allreduce(setup, mesh8, mesh1):
    """The DDP guarantee must exist as a real collective in the compiled
    program, not merely as numerical equivalence: the GSPMD partitioner
    must have inserted an all-reduce (the NCCL-allreduce analogue the
    reference gets from DDP's reducer, `cifar_example_ddp.py:83`,
    SURVEY.md §2B) into the 8-device program — and the 1-device program
    must contain none (nothing to reduce across)."""
    model, opt, state = setup
    batch = _make_batch(0, 16)
    # (.lower only traces avals — no execution, no donation, no copy needed)
    hlo8 = (make_train_step(model, opt, mesh8, constant_lr(0.05))
            .lower(state, batch).compile().as_text())
    # Specifically the GRADIENT all-reduce, not just any collective (the
    # sharded-batch metric means also lower to all-reduces): XLA emits the
    # grads as a bucketed tuple all-reduce whose operands are param-shaped —
    # conv1's kernel grad f32[5,5,3,6] must sit on an all-reduce line.
    grad_ar = [l for l in hlo8.splitlines()
               if "all-reduce(" in l and "f32[5,5,3,6]" in l]
    assert grad_ar, "no param-shaped (gradient) all-reduce in 8-device HLO"
    hlo1 = (make_train_step(model, opt, mesh1, constant_lr(0.05))
            .lower(state, batch).compile().as_text())
    assert "all-reduce" not in hlo1


def test_multi_step_trajectory_equivalence(setup, mesh8, mesh1):
    """Replicas stay in lockstep over several steps (momentum included)."""
    model, opt, state = setup
    step8 = make_train_step(model, opt, mesh8, constant_lr(0.05))
    step1 = make_train_step(model, opt, mesh1, constant_lr(0.05))
    s8, s1 = _copy(state), _copy(state)
    for i in range(3):
        batch = _make_batch(i, 8)
        s8, _ = step8(s8, batch)
        s1, _ = step1(s1, batch)
    for a, b in zip(
        jax.tree_util.tree_leaves(s8.params), jax.tree_util.tree_leaves(s1.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_shard_map_matches_gspmd(setup, mesh8):
    """Explicit-collectives path ≡ GSPMD-inferred path, step for step.

    Two statements of the same distributed program — per-shard grads +
    explicit `lax.pmean` vs sharding annotations + inferred all-reduce —
    must produce identical losses, counts, and parameter trajectories.
    """
    model, opt, state = setup
    step_g = make_train_step(model, opt, mesh8, constant_lr(0.05))
    step_s = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             explicit=True)
    sg, ss = _copy(state), _copy(state)
    for i in range(3):
        batch = _make_batch(i, 16)
        sg, mg = step_g(sg, batch)
        ss, ms = step_s(ss, batch)
        np.testing.assert_allclose(
            float(mg["loss"]), float(ms["loss"]), rtol=1e-5
        )
        assert int(mg["correct"]) == int(ms["correct"])
        assert int(mg["count"]) == int(ms["count"])
    for a, b in zip(
        jax.tree_util.tree_leaves(sg.params), jax.tree_util.tree_leaves(ss.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_shard_map_accum_matches_gspmd(setup, mesh8):
    """Explicit-collectives path under gradient accumulation ≡ GSPMD path.

    The one reduction must sit after the microbatch scan (the invariant
    `tpu_dp.analysis` DP202 verifies statically); numerically that means
    the accum shard_map step tracks the accum GSPMD step exactly.
    """
    model, opt, state = setup
    step_g = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             accum_steps=2)
    step_s = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             accum_steps=2, explicit=True)
    sg, ss = _copy(state), _copy(state)
    for i in range(2):
        flat = _make_batch(i, 32)
        batch = {
            "image": flat["image"].reshape(2, 16, 32, 32, 3),
            "label": flat["label"].reshape(2, 16),
        }
        sg, mg = step_g(sg, batch)
        ss, ms = step_s(ss, batch)
        np.testing.assert_allclose(
            float(mg["loss"]), float(ms["loss"]), rtol=1e-5
        )
        assert int(mg["correct"]) == int(ms["correct"])
        assert int(mg["count"]) == int(ms["count"])
    for a, b in zip(
        jax.tree_util.tree_leaves(sg.params), jax.tree_util.tree_leaves(ss.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_shard_map_sync_bn_resnet(mesh8):
    """shard_map path with a BatchNorm model (axis_name-synced stats)."""
    from tpu_dp.models import ResNet18
    from tpu_dp.parallel.dist import DATA_AXIS
    model_s = ResNet18(num_classes=10, num_filters=8, axis_name=DATA_AXIS)
    model_g = ResNet18(num_classes=10, num_filters=8)
    opt = SGD(momentum=0.9)
    state = create_train_state(
        model_g, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step_g = make_train_step(model_g, opt, mesh8, constant_lr(0.05))
    step_s = make_train_step(model_s, opt, mesh8, constant_lr(0.05),
                             explicit=True)
    sg, ss = _copy(state), _copy(state)
    batch = _make_batch(0, 16)
    sg, mg = step_g(sg, batch)
    ss, ms = step_s(ss, batch)
    np.testing.assert_allclose(float(mg["loss"]), float(ms["loss"]), rtol=1e-5)
    # Global-batch BN statistics: running stats from per-shard stats synced
    # over the data axis must match GSPMD's global-batch computation.
    for a, b in zip(
        jax.tree_util.tree_leaves(sg.batch_stats),
        jax.tree_util.tree_leaves(ss.batch_stats),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(sg.params), jax.tree_util.tree_leaves(ss.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_loss_decreases(setup, mesh8):
    """The reference's in-band signal: running loss goes down."""
    model, opt, state = setup
    step = make_train_step(model, opt, mesh8, constant_lr(0.05))
    state = _copy(state)
    ds = make_synthetic(512, 10, seed=1, name="synthetic")
    losses = []
    for i in range(20):
        sel = slice((i * 64) % 512, (i * 64) % 512 + 64)
        batch = {
            "image": normalize(ds.images[sel]),
            "label": ds.labels[sel],
        }
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05


def test_step_counter_and_lr(setup, mesh8):
    model, opt, state = setup
    step = make_train_step(model, opt, mesh8, constant_lr(0.01))
    batch = _make_batch(0, 8)
    state = _copy(state)
    prev_step = int(state.step)
    s1, m = step(state, batch)
    assert int(s1.step) == prev_step + 1
    assert float(m["lr"]) == pytest.approx(0.01)


def test_eval_step_counts(setup, mesh8):
    model, opt, state = setup
    ev = make_eval_step(model, mesh8)
    batch = _make_batch(0, 24)
    m = ev(state, batch)
    assert int(m["count"]) == 24
    assert 0 <= int(m["correct"]) <= 24


def test_scanned_multi_step_matches_host_loop(setup, mesh8):
    """K scanned steps (one dispatch) ≡ K host-loop step calls, exactly.

    `make_train_step(feed="window")` is the device-side training loop (lax.scan over the
    step body); its trajectory, per-step losses, and LR schedule positions
    must be indistinguishable from driving `make_train_step` from the host.
    """
    import jax.numpy as jnp

    from tpu_dp.train import cosine_lr

    model, opt, state = setup
    K, n = 4, 16
    sched = cosine_lr(0.05, 10, 2)
    step = make_train_step(model, opt, mesh8, sched)
    loop = make_train_step(model, opt, mesh8, sched,
                           feed="window", num_steps=K)

    batches = [_make_batch(100 + i, n) for i in range(K)]
    pool = {
        "image": np.stack([b["image"] for b in batches]),
        "label": np.stack([b["label"] for b in batches]),
    }

    s_host = _copy(state)
    host_metrics = []
    for b in batches:
        s_host, m = step(s_host, b)
        host_metrics.append(m)

    s_scan, stacked = loop(_copy(state), pool)

    assert int(s_scan.step) == int(s_host.step)
    for i, m in enumerate(host_metrics):
        np.testing.assert_allclose(
            float(stacked["loss"][i]), float(m["loss"]), rtol=1e-5
        )
        assert int(stacked["correct"][i]) == int(m["correct"])
        np.testing.assert_allclose(
            float(stacked["lr"][i]), float(m["lr"]), rtol=1e-6
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_scan.params),
        jax.tree_util.tree_leaves(s_host.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def _image_dataset(sample_shape, n, seed):
    """uint8 images of any row shape (`make_synthetic`'s are 32x32x3)."""
    from tpu_dp.data.cifar import ArrayDataset

    rng = np.random.default_rng(seed)
    return ArrayDataset(
        images=rng.integers(0, 256, (n, *sample_shape), dtype=np.uint8),
        labels=rng.integers(0, 10, n).astype(np.int32),
        name="res", num_classes=10, synthetic=True)


# 28x28x1: a row of 784 bytes is no whole number of 128-lane tiles.
@pytest.mark.parametrize("sample_shape", [(32, 32, 3), (28, 28, 1)],
                         ids=["32x32x3", "28x28x1"])
def test_resident_loop_matches_multi_step(mesh8, sample_shape):
    """Device-resident feed ≡ streaming feed, bit for bit.

    `make_train_step(feed="resident")` gathers each step's batch on-device from the
    data set staged with its rows flat (`DataPipeline.resident_data`) and
    restores the rows' shape; the trajectory and per-step metrics must be
    those of `feed="window"` on the equivalent stacked pool
    (VERDICT r4 next-steps #3). Exercises uint8 staging: normalization
    happens in-body for both paths.
    """
    from tpu_dp.data.pipeline import DataPipeline
    from tpu_dp.train import cosine_lr

    model, opt = Net(), SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, *sample_shape), np.float32),
        opt)
    K, n = 4, 16
    sched = cosine_lr(0.05, 10, 2)
    ds = _image_dataset(sample_shape, K * n, seed=7)

    loop = make_train_step(model, opt, mesh8, sched,
                           feed="window", num_steps=K)
    # Shuffled indices: the pool holds the same examples in the same order.
    idx = np.random.default_rng(3).permutation(K * n).astype(np.int32)
    idx = idx.reshape(K, n)
    pool = {
        "image": ds.images[idx],  # uint8: in-body norm
        "label": ds.labels[idx],
    }
    s_stream, stream_m = loop(_copy(state), pool)

    pipe = DataPipeline(ds, batch_size=n, mesh=mesh8)
    rloop = make_train_step(model, opt, mesh8, sched,
                            feed="resident", num_steps=K,
                            sample_shapes=pipe.sample_shapes)
    s_res, res_m = rloop(_copy(state), pipe.resident_data(), idx)

    assert int(s_res.step) == int(s_stream.step) == K
    np.testing.assert_array_equal(np.asarray(res_m["loss"]),
                                  np.asarray(stream_m["loss"]))
    np.testing.assert_array_equal(np.asarray(res_m["correct"]),
                                  np.asarray(stream_m["correct"]))
    for a, b in zip(
        jax.tree_util.tree_leaves(s_res.params),
        jax.tree_util.tree_leaves(s_stream.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("idx_shape", [(16,), (2, 8)],
                         ids=["batch", "microbatches"])
@pytest.mark.parametrize("arrays", [
    {"image": ((32, 32, 3), np.uint8), "label": ((), np.int32)},
    {"image": ((28, 28, 1), np.uint8), "label": ((), np.int32)},
    {"tokens": ((24,), np.int32)},
], ids=["images", "images-784", "tokens"])
def test_gather_rows_gives_the_rows_their_shape_back(arrays, idx_shape):
    """What the resident step gathers from flat rows is, in shape, dtype
    and every bit, what indexing the data set's own arrays gives."""
    from tpu_dp.train.step import gather_rows

    rng = np.random.default_rng(0)
    full = {k: rng.integers(0, 200, (40, *shape)).astype(dtype)
            for k, (shape, dtype) in arrays.items()}
    flat = {k: v.reshape(len(v), -1) if v.ndim > 2 else v
            for k, v in full.items()}
    idx = rng.integers(0, 40, idx_shape).astype(np.int32)
    shapes = {k: v.shape[1:] for k, v in full.items()}
    got = jax.jit(lambda d, i: gather_rows(d, i, shapes))(flat, idx)
    assert set(got) == set(full)
    for k, v in full.items():
        assert got[k].dtype == v.dtype
        assert got[k].shape == idx_shape + v.shape[1:]
        np.testing.assert_array_equal(np.asarray(got[k]), v[idx])


def test_resident_loop_with_accum(setup, mesh8):
    """Scan-of-scan over the resident feed: (window, accum, batch) indices."""
    from tpu_dp.data.pipeline import DataPipeline
    from tpu_dp.train import constant_lr

    model, opt, state = setup
    ds = make_synthetic(64, 10, seed=8, name="res")
    pipe = DataPipeline(ds, batch_size=16, mesh=mesh8, accum_steps=2)
    data = pipe.resident_data()

    ref = make_train_step(model, opt, mesh8, constant_lr(0.05), accum_steps=2)
    s_ref = _copy(state)
    for j in range(2):
        lo = j * 32
        s_ref, _ = ref(s_ref, {
            "image": normalize(ds.images[lo:lo + 32]).reshape(2, 16, 32, 32, 3),
            "label": ds.labels[lo:lo + 32].reshape(2, 16),
        })

    rloop = make_train_step(model, opt, mesh8, constant_lr(0.05),
                            feed="resident", num_steps=2, accum_steps=2,
                            sample_shapes=pipe.sample_shapes)
    idx = np.arange(64, dtype=np.int32).reshape(2, 2, 16)
    s_res, m = rloop(_copy(state), data, idx)

    assert int(s_res.step) == 2
    assert int(m["count"][0]) == 32
    for a, b in zip(
        jax.tree_util.tree_leaves(s_res.params),
        jax.tree_util.tree_leaves(s_ref.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_scanned_loop_modular_pool_matches_host_loop(setup, mesh8):
    """Pool-cycling branch (pool < num_steps) ≡ host loop cycling batches.

    This is the exact path bench.py measures (4-slot pool, 30-step window):
    the in-program modular gather must feed batch i % pool to step i.
    """
    from tpu_dp.train import cosine_lr

    model, opt, state = setup
    K, pool_n, n = 6, 3, 16
    sched = cosine_lr(0.05, 10, 2)
    step = make_train_step(model, opt, mesh8, sched)
    loop = make_train_step(model, opt, mesh8, sched,
                           feed="window", num_steps=K)

    batches = [_make_batch(200 + i, n) for i in range(pool_n)]
    pool = {
        "image": np.stack([b["image"] for b in batches]),
        "label": np.stack([b["label"] for b in batches]),
    }

    s_host = _copy(state)
    host_losses = []
    for i in range(K):
        s_host, m = step(s_host, batches[i % pool_n])
        host_losses.append(float(m["loss"]))

    s_scan, stacked = loop(_copy(state), pool)

    assert int(s_scan.step) == K
    np.testing.assert_allclose(
        np.asarray(stacked["loss"]), np.asarray(host_losses), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_scan.params),
        jax.tree_util.tree_leaves(s_host.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
