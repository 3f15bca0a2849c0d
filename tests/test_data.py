"""Data pipeline tests: normalization parity, pipeline sharding, prefetch."""

import jax
import numpy as np
import pytest

from tpu_dp.data import ArrayDataset, DataPipeline, load_dataset
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.parallel import dist


def test_normalize_matches_reference_transform():
    """ToTensor + Normalize(0.5, 0.5) == x/255*2-1 (`cifar_example.py:38-40`)."""
    u8 = np.array([[0, 127, 255]], dtype=np.uint8)
    out = normalize(u8)
    np.testing.assert_allclose(out, [[-1.0, 127 / 255 * 2 - 1, 1.0]], atol=1e-6)


def test_synthetic_is_deterministic_and_separable():
    a = make_synthetic(100, 10, seed=5, name="s")
    b = make_synthetic(100, 10, seed=5, name="s")
    np.testing.assert_array_equal(a.images, b.images)
    np.testing.assert_array_equal(a.labels, b.labels)
    # Class templates differ: mean images of two classes are far apart.
    m0 = a.images[a.labels == a.labels[0]].mean(axis=0)
    other = a.labels[a.labels != a.labels[0]][0]
    m1 = a.images[a.labels == other].mean(axis=0)
    assert np.abs(m0.astype(np.float32) - m1.astype(np.float32)).mean() > 5


def test_load_dataset_synthetic_fallback(tmp_path):
    ds = load_dataset("cifar10", tmp_path, train=True, synthetic_num_examples=64)
    assert ds.synthetic and len(ds) == 64 and ds.num_classes == 10
    ds100 = load_dataset("cifar100", tmp_path, train=False,
                         synthetic_num_examples=32)
    assert ds100.num_classes == 100


def test_pipeline_shapes_and_epoch(mesh8):
    ds = make_synthetic(100, 10, seed=0, name="s")
    pipe = DataPipeline(ds, batch_size=16, mesh=mesh8, seed=0, prefetch=2)
    assert len(pipe) == 6  # 100 // 16 with drop_remainder
    batches = list(pipe)
    assert len(batches) == 6
    for b in batches:
        assert b["image"].shape == (16, 32, 32, 3)
        assert b["label"].shape == (16,)
        # Default pipeline ships uint8; the compiled step normalizes on
        # device (4x less host->HBM traffic).
        assert b["image"].dtype == np.uint8
        # Sharded over the data axis of the mesh.
        assert b["image"].sharding.spec[0] == dist.DATA_AXIS

    pipe.set_epoch(0)
    first = next(iter(pipe))
    pipe.set_epoch(1)
    second = next(iter(pipe))
    assert not np.allclose(np.asarray(first["image"]), np.asarray(second["image"]))


def test_pipeline_no_prefetch_matches_prefetch(mesh8):
    ds = make_synthetic(64, 10, seed=2, name="s")
    p0 = DataPipeline(ds, 16, mesh8, shuffle=False, prefetch=0)
    p2 = DataPipeline(ds, 16, mesh8, shuffle=False, prefetch=2)
    for a, b in zip(p0, p2):
        np.testing.assert_array_equal(np.asarray(a["image"]), np.asarray(b["image"]))
        np.testing.assert_array_equal(np.asarray(a["label"]), np.asarray(b["label"]))


def test_cifar10_pickle_format_roundtrip(tmp_path):
    """Write the standard CIFAR-10 batch layout and load it back."""
    import pickle

    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()
    rng = np.random.default_rng(0)
    for i in range(1, 6):
        data = rng.integers(0, 256, size=(20, 3072), dtype=np.int64).astype(np.uint8)
        with open(d / f"data_batch_{i}", "wb") as f:
            pickle.dump({b"data": data, b"labels": list(rng.integers(0, 10, 20))}, f)
    ds = load_dataset("cifar10", tmp_path, train=True)
    assert not ds.synthetic
    assert ds.images.shape == (100, 32, 32, 3)


def test_device_normalize_equals_host_normalize(mesh8):
    """uint8-to-device + in-step normalize ≡ host normalize (same training)."""
    from tpu_dp.models import Net
    from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

    ds = make_synthetic(32, 10, seed=3, name="dn")
    model, opt = Net(), SGD(0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step = make_train_step(model, opt, mesh8, constant_lr(0.05))

    def _copy(s):
        import jax.numpy as jnp

        return jax.tree_util.tree_map(jnp.array, s)

    s_u8, m_u8 = step(_copy(state), {"image": ds.images, "label": ds.labels})
    s_f32, m_f32 = step(
        _copy(state), {"image": normalize(ds.images), "label": ds.labels}
    )
    assert float(m_u8["loss"]) == pytest.approx(float(m_f32["loss"]), rel=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_u8.params),
        jax.tree_util.tree_leaves(s_f32.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_partial_batch_weight_mask(mesh8):
    """Eval pipeline: final partial batch is padded with a zeroing mask."""
    ds = make_synthetic(40, 10, seed=4, name="pw")
    pipe = DataPipeline(ds, 32, mesh8, shuffle=False, drop_remainder=False,
                        prefetch=0)
    batches = list(pipe)
    assert len(batches) == 2
    assert "weight" not in batches[0]
    w = np.asarray(batches[1]["weight"])
    assert batches[1]["image"].shape == (32, 32, 32, 3)
    assert w.sum() == 8 and (w[:8] == 1).all() and (w[8:] == 0).all()


def test_partial_batch_pad_exceeding_shard(mesh8):
    """Pad larger than the shard itself must tile the shard (np.resize)."""
    ds = make_synthetic(8, 10, seed=5, name="tiny")
    pipe = DataPipeline(ds, 24, mesh8, shuffle=False, drop_remainder=False,
                        prefetch=0)
    (b,) = list(pipe)
    assert b["image"].shape == (24, 32, 32, 3)
    assert np.asarray(b["weight"]).sum() == 8


def test_accum_requires_drop_remainder(mesh8):
    ds = make_synthetic(64, 10, seed=6, name="ar")
    with pytest.raises(ValueError, match="drop_remainder"):
        DataPipeline(ds, 16, mesh8, accum_steps=2, drop_remainder=False)


def test_pipeline_windows_grouping(mesh8):
    """windows(k): full k-stacks then per-step singles for the remainder."""
    from tpu_dp.data.cifar import make_synthetic
    from tpu_dp.data.pipeline import DataPipeline

    ds = make_synthetic(9 * 16, 10, seed=0, name="synthetic")
    pipe = DataPipeline(ds, 16, mesh8, shuffle=False, prefetch=1)
    items = list(pipe.windows(4))
    assert [n for n, _ in items] == [4, 4, 1]
    pool = items[0][1]
    assert pool["image"].shape == (4, 16, 32, 32, 3)
    single = items[2][1]
    assert single["image"].shape == (16, 32, 32, 3)
    # Coverage: stacked + single batches reproduce the plain iteration order.
    import numpy as np

    plain = [np.asarray(b["label"]) for b in pipe]
    windowed = []
    for n, item in items:
        lab = np.asarray(item["label"])
        windowed.extend(lab[j] for j in range(n)) if n > 1 else windowed.append(lab)
    np.testing.assert_array_equal(np.concatenate(plain),
                                  np.concatenate(windowed))

    with pytest.raises(ValueError):
        list(DataPipeline(ds, 16, mesh8, shuffle=False,
                          drop_remainder=False).windows(4))


def test_index_windows_match_windows(mesh8):
    """index_windows(k) names exactly the examples windows(k) ships.

    Gathering the resident dataset with the yielded indices must reproduce
    the streaming windows' labels, window for window — the resident path's
    ordering contract.
    """
    from tpu_dp.data.pipeline import DataPipeline

    ds = make_synthetic(9 * 16, 10, seed=0, name="synthetic")
    pipe = DataPipeline(ds, 16, mesh8, shuffle=True, seed=3, prefetch=0)
    pipe.set_epoch(1)
    streamed = [(n, np.asarray(item["label"]))
                for n, item in pipe.windows(4)]
    pipe.set_epoch(1)  # same epoch permutation for the index pass
    indexed = list(pipe.index_windows(4))

    assert [n for n, _ in indexed] == [n for n, _ in streamed] == [4, 4, 1]
    for (n, labels), (_, idx) in zip(streamed, indexed):
        idx = np.asarray(idx)
        assert idx.dtype == np.int32
        assert idx.shape == (n, 16)
        gathered = ds.labels[idx]
        np.testing.assert_array_equal(
            labels if n > 1 else labels[None], gathered
        )

    with pytest.raises(ValueError):
        DataPipeline(ds, 16, mesh8, shuffle=False,
                     drop_remainder=False).index_windows(4)


def _dataset_of(kind):
    from tpu_dp.data.cifar import ArrayDataset
    from tpu_dp.data.tokens import make_synthetic_tokens

    if kind == "tokens":
        return make_synthetic_tokens(32, 24, 50, seed=0)
    if kind == "cifar":
        return make_synthetic(32, 10, seed=0, name="synthetic")
    rng = np.random.default_rng(0)
    return ArrayDataset(
        images=rng.integers(0, 256, (32, 28, 28, 1), dtype=np.uint8),
        labels=rng.integers(0, 10, 32).astype(np.int32),
        name="mnist-like", num_classes=10, synthetic=True)


@pytest.mark.parametrize("kind,shapes", [
    ("cifar", {"image": (32, 3072), "label": (32,)}),
    ("mnist-like", {"image": (32, 784), "label": (32,)}),
    ("tokens", {"tokens": (32, 24)}),
])
def test_resident_data_stages_rows_flat(mesh8, kind, shapes):
    """Every staged array has rank at most 2 (a 4-D uint8 argument is
    N-minor on the chip, and a gather along it relays the whole data set
    out, PERF.md PR 29), the rows' bytes and dtype are the data set's, and
    `sample_shapes` says what the step reshapes a gathered row to."""
    from tpu_dp.data.pipeline import DataPipeline

    ds = _dataset_of(kind)
    pipe = DataPipeline(ds, 16, mesh8)
    staged = pipe.resident_data()
    assert {k: v.shape for k, v in staged.items()} == shapes
    assert pipe.sample_shapes == {k: v.shape[1:]
                                  for k, v in ds.arrays.items()}
    for k, v in ds.arrays.items():
        assert staged[k].dtype == v.dtype
        assert staged[k].sharding.is_fully_replicated
        np.testing.assert_array_equal(
            np.asarray(staged[k]).reshape(v.shape), v)


def test_index_windows_accum_shape(mesh8):
    from tpu_dp.data.pipeline import DataPipeline

    ds = make_synthetic(128, 10, seed=0, name="synthetic")
    pipe = DataPipeline(ds, 16, mesh8, shuffle=False, prefetch=0,
                        accum_steps=2)
    items = list(pipe.index_windows(2))  # 4 updates → 2 windows of 2
    assert [n for n, _ in items] == [2, 2]
    assert items[0][1].shape == (2, 2, 16)  # (window, accum, batch)
    flat = np.concatenate([np.asarray(i).ravel() for _, i in items])
    np.testing.assert_array_equal(flat, np.arange(128, dtype=np.int32))
