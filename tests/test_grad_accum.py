"""Gradient accumulation: accum_steps microbatches ≡ one big batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.config import Config
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.data.pipeline import DataPipeline
from tpu_dp.models import Net
from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step
from tpu_dp.train.trainer import Trainer


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def test_accum_equivalent_to_big_batch(mesh8):
    model, opt = Net(), SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    ds = make_synthetic(32, 10, seed=0, name="ga")
    imgs, labels = normalize(ds.images), ds.labels

    big = make_train_step(model, opt, mesh8, constant_lr(0.05))
    acc = make_train_step(model, opt, mesh8, constant_lr(0.05), accum_steps=4)

    s_big, m_big = big(_copy(state), {"image": imgs, "label": labels})
    s_acc, m_acc = acc(
        _copy(state),
        {
            "image": imgs.reshape(4, 8, 32, 32, 3),
            "label": labels.reshape(4, 8),
        },
    )
    # Equal microbatch sizes ⇒ mean-of-means == global mean: identical
    # update and identical metrics.
    assert float(m_acc["loss"]) == pytest.approx(float(m_big["loss"]), rel=1e-5)
    assert int(m_acc["correct"]) == int(m_big["correct"])
    assert int(m_acc["count"]) == int(m_big["count"]) == 32
    for a, b in zip(
        jax.tree_util.tree_leaves(s_acc.params),
        jax.tree_util.tree_leaves(s_big.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_pipeline_accum_grouping(mesh8):
    ds = make_synthetic(128, 10, seed=1, name="ga")
    pipe = DataPipeline(ds, batch_size=16, mesh=mesh8, accum_steps=2,
                        shuffle=False, prefetch=0)
    assert len(pipe) == 4  # 128 / (16·2)
    batches = list(pipe)
    assert len(batches) == 4
    for b in batches:
        assert b["image"].shape == (2, 16, 32, 32, 3)
        assert b["label"].shape == (2, 16)


def test_multi_step_composes_with_accum(mesh8):
    """Scan-of-scan: `make_train_step(feed="window", accum_steps=a)` ≡ sequential
    `make_train_step(accum_steps=a)` calls (VERDICT r4 next-steps #4)."""

    model, opt = Net(), SGD(momentum=0.9)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    ds = make_synthetic(64, 10, seed=0, name="ga")
    imgs, labels = normalize(ds.images), ds.labels
    # 2 windowed steps × 2 microbatches × batch 16.
    pool = {
        "image": imgs.reshape(2, 2, 16, 32, 32, 3),
        "label": labels.reshape(2, 2, 16),
    }

    per_step = make_train_step(model, opt, mesh8, constant_lr(0.05),
                               accum_steps=2)
    s_ref = _copy(state)
    losses = []
    for j in range(2):
        s_ref, m = per_step(
            s_ref,
            {"image": pool["image"][j], "label": pool["label"][j]},
        )
        losses.append(float(m["loss"]))

    loop = make_train_step(model, opt, mesh8, constant_lr(0.05),
                           feed="window", num_steps=2, accum_steps=2)
    s_win, stacked = loop(_copy(state), pool)

    assert int(s_win.step) == int(s_ref.step) == 2
    np.testing.assert_allclose(
        np.asarray(stacked["loss"]), np.asarray(losses), rtol=1e-5
    )
    assert int(stacked["count"][0]) == 32  # accum × batch per update
    for a, b in zip(
        jax.tree_util.tree_leaves(s_win.params),
        jax.tree_util.tree_leaves(s_ref.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_trainer_with_accum(tmp_path):
    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 128
    c.data.synthetic_test_size = 32
    c.data.batch_size = 16
    c.data.prefetch = 1
    c.optim.grad_accum_steps = 2
    c.optim.lr = 0.05
    c.train.epochs = 2
    c.train.ckpt_dir = str(tmp_path / "ck")
    result = Trainer(c).fit()
    assert result["history"][1]["loss"] < result["history"][0]["loss"]
