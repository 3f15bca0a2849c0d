"""Pallas fused cross-entropy vs the jnp reference path (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.ops.xent import mean_softmax_xent, softmax_xent
from tpu_dp.train.step import cross_entropy_loss


@pytest.mark.parametrize("b,c", [(16, 10), (300, 100), (256, 10)])
def test_forward_matches_jnp(b, c):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(b, c)).astype(np.float32) * 4)
    labels = jnp.asarray(rng.integers(0, c, size=b))
    per_ex = softmax_xent(logits, labels)
    assert per_ex.shape == (b,)
    expected = float(cross_entropy_loss(logits, labels))
    assert float(jnp.mean(per_ex)) == pytest.approx(expected, rel=1e-5)


def test_grad_matches_jnp():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(64, 10)).astype(np.float32) * 3)
    labels = jnp.asarray(rng.integers(0, 10, size=64))

    g_fused = jax.grad(lambda l: jnp.mean(softmax_xent(l, labels)))(logits)
    g_ref = jax.grad(lambda l: cross_entropy_loss(l, labels))(logits)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_weighted_mean_matches_reference():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(32, 10)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, size=32))
    weight = jnp.asarray((rng.uniform(size=32) > 0.3).astype(np.float32))
    fused = float(mean_softmax_xent(logits, labels, weight))
    ref = float(cross_entropy_loss(logits, labels, weight))
    assert fused == pytest.approx(ref, rel=1e-5)


def test_under_jit_and_nonaligned_batch():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(37, 10)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, size=37))
    f = jax.jit(lambda l, y: jnp.mean(softmax_xent(l, y)))
    assert float(f(logits, labels)) == pytest.approx(
        float(cross_entropy_loss(logits, labels)), rel=1e-5
    )


def test_batch_sharding_propagates_under_mesh(mesh8):
    """GSPMD must shard the kernel's rows over the mesh, not replicate it
    (the regression probe is the output sharding), and values must match
    the unsharded run — forward and backward."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(64, 10)).astype(np.float32) * 3)
    labels = jnp.asarray(rng.integers(0, 10, size=64))
    ls = jax.device_put(logits, NamedSharding(mesh8, P("data")))
    ys = jax.device_put(labels, NamedSharding(mesh8, P("data")))

    f = jax.jit(lambda l, y: softmax_xent(l, y))
    per_ex = f(ls, ys)
    assert per_ex.sharding.spec == P("data")
    np.testing.assert_allclose(np.asarray(per_ex),
                               np.asarray(softmax_xent(logits, labels)),
                               rtol=1e-6)

    g = jax.jit(jax.grad(lambda l, y: jnp.mean(softmax_xent(l, y))))
    gl = g(ls, ys)
    assert gl.sharding.spec[0] == "data"
    np.testing.assert_allclose(
        np.asarray(gl),
        np.asarray(jax.grad(lambda l: jnp.mean(softmax_xent(l, labels)))(
            logits)),
        rtol=1e-5, atol=1e-7)


def test_shard_map_step_with_pallas_xent(mesh8):
    """The explicit-collectives step with the Pallas loss: per-shard kernel
    under shard_map (jnp fallback in interpret mode) must match the GSPMD
    statement of the same program."""
    import numpy as np

    from tpu_dp.data.cifar import make_synthetic, normalize
    from tpu_dp.models import Net
    from tpu_dp.train import (
        SGD, constant_lr, create_train_state, make_train_step,
    )

    opt = SGD(momentum=0.9)
    ds = make_synthetic(16, 10, seed=0, name="xent_sm")
    batch = {"image": normalize(ds.images), "label": ds.labels}
    x0 = np.zeros((1, 32, 32, 3), np.float32)

    m_sm = Net()
    s_sm = create_train_state(m_sm, jax.random.PRNGKey(0), x0, opt)
    _, met_sm = make_train_step(
        m_sm, opt, mesh8, constant_lr(0.1), use_pallas_xent=True,
        explicit=True)(
        s_sm, dict(batch))

    m_g = Net()
    s_g = create_train_state(m_g, jax.random.PRNGKey(0), x0, opt)
    _, met_g = make_train_step(m_g, opt, mesh8, constant_lr(0.1),
                               use_pallas_xent=True)(s_g, dict(batch))
    assert float(met_sm["loss"]) == pytest.approx(float(met_g["loss"]),
                                                  rel=2e-4)


def test_kernel_off_the_tpu_without_the_request_raises(monkeypatch):
    """No silent interpret mode: on a backend that is not a TPU the kernel
    runs only inside `interpret_kernels()` (conftest's fixture, suspended
    here), and the refusal names the backend."""
    from tpu_dp.ops import _partition

    monkeypatch.setattr(_partition, "_interpret_requests", 0)
    logits = jnp.zeros((8, 10), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    with pytest.raises(RuntimeError, match=r"backend 'cpu'.*interpret_kernels"):
        softmax_xent(logits, labels)


def test_kernel_off_the_tpu_with_the_request_runs(monkeypatch):
    from tpu_dp.ops import _partition, interpret_kernels

    monkeypatch.setattr(_partition, "_interpret_requests", 0)
    logits = jnp.zeros((8, 10), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    with interpret_kernels():
        loss = softmax_xent(logits, labels)
    np.testing.assert_allclose(np.asarray(loss), np.log(10.0), rtol=1e-6)
    assert _partition._interpret_requests == 0  # the request ended with it
