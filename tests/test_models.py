"""Model unit tests — shapes and parameter counts vs the reference spec.

SURVEY.md §4 Unit: "model forward shapes/param counts vs `Net` spec
(`cifar_example.py:20-25`: conv 3→6→16, fc 400→120→84→10)".
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.models import Net, ResNet18, ResNet50, build_model


def _param_count(params):
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def test_net_output_shape_and_param_count():
    model = Net()
    x = np.zeros((4, 32, 32, 3), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    logits = model.apply(variables, x)
    assert logits.shape == (4, 10)
    # Exact torch parity: conv1 456 + conv2 2416 + fc1 48120 + fc2 10164
    # + fc3 850 = 62006 (`cifar_example.py:20-25`).
    assert _param_count(variables["params"]) == 62_006


def test_net_layer_shapes():
    model = Net()
    x = np.zeros((2, 32, 32, 3), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    p = variables["params"]
    assert p["conv1"]["kernel"].shape == (5, 5, 3, 6)
    assert p["conv2"]["kernel"].shape == (5, 5, 6, 16)
    assert p["fc1"]["kernel"].shape == (400, 120)  # 16·5·5 = 400
    assert p["fc2"]["kernel"].shape == (120, 84)
    assert p["fc3"]["kernel"].shape == (84, 10)


@pytest.mark.parametrize("factory,expected_min", [(ResNet18, 11e6)])
def test_resnet18_forward(factory, expected_min):
    model = factory(num_classes=10)
    x = np.zeros((2, 32, 32, 3), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    # CIFAR ResNet-18 ≈ 11.17M params.
    n = _param_count(variables["params"])
    assert expected_min < n < 12e6
    assert "batch_stats" in variables


def test_resnet50_builds():
    model = build_model("resnet50", num_classes=100)
    x = np.zeros((1, 32, 32, 3), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (1, 100)


def test_resnet50_train_step_tiny(mesh1):
    """One DP train step through the bottleneck blocks (BASELINE config 3's
    model): pins the 1x1-reduce/3x3/1x1-expand backward path, the
    shape-triggered projection shortcuts, and the zero-init residual BN
    scale under jit — at tiny widths so CPU compile stays fast. Forward
    alone (test_resnet50_builds) would miss a broken custom-VJP or
    BN-stat plumbing in the blocks."""
    from tpu_dp.data.cifar import make_synthetic, normalize
    from tpu_dp.train import SGD, constant_lr, create_train_state, make_train_step

    mesh = mesh1
    model = build_model("resnet50", num_classes=100, num_filters=8)
    opt = SGD(momentum=0.9, weight_decay=5e-4)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32), opt
    )
    step = make_train_step(model, opt, mesh, constant_lr(0.1))
    ds = make_synthetic(8, 100, seed=0, name="r50")
    state, m = step(state, {"image": normalize(ds.images), "label": ds.labels})
    assert int(state.step) == 1
    assert np.isfinite(float(m["loss"])) and float(m["loss"]) > 0
    assert int(m["count"]) == 8


def test_net_bf16_compute():
    model = Net(dtype=jnp.bfloat16)
    x = np.zeros((2, 32, 32, 3), np.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    # Params stay f32; logits come back f32 (final dense computes in f32).
    kinds = {x.dtype for x in jax.tree_util.tree_leaves(variables["params"])}
    assert kinds == {np.dtype(np.float32)}
    assert model.apply(variables, x).dtype == jnp.float32


class TestFusedResNet:
    """Fused Pallas-block ResNet ≡ the standard one (tpu_dp/ops/conv_block).

    The fused model must be a pure execution-strategy change: identical
    parameter tree (checkpoint-interchangeable), bit-identical eval
    forward, train forward within bf16 rounding, and a working train step.
    """

    def _models(self, fused_stages, **kw):
        m0 = build_model("resnet18", num_classes=10, dtype=jnp.bfloat16, **kw)
        m1 = build_model("resnet18", num_classes=10, dtype=jnp.bfloat16,
                         fused_stages=fused_stages, fused_block_b=4, **kw)
        return m0, m1

    def test_param_trees_and_init_identical(self):
        m0, m1 = self._models((0,))
        x = np.zeros((2, 32, 32, 3), np.float32)
        v0 = m0.init(jax.random.PRNGKey(0), x, train=False)
        v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
        assert (jax.tree_util.tree_structure(v0)
                == jax.tree_util.tree_structure(v1))
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), v0, v1))

    @pytest.mark.parametrize("fused_stages", [(0,), (0, 1, 2, 3)])
    def test_forward_equivalence(self, fused_stages):
        m0, m1 = self._models(fused_stages)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3),
                              jnp.float32)
        v = m0.init(jax.random.PRNGKey(0), x, train=False)
        # Eval mode: affine from running stats — must agree to bf16 exactness.
        ye0 = m0.apply(v, x, train=False)
        ye1 = m1.apply(v, x, train=False)
        np.testing.assert_allclose(np.asarray(ye0, np.float32),
                                   np.asarray(ye1, np.float32), atol=1e-6)
        # Train mode: batch-stats path, bf16-rounding-level agreement.
        y0, s0 = m0.apply(v, x, train=True, mutable=["batch_stats"])
        y1, s1 = m1.apply(v, x, train=True, mutable=["batch_stats"])
        scale = float(jnp.abs(y0).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(y0, np.float32) / scale,
                                   np.asarray(y1, np.float32) / scale,
                                   atol=5e-3)
        for d in jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda a, b: float(jnp.abs(a - b).max()), s0, s1)):
            assert d < 5e-3

    def test_fused_bwd_grads_match_default(self):
        # fused_bwd changes only the backward execution path: gradients of
        # the same loss must agree with the XLA-backward fused model.
        from tpu_dp.train.step import cross_entropy_loss

        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3),
                              jnp.float32)
        labels = jnp.array([0, 1, 2, 3])
        kw = dict(num_classes=10, num_filters=16, dtype=jnp.bfloat16,
                  fused_stages=(0,), fused_block_b=2)
        m0 = build_model("resnet18", **kw)
        m1 = build_model("resnet18", fused_bwd=True, **kw)
        v = m0.init(jax.random.PRNGKey(0), x, train=False)

        def loss(model, params):
            out, _ = model.apply(
                {"params": params, "batch_stats": v["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return cross_entropy_loss(out, labels)

        g0 = jax.grad(lambda p: loss(m0, p))(v["params"])
        g1 = jax.grad(lambda p: loss(m1, p))(v["params"])
        for a, b in zip(jax.tree_util.tree_leaves(g0),
                        jax.tree_util.tree_leaves(g1)):
            m = float(jnp.abs(a).max()) + 1e-6
            np.testing.assert_allclose(np.asarray(a, np.float32) / m,
                                       np.asarray(b, np.float32) / m,
                                       atol=2e-2)

    def test_fused_train_step(self, mesh1):
        from tpu_dp.data.cifar import make_synthetic, normalize
        from tpu_dp.train import (
            SGD, constant_lr, create_train_state, make_train_step,
        )

        model = build_model("resnet18", num_classes=10, num_filters=64,
                            dtype=jnp.bfloat16, fused_stages=(0,),
                            fused_block_b=4)
        opt = SGD(momentum=0.9, weight_decay=5e-4)
        state = create_train_state(
            model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32),
            opt)
        step = make_train_step(model, opt, mesh1, constant_lr(0.1))
        ds = make_synthetic(8, 10, seed=0, name="fused")
        state, m = step(state, {"image": normalize(ds.images),
                                "label": ds.labels})
        assert int(state.step) == 1
        assert np.isfinite(float(m["loss"])) and float(m["loss"]) > 0

    def test_resnet50_fused_bottleneck_equivalence(self):
        """ResNet-50's stride-1 bottlenecks run their middle 3x3 on the
        kernel: same param tree (checkpoint-interchangeable), bit-identical
        eval forward, train forward within bf16 rounding."""
        kw = dict(num_classes=100, num_filters=16, dtype=jnp.bfloat16)
        m0 = build_model("resnet50", **kw)
        m1 = build_model("resnet50", fused_stages=(0, 1, 2, 3),
                         fused_block_b=2, **kw)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3),
                              jnp.float32)
        v0 = m0.init(jax.random.PRNGKey(0), x, train=False)
        v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
        assert (jax.tree_util.tree_structure(v0)
                == jax.tree_util.tree_structure(v1))
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), v0, v1))
        ye0 = m0.apply(v0, x, train=False)
        ye1 = m1.apply(v0, x, train=False)
        np.testing.assert_allclose(np.asarray(ye0, np.float32),
                                   np.asarray(ye1, np.float32), atol=1e-6)
        y0, st0 = m0.apply(v0, x, train=True, mutable=["batch_stats"])
        y1, st1 = m1.apply(v0, x, train=True, mutable=["batch_stats"])
        s = float(jnp.abs(y0).max()) + 1e-6
        np.testing.assert_allclose(np.asarray(y0, np.float32) / s,
                                   np.asarray(y1, np.float32) / s,
                                   atol=5e-3)
        # Running-stat updates (incl. BatchNorm_1 fed by kernel-emitted
        # moments) must track the unfused model too.
        for d in jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda a, b: float(jnp.abs(a - b).max()), st0, st1)):
            assert d < 5e-3

    def test_parse_fused_stages(self):
        from tpu_dp.models import parse_fused_stages

        assert parse_fused_stages("") == ()
        assert parse_fused_stages(None) == ()
        assert parse_fused_stages("all") == (0, 1, 2, 3)
        assert parse_fused_stages("0") == (0,)
        assert parse_fused_stages("2,0") == (0, 2)
        with pytest.raises(ValueError):
            parse_fused_stages("one")

    def test_fused_shard_map_step_matches_gspmd(self, mesh8):
        """Both distributed statements of the fused model agree: the
        explicit shard_map step (per-shard kernel + lax.pmean) and the
        GSPMD step (custom_partitioning shards the batch dim)."""
        from tpu_dp.data.cifar import make_synthetic, normalize
        from tpu_dp.parallel import dist
        from tpu_dp.train import (
            SGD, constant_lr, create_train_state, make_train_step,
        )

        opt = SGD(momentum=0.9)
        ds = make_synthetic(16, 10, seed=0, name="fused_sm")
        batch = {"image": normalize(ds.images), "label": ds.labels}
        x0 = np.zeros((1, 32, 32, 3), np.float32)

        mf = build_model("resnet18", num_classes=10, dtype=jnp.bfloat16,
                         fused_stages=(0,), fused_block_b=2,
                         axis_name=dist.DATA_AXIS)
        sf = create_train_state(mf, jax.random.PRNGKey(0), x0, opt)
        _, m_sm = make_train_step(mf, opt, mesh8, constant_lr(0.1),
                                  explicit=True)(
            sf, dict(batch))

        mg = build_model("resnet18", num_classes=10, dtype=jnp.bfloat16,
                         fused_stages=(0,), fused_block_b=2)
        sg = create_train_state(mg, jax.random.PRNGKey(0), x0, opt)
        _, m_g = make_train_step(mg, opt, mesh8, constant_lr(0.1))(
            sg, dict(batch))

        # rel 2e-4 (~3x the observed 7e-5), not exactness: the two programs
        # differ structurally (shard_map's interpret fallback runs the
        # unfused XLA statement, GSPMD runs the emit kernel), so XLA may
        # reassociate the f32 BN-stat reductions differently — compile-order
        # rounding, verified bit-identical in eager forward.
        assert float(m_sm["loss"]) == pytest.approx(float(m_g["loss"]),
                                                    rel=2e-4)

    def test_checkpoint_interchangeable_unfused_to_fused(self, tmp_path,
                                                         mesh1):
        """The interchangeability claim end to end: a checkpoint saved from
        an UNFUSED run restores into a FUSED model (and trains a step) —
        the fused path is an execution strategy, not a different model."""
        from tpu_dp.checkpoint import load_checkpoint, save_checkpoint
        from tpu_dp.data.cifar import make_synthetic, normalize
        from tpu_dp.train import (
            SGD, constant_lr, create_train_state, make_train_step,
        )

        mesh = mesh1
        opt = SGD(momentum=0.9)
        x0 = np.zeros((1, 32, 32, 3), np.float32)
        ds = make_synthetic(8, 10, seed=0, name="ckpt_x")
        batch = {"image": normalize(ds.images), "label": ds.labels}

        m0 = build_model("resnet18", num_classes=10, num_filters=16,
                         dtype=jnp.bfloat16)
        s0 = create_train_state(m0, jax.random.PRNGKey(0), x0, opt)
        s0, _ = make_train_step(m0, opt, mesh, constant_lr(0.1))(
            s0, dict(batch))
        save_checkpoint(tmp_path, s0, {"step": 1})

        m1 = build_model("resnet18", num_classes=10, num_filters=16,
                         dtype=jnp.bfloat16, fused_stages=(0,),
                         fused_block_b=2)
        s1 = create_train_state(m1, jax.random.PRNGKey(7), x0, opt)
        restored, meta = load_checkpoint(tmp_path, s1)
        assert meta["step"] == 1
        # Bit-identical restore of the unfused run's FULL state (params,
        # momentum buffers, batch_stats, step) into the fused model's tree.
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            restored, jax.device_get(s0))
        # ...and the fused model trains from it.
        restored = jax.tree_util.tree_map(jnp.asarray, restored)
        s2, metrics = make_train_step(m1, opt, mesh, constant_lr(0.1))(
            restored, dict(batch))
        assert int(s2.step) == 2
        assert np.isfinite(float(metrics["loss"]))

    def test_resnet50_fused_train_step_mesh8(self, mesh8):
        """Fused bottlenecks under the 8-device GSPMD mesh: the kernel's
        partitioning (incl. the stats psum) must compose with the sharded
        train step."""
        from tpu_dp.data.cifar import make_synthetic, normalize
        from tpu_dp.train import (
            SGD, constant_lr, create_train_state, make_train_step,
        )

        model = build_model("resnet50", num_classes=100, num_filters=8,
                            dtype=jnp.bfloat16, fused_stages=(0,),
                            fused_block_b=2)
        opt = SGD(momentum=0.9)
        state = create_train_state(
            model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3),
                                                   np.float32), opt)
        step = make_train_step(model, opt, mesh8, constant_lr(0.1))
        ds = make_synthetic(16, 100, seed=0, name="r50_mesh")
        state, m = step(state, {"image": normalize(ds.images),
                                "label": ds.labels})
        assert int(state.step) == 1
        assert np.isfinite(float(m["loss"])) and float(m["loss"]) > 0
        assert int(m["count"]) == 16

    @pytest.mark.slow
    def test_fused_training_trajectory_tracks_unfused(self, mesh1):
        """24 optimizer steps, same data order: the fused-all model's loss
        trajectory must track the unfused one closely at every step — a
        slow-bias bug (e.g. subtly wrong kernel-emitted stat normalization)
        would compound here while staying invisible to single-step tests."""
        from tpu_dp.data.cifar import make_synthetic, normalize
        from tpu_dp.train import (
            SGD, constant_lr, create_train_state, make_train_step,
        )

        opt = SGD(momentum=0.9)
        ds = make_synthetic(256, 10, seed=0, name="traj")
        imgs = normalize(ds.images)
        labels = ds.labels
        x0 = np.zeros((1, 32, 32, 3), np.float32)

        def run(fused):
            kw = dict(num_classes=10, num_filters=16, dtype=jnp.bfloat16)
            if fused:
                kw.update(fused_stages=(0, 1, 2, 3))
            m = build_model("resnet18", **kw)
            s = create_train_state(m, jax.random.PRNGKey(0), x0, opt)
            step = make_train_step(m, opt, mesh1, constant_lr(0.05))
            losses = []
            for i in range(24):
                lo = (i * 32) % 256
                s, met = step(s, {"image": imgs[lo:lo + 32],
                                  "label": labels[lo:lo + 32]})
                losses.append(float(met["loss"]))
            return losses

        l0 = run(False)
        l1 = run(True)
        assert l0[-1] < 0.5 and l1[-1] < 0.5  # both actually converge
        for i, (a, b) in enumerate(zip(l0, l1)):
            assert abs(a - b) < 0.05, f"step {i}: {a} vs {b}"
