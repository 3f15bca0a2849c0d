"""A token model through the shared trainer: AdamW, the token data set, and
`Trainer.train_epoch` against the plain reference's steps.

(d) `tpu_dp.train.optim.AdamW` against `benchmark/reference_sdar.py`'s over
five steps, and sharded against replicated on the 8-device CPU mesh, with
the checkpoint carrying its state from one layout to the other; (e) three
steps through `Trainer` (resident and streamed feed) reproduce the
reference's losses, and a run that checkpoints and resumes takes the same
fourth step; (f) the token data set through the sampler: every row once an
epoch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_sdar_model import LENGTH, MODEL, SHAPES, VOCAB, ref
from tpu_dp import checkpoint as ckpt_lib
from tpu_dp.config import Config, parse_cli
from tpu_dp.data import DataPipeline, TokenDataset, make_synthetic_tokens
from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.obs import counters as global_counters
from tpu_dp.train import (
    constant_lr,
    create_train_state,
    make_train_step,
    shard_optimizer,
)
from tpu_dp.train.hooks import StepHook
from tpu_dp.train.optim import AdamW
from tpu_dp.train.trainer import Trainer

OPT = {"name": "adamw", "lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0}


def adamw():
    return AdamW(OPT["b1"], OPT["b2"], OPT["eps"],
                 weight_decay=OPT["weight_decay"], clip_norm=OPT["clip_norm"],
                 decay_exclude_bias_and_norm=True)


# ------------------------------------------------------------------ (d) AdamW

def test_adamw_follows_the_reference_over_five_steps():
    rng = np.random.default_rng(0)
    params = {"dense": {"kernel": jnp.asarray(rng.normal(size=(6, 5)),
                                              jnp.float32)},
              "norm": {"scale": jnp.ones((5,), jnp.float32)}}
    # the reference updates its trees in place: it gets copies
    ours, theirs = params, jax.tree_util.tree_map(jnp.array, params)
    opt, state, ref_state = adamw(), adamw().init(params), ref.adamw_init(params)
    update = jax.jit(opt.update)
    for k in range(5):
        # the first gradients are large (clipped), the later ones are not
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32)
            * (3.0 if k < 2 else 0.05), params)
        ours, state = update(grads, state, ours, OPT["lr"])
        theirs, ref_state, clipped = ref.adamw_update(
            theirs, grads, ref_state, OPT["lr"], OPT)
        if k == 0:
            # what the benchmark reads back as the first gradient
            np.testing.assert_allclose(
                state["m"]["dense"]["kernel"] / (1 - OPT["b1"]),
                clipped["dense"]["kernel"], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves((ours, state["m"], state["v"])),
                    jax.tree_util.tree_leaves(
                        (theirs, ref_state["m"], ref_state["v"]))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert int(state["count"]) == 5
    # the decay spares the norm's weight and not the matrix
    still = opt.update(jax.tree_util.tree_map(jnp.zeros_like, params),
                       opt.init(params), params, 1.0)[0]
    assert np.array_equal(still["norm"]["scale"], params["norm"]["scale"])
    np.testing.assert_allclose(still["dense"]["kernel"],
                               0.9 * params["dense"]["kernel"], rtol=1e-6)


def _image_batch(seed, n):
    ds = make_synthetic(n, 10, seed=seed, name="synthetic")
    return {"image": normalize(ds.images), "label": ds.labels}


def test_adamw_sharded_is_adamw_replicated_and_the_checkpoint_carries_it(
        mesh8, tmp_path):
    """Through the step factories on 8 devices: the sharded update (1/8 of
    every leaf a replica, the clip's norm summed over shards) takes the
    replicated update's steps, and its state, saved, loads back onto the
    replicated layout and onto the sharded one again."""
    model, sample = Net(), np.zeros((1, 32, 32, 3), np.float32)
    opt, sopt = adamw(), shard_optimizer(adamw(), 8)
    rng = jax.random.PRNGKey(0)
    state_r = create_train_state(model, rng, sample, opt)
    state_s = create_train_state(model, rng, sample, sopt)
    step_r = make_train_step(model, opt, mesh8, constant_lr(1e-2))
    step_s = make_train_step(model, sopt, mesh8, constant_lr(1e-2),
                             update_sharding="sharded")
    for k in range(3):
        batch = _image_batch(k, 64)
        state_r, m_r = step_r(state_r, batch)
        state_s, m_s = step_s(state_s, batch)
        assert float(m_s["loss"]) == pytest.approx(float(m_r["loss"]),
                                                   rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(state_s.params),
                    jax.tree_util.tree_leaves(state_r.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # a replica holds an eighth of every moment; all copies of the count agree
    assert np.all(np.asarray(state_s.opt_state["count"]) == 3)
    for s, r in zip(jax.tree_util.tree_leaves(state_s.opt_state["v"]),
                    jax.tree_util.tree_leaves(state_r.opt_state["v"])):
        assert s.ndim == 1 and s.size == r.size + (-r.size) % 8
        np.testing.assert_allclose(np.asarray(s)[:r.size].reshape(r.shape), r,
                                   rtol=1e-4, atol=1e-9)

    ckpt_lib.save_checkpoint(tmp_path / "ck", state_s, {"epoch": 0})
    fresh_r = create_train_state(model, rng, sample, opt)
    fresh_s = create_train_state(model, rng, sample, sopt)
    back_r, _ = ckpt_lib.load_checkpoint(tmp_path / "ck", fresh_r)
    back_s, _ = ckpt_lib.load_checkpoint(tmp_path / "ck", fresh_s)
    assert int(back_r.opt_state["count"]) == 3
    # a fourth step from either layout is the same step
    batch = _image_batch(3, 64)
    next_r, m_r = step_r(back_r, batch)
    next_s, m_s = step_s(back_s, batch)
    assert float(m_s["loss"]) == pytest.approx(float(m_r["loss"]), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(next_s.params),
                    jax.tree_util.tree_leaves(next_r.params)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # the replicated layout's scalar count lands in one replica's copy
    # only; the sharded update takes the largest and goes on counting
    ckpt_lib.save_checkpoint(tmp_path / "ck_r", next_r, {"epoch": 0})
    from_r, _ = ckpt_lib.load_checkpoint(tmp_path / "ck_r", fresh_s)
    after, _ = step_s(from_r, batch)
    assert np.all(np.asarray(after.opt_state["count"]) == 5)


# ------------------------------------------------- (e) through the Trainer

ROWS_A_STEP, STEPS_AN_EPOCH = 4, 3


def tiny_argv(tmp_path, **overrides) -> list[str]:
    argv = ["--preset=sdar_30b_a3b_ep8", f"--model.num_classes={VOCAB}",
            "--model.bf16=false", f"--data.seq_len={LENGTH}",
            f"--data.batch_size={ROWS_A_STEP}",
            f"--data.synthetic_train_size={ROWS_A_STEP * STEPS_AN_EPOCH}",
            "--data.synthetic_test_size=6", "--optim.lr=0.001",
            "--parallel.num_devices=1", "--train.epochs=1",
            f"--train.ckpt_dir={tmp_path / 'ck'}",
            "--resilience.handle_signals=false"]
    argv += [f"--model.{k}={v}" for k, v in SHAPES.items()]
    return argv + [f"--{k}={v}" for k, v in overrides.items()]


def tiny_cfg(tmp_path, **overrides) -> Config:
    return parse_cli(tiny_argv(tmp_path, **overrides))


def token_sets(seed=3):
    return (make_synthetic_tokens(ROWS_A_STEP * STEPS_AN_EPOCH, LENGTH, VOCAB,
                                  seed=seed, example_seed=1),
            make_synthetic_tokens(6, LENGTH, VOCAB, seed=seed, example_seed=2))


class Losses(StepHook):
    def __init__(self, trainer):
        super().__init__(trainer)
        self.seen = []

    def on_step_end(self, ev):
        self.seen += [m["loss"] for m in ev.window]

    def values(self):
        return [float(v) for v in self.seen]


def reference_losses(params0, tokens, steps, seed=0):
    """The plain reference over ``steps`` steps of the sampler's rows."""
    n = len(tokens)
    batches = []
    for k in range(steps):
        epoch, step = divmod(k, STEPS_AN_EPOCH)
        order = np.random.default_rng([seed, epoch]).permutation(n)
        batches.append(jnp.asarray(
            tokens[order[step * ROWS_A_STEP:(step + 1) * ROWS_A_STEP]]))
    opt = {**OPT, "lr": 0.001}
    return ref.follow(MODEL, opt,
                      lambda: jax.tree_util.tree_map(jnp.array, params0),
                      batches, noise_seed=seed + 1)


@pytest.fixture(autouse=True)
def _isolate_global_counters():
    saved = dict(global_counters._counts), dict(global_counters._gauges)
    global_counters.reset()
    yield
    global_counters.reset()
    global_counters._counts.update(saved[0])
    global_counters._gauges.update(saved[1])


@pytest.mark.parametrize("feed", ["auto", "off"])
def test_three_steps_through_the_trainer_are_the_references(tmp_path, feed):
    train, test = token_sets()
    trainer = Trainer(tiny_cfg(tmp_path, **{"data.device_resident": feed}),
                      datasets=(train, test))
    assert (trainer.resident_train is not None) == (feed == "auto")
    params0 = jax.tree_util.tree_map(jnp.array, trainer.state.params)
    losses = Losses(trainer)
    trainer.add_hook(losses)
    stats = trainer.train_epoch(0)
    want = reference_losses(params0, train.tokens, STEPS_AN_EPOCH)
    assert losses.values() == pytest.approx(want["loss"], rel=2e-5)
    assert stats["loss"] == pytest.approx(np.mean(want["loss"]), rel=2e-5)
    # AdamW moves an element by lr * m / sqrt(v): where a gradient element
    # is all but zero, rounding decides its share of the 1e-3 a step
    for a, b in zip(jax.tree_util.tree_leaves(trainer.state.params),
                    jax.tree_util.tree_leaves(want["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # the epoch's counters, published at its fence; accuracy is the share
    # of the masked tokens predicted right
    counts = global_counters.snapshot()
    tokens = STEPS_AN_EPOCH * ROWS_A_STEP * LENGTH
    assert counts["diffusion.tokens"] == tokens
    assert 0 < counts["diffusion.masked_tokens"] < tokens
    assert counts["moe.assignments"] == 2 * tokens * 2 * 2
    assert counts["moe.assignments_dropped"] == 0
    assert stats["accuracy"] == pytest.approx(
        sum(want["hits"]) / counts["diffusion.masked_tokens"])
    # evaluation: the same loss on a fixed noise key, in the same slots
    first, again = trainer.evaluate(), trainer.evaluate()
    assert first == again and first["loss"] > 0 and 0 <= first["accuracy"] <= 1


def test_checkpoint_and_resume_take_the_same_fourth_step(tmp_path):
    train, test = token_sets()

    def run(cfg):
        trainer = Trainer(cfg, datasets=(train, test))
        losses = Losses(trainer)
        trainer.add_hook(losses)
        params0 = jax.tree_util.tree_map(jnp.array, trainer.state.params)
        trainer.fit()
        return trainer, losses.values(), params0

    _, whole, params0 = run(tiny_cfg(tmp_path / "whole",
                                     **{"train.epochs": 2}))
    first, before, _ = run(tiny_cfg(tmp_path / "cut"))
    assert int(first.state.step) == STEPS_AN_EPOCH
    second, after, _ = run(tiny_cfg(
        tmp_path / "cut", **{"train.epochs": 2, "train.resume": "true"}))
    assert second.start_epoch == 1
    assert int(second.state.opt_state["count"]) == 2 * STEPS_AN_EPOCH
    assert before + after == pytest.approx(whole, rel=1e-6)
    want = reference_losses(params0, train.tokens, STEPS_AN_EPOCH + 1)
    assert whole[:STEPS_AN_EPOCH + 1] == pytest.approx(want["loss"], rel=5e-5)


def test_train_py_trains_the_preset_at_tiny_shapes(tmp_path, capsys):
    """`python train.py --preset=sdar_30b_a3b_ep8` with shape overrides:
    the program's own loader, `Trainer.fit`, evaluation, the summary."""
    import train as train_main

    argv = tiny_argv(tmp_path, **{"train.epochs": 2})
    assert train_main.main(argv) == 0
    out = capsys.readouterr().out
    assert "Finished Training" in out and '"items_per_sec"' in out
    assert '"images_per_sec"' not in out


# ------------------------------------------------------ (f) the data set

def test_token_rows_reach_the_step_once_an_epoch(mesh1):
    data = make_synthetic_tokens(24, LENGTH, VOCAB, seed=5)
    assert data.tokens.dtype == np.int32 and data.tokens.shape == (24, LENGTH)
    assert data.tokens.max() < VOCAB - 1      # the mask token is never data
    assert data.num_classes == VOCAB and data.items_per_row == LENGTH
    assert data.tokens.flags.c_contiguous
    # tag every row with its number, so that a batch says which rows it holds
    tagged = TokenDataset(
        np.ascontiguousarray(np.broadcast_to(
            np.arange(24, dtype=np.int32)[:, None], (24, LENGTH))),
        "tagged", VOCAB)
    pipe = DataPipeline(tagged, 4, mesh1, shuffle=True, seed=0)
    for epoch in (0, 1):
        pipe.set_epoch(epoch)
        batches = [np.asarray(b["tokens"]) for b in pipe]
        assert all(set(b) == {"tokens"} for b in pipe)
        assert [b.shape for b in batches] == [(4, LENGTH)] * 6
        rows = np.concatenate([b[:, 0] for b in batches])
        assert sorted(rows) == list(range(24))
        assert list(rows) == list(
            np.random.default_rng([0, epoch]).permutation(24))
    # the resident feed stages the same arrays and ships indices
    assert set(pipe.resident_data()) == {"tokens"}
    assert pipe.dataset_bytes() == 24 * LENGTH * 4


def test_zipf_ids_are_skewed_and_the_split_shares_the_skew():
    train = make_synthetic_tokens(64, 256, 512, seed=9, example_seed=1)
    test = make_synthetic_tokens(64, 256, 512, seed=9, example_seed=2)
    counts = np.bincount(train.tokens.ravel(), minlength=512)
    top = np.argsort(counts)[::-1]
    # rank 1 holds 1 / H(511) = 14.7% of a Zipf(1.0) draw over 511 ids
    assert counts[top[0]] / counts.sum() == pytest.approx(0.147, abs=0.02)
    assert counts[top[0]] > 1.6 * counts[top[1]]
    assert np.bincount(test.tokens.ravel(), minlength=512).argmax() == top[0]
    assert not np.array_equal(train.tokens, test.tokens)
