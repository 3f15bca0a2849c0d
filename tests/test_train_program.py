"""`make_train_step`, the one factory of every train program.

The feed, the exchange and the guard are its arguments, so their product is
reachable; the pairs that other files hold against each other (window and
resident against the host loop in `test_step.py`, sharded against replicated
in `test_shard_update.py`, the wire codecs in `test_quant.py` and
`test_overlap.py`) are not repeated here. Each case below is a combination
that no other equivalence test reaches, held to the trajectory of the
single-batch GSPMD step on the same rows; then what the factory refuses.
"""

import jax
import numpy as np
import pytest

from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.data.pipeline import DataPipeline
from tpu_dp.models import Net
from tpu_dp.train import (
    SGD,
    cosine_lr,
    create_train_state,
    make_train_step,
    shard_optimizer,
)
from tpu_dp.train.step import default_guard_in

STEPS, BATCH, WORLD = 4, 16, 8

# ``pool`` (window feed): batches staged, cycled when fewer than STEPS.
CASES = {
    "resident-sharded-accum2": dict(
        feed="resident", update_sharding="sharded", accum_steps=2),
    "window-sharded-sentinel": dict(
        feed="window", update_sharding="sharded", sentinel=True),
    "resident-sentinel": dict(feed="resident", sentinel=True),
    "window-pool2-explicit": dict(feed="window", explicit=True, pool=2),
    "resident-explicit": dict(feed="resident", explicit=True),
    "window-accum2-sentinel": dict(
        feed="window", accum_steps=2, sentinel=True),
    "batch-sharded-accum2-sentinel": dict(
        feed="batch", update_sharding="sharded", accum_steps=2,
        sentinel=True),
}


def _fresh_state(model, opt):
    return create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32),
        opt)


@pytest.mark.parametrize("case", list(CASES))
def test_follows_the_single_batch_gspmd_step(mesh8, case):
    kw = dict(CASES[case])
    feed, accum = kw["feed"], kw.get("accum_steps", 1)
    pool = kw.pop("pool", STEPS)
    steps = 1 if feed == "batch" else STEPS
    model, sched = Net(), cosine_lr(0.05, 10, 2)
    ds = make_synthetic(pool * accum * BATCH, 10, seed=3, name="prog")
    pipe = DataPipeline(ds, batch_size=BATCH, mesh=mesh8, accum_steps=accum)
    lead = (accum,) if accum > 1 else ()
    # Step k trains on rows idx[k % pool].
    idx = np.arange(len(ds.labels), dtype=np.int32).reshape(
        pool, *lead, BATCH)

    def placed(rows):
        return {"image": normalize(ds.images[rows]), "label": ds.labels[rows]}

    opt = SGD(momentum=0.9)
    ref = make_train_step(model, opt, mesh8, sched, accum_steps=accum)
    s_ref, ref_loss = _fresh_state(model, opt), []
    for k in range(steps):
        s_ref, m = ref(s_ref, placed(idx[k % pool]))
        ref_loss.append(float(m["loss"]))

    if kw.get("update_sharding") == "sharded":
        opt = shard_optimizer(opt, WORLD)
    program = make_train_step(
        model, opt, mesh8, sched, num_steps=steps,
        sample_shapes=pipe.sample_shapes if feed == "resident" else None,
        **kw)
    if feed == "resident":
        fed = (pipe.resident_data(), idx)
    else:
        fed = (placed(idx[0] if feed == "batch" else idx),)
    guard = (default_guard_in(),) if kw.get("sentinel") else ()
    state, metrics = program(_fresh_state(model, opt), *fed, *guard)

    assert int(state.step) == steps
    np.testing.assert_allclose(
        np.asarray(metrics["loss"]).reshape(-1), ref_loss, rtol=1e-5)
    if guard:
        assert np.all(np.asarray(metrics["applied"]) == 1)
    for got, want in zip(jax.tree_util.tree_leaves(state.params),
                         jax.tree_util.tree_leaves(s_ref.params)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=2e-6)


def _factory(mesh, optimizer=None, **kw):
    return make_train_step(Net(), optimizer or SGD(momentum=0.9), mesh,
                           cosine_lr(0.05, 10, 2), **kw)


FEEDS = {
    "batch": dict(feed="batch"),
    "window": dict(feed="window", num_steps=2),
    "resident": dict(feed="resident", num_steps=2,
                     sample_shapes={"image": (32, 32, 3), "label": ()}),
}


@pytest.mark.parametrize("feed", list(FEEDS))
@pytest.mark.parametrize("keyword", [dict(bucket_mb=1.0),
                                     dict(collective_dtype="bf16")],
                         ids=["bucket_mb", "collective_dtype"])
def test_replicated_exchange_refuses_the_sharded_one_s_keywords(
        mesh8, feed, keyword):
    """Through every feed: dropped in silence, the keyword would leave the
    caller believing the overlap schedule or the compression armed."""
    (name,) = keyword
    with pytest.raises(ValueError, match=f"{name} applies to the sharded"):
        _factory(mesh8, **FEEDS[feed], **keyword)


@pytest.mark.parametrize("kw, message", [
    (dict(feed="batch", num_steps=2), "feed='batch' runs one step"),
    (dict(feed="resident", num_steps=2), "needs sample_shapes"),
    (dict(feed="stream"), "feed must be one of"),
    (dict(update_sharding="sharded", explicit=False),
     "needs explicit collectives"),
], ids=["batch-num_steps", "resident-sample_shapes", "unknown-feed",
        "sharded-inferred"])
def test_refuses_what_no_program_is(mesh8, kw, message):
    optimizer = None
    if kw.get("update_sharding") == "sharded":
        optimizer = shard_optimizer(SGD(momentum=0.9), WORLD)
    with pytest.raises(ValueError, match=message):
        _factory(mesh8, optimizer, **kw)
