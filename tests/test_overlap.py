"""Bucketed, overlap-scheduled gradient collectives (`train.bucket_mb`;
docs/PERF.md "Overlapped collectives") + the async double-buffered input
feed — the two halves of ROADMAP item 4.

The correctness story, proven on the 8-device CPU mesh:

1. **Bucket plan units** — reverse production order, size targeting, the
   single-giant-leaf degenerate case, the self-describing composition key
   (a per-leaf key is the single-leaf case), `parse_bucket_mb` validation,
   and the bucketed `wire_report` accounting.
2. **Collective level** — bucketed f32 reduce-scatter matches the
   monolithic path bitwise on this backend (the documented contract is
   reduction-order tolerance, docs/PERF.md); bf16/int8 wires within their
   codec bounds; per-bucket error-feedback residuals; sub-threshold
   buckets ride the f32 fallback; the compiled schedule keeps the K
   bucket exchanges separate (the overlap property's precondition).
3. **Step level** — bucketed training parity vs the replicated f32
   reference across all three wire dtypes lives in the ONE wire-dtype
   parity harness (tests/test_quant.py, bucketed × {f32, bf16, int8}),
   and the error-feedback telescoping property survives bucketing in the
   ONE telescoping test there (per leaf × per bucket, 24 steps); here:
   the windowed multi-step composition.
4. **Analyzer** — DP301 accepts the K-bucket schedule and rejects a
   dropped or duplicated bucket; DP304's fingerprint artifact round-trips
   the bucket layout; Level 2 still proves exactly-one-reduction-per-leaf
   through the bucketed exchange.
5. **commprof** — a profiled CPU capture of the bucketed program
   reconciles exactly K reduce-scatters per step against the fingerprint
   schedule, with per-bucket wire bytes byte-exact vs `quant.wire_report`.
6. **Checkpoint** — bucketed residuals round-trip bitwise same-layout;
   resharding across bucket-size changes, per-leaf <-> bucketed layout
   flips, and codec-off targets all preserve (or deliberately drop) the
   pending error-feedback correction leaf-exactly.
7. **Input feed** — device placement is genuinely async: no per-batch
   host sync (the `data_wait` span shrinks vs the `sync_placement`
   comparator) and the double buffer keeps the next batch's placement in
   flight while the consumer computes.

Fast lane: ``pytest -m overlap``.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.parallel import bucketing, collectives, dist, quant
from tpu_dp.train import (
    SGD,
    constant_lr,
    create_train_state,
    make_train_step,
    shard_optimizer,
)

pytestmark = pytest.mark.overlap

WORLD = 8
BLOCK = 64
BB = 4 * 1024  # 4 KB buckets: several buckets even on toy trees


def _sample():
    return np.zeros((1, 32, 32, 3), np.float32)


def _make_batch(seed, n=16):
    ds = make_synthetic(n, 10, seed=seed, name="synthetic")
    return {"image": normalize(ds.images), "label": ds.labels}


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _l2(a, b):
    return float(np.sqrt(sum(
        float(np.sum((np.asarray(x) - np.asarray(y)) ** 2))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )))


def _tree(rng):
    return {
        "a": jnp.asarray(rng.normal(size=(400, 120)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(5, 5, 3, 6)).astype(np.float32)),
        "c": jnp.asarray(rng.normal(size=(1000,)).astype(np.float32)),
    }


def _per_replica(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (i + 1) for i in range(WORLD)]), tree
    )


# --------------------------------------------------------------------------
# 1. bucket plan units
# --------------------------------------------------------------------------

def test_parse_bucket_mb_validation():
    assert bucketing.parse_bucket_mb(0) == 0
    assert bucketing.parse_bucket_mb(None) == 0
    assert bucketing.parse_bucket_mb(1) == 2**20
    assert bucketing.parse_bucket_mb(0.5) == 2**19
    with pytest.raises(ValueError, match="bucket_mb"):
        bucketing.parse_bucket_mb(-1)
    with pytest.raises(ValueError, match="bucket_bytes"):
        bucketing.plan_buckets([("a", 10)], WORLD, 0)


def test_plan_reverse_production_order_and_size_target():
    """Buckets fill from the LAST leaf backwards (backward produces
    gradients in reverse forward order) and close at the byte target."""
    leaves = [("l0", 1000), ("l1", 50), ("l2", 3000), ("l3", 8)]
    plan = bucketing.plan_buckets(leaves, world=8,
                                  bucket_bytes=4 * 1024)  # 1024 f32 elems
    # Reverse order: l3 (8 -> padded 8), l2 (3000) closes bucket 0;
    # l1, l0 close bucket 1 at the tail.
    assert [b.keys for b in plan] == [("l3", "l2"), ("l1", "l0")]
    assert [b.index for b in plan] == [0, 1]
    assert plan[0].elements == 3008 and plan[1].elements == 1050
    # Every leaf exactly once across the union — the exactly-once seed.
    seen = [k for b in plan for k in b.keys]
    assert sorted(seen) == sorted(k for k, _ in leaves)


def test_plan_single_giant_leaf_owns_bucket():
    plan = bucketing.plan_buckets(
        [("small", 4), ("giant", 10_000_000)], world=8, bucket_bytes=2**20)
    assert [b.keys for b in plan] == [("giant",), ("small",)]


def test_composition_key_roundtrip():
    b = bucketing.GradBucket(index=0, keys=("fc1/kernel", "conv2/bias"),
                             sizes=(48000, 16))
    assert bucketing.composition(b.key) == ["fc1/kernel", "conv2/bias"]
    # Single-leaf buckets degenerate to the plain leaf key — unbucketed
    # residual checkpoints are the single-leaf case of the same grammar.
    solo = bucketing.GradBucket(index=0, keys=("conv1/kernel",),
                                sizes=(450,))
    assert solo.key == "conv1/kernel"
    assert bucketing.composition(solo.key) == ["conv1/kernel"]


def test_quantize_threshold_is_per_bucket():
    """Concatenation is what lets small leaves compress: alone below the
    world*block threshold, together above it."""
    leaves = [("x", 300), ("y", 300)]
    plan = bucketing.plan_buckets(leaves, world=8, bucket_bytes=2**20,
                                  block_size=64, int8=True)
    assert len(plan) == 1 and plan[0].quantizes  # 600 >= 8*64
    tiny = bucketing.plan_buckets([("x", 300)], world=8, bucket_bytes=2**20,
                                  block_size=64, int8=True)
    assert not tiny[0].quantizes  # 300 < 512: f32 fallback bucket


def test_wire_report_bucketed_accounting(rng):
    tree = _tree(rng)
    mono = quant.wire_report(tree, WORLD, BLOCK)
    buck = quant.wire_report(tree, WORLD, BLOCK, bucket_bytes=BB)
    # f32/bf16 bytes are padding-preserving under concatenation.
    assert buck["wire_bytes_per_step"]["f32"] == \
        mono["wire_bytes_per_step"]["f32"]
    assert buck["wire_bytes_per_step"]["bf16"] == \
        mono["wire_bytes_per_step"]["bf16"]
    # int8 block padding is per bucket; the layout summary rides along.
    assert buck["bucket_bytes"] == BB
    assert len(buck["buckets"]) >= 2
    assert sum(e["leaves"] for e in buck["buckets"]) == buck["leaves"] == 3
    plan = bucketing.plan_for_tree(tree, WORLD, BB, block_size=BLOCK,
                                   int8=True)
    assert len(buck["buckets"]) == len(plan)
    # Small leaves compress inside buckets: more quantized leaves than
    # the per-leaf layout could manage.
    assert buck["quantized_leaves"] >= mono["quantized_leaves"]


# --------------------------------------------------------------------------
# 2. collective level
# --------------------------------------------------------------------------

def _roundtrip_bucketed(mesh8, tree, dtype=None, bucket_bytes=BB):
    from jax.sharding import PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    def via_bucketed(t):
        sh = collectives.psum_scatter_bucketed(
            t, dist.DATA_AXIS, world=WORLD, mean=True, dtype=dtype,
            bucket_bytes=bucket_bytes)
        return collectives.all_gather(sh, t, dist.DATA_AXIS)

    def via_mono(t):
        return collectives.all_gather(
            collectives.psum_scatter(t, dist.DATA_AXIS, world=WORLD,
                                     mean=True), t, dist.DATA_AXIS)

    fb = jax.jit(_shard_map(via_bucketed, mesh8, (P(dist.DATA_AXIS),), P()))
    fm = jax.jit(_shard_map(via_mono, mesh8, (P(dist.DATA_AXIS),), P()))
    return fb, fm


def test_bucketed_scatter_matches_monolithic_f32(mesh8, rng):
    """Bucketed f32 vs the monolithic reduce-scatter: concatenation does
    not change the per-element cross-replica addition order, so on the
    CPU backend the result is bitwise (the documented cross-backend
    contract is reduction-order tolerance, docs/PERF.md)."""
    tree = _tree(rng)
    args = _per_replica(tree)
    fb, fm = _roundtrip_bucketed(mesh8, tree)
    out_b, out_m = fb(args), fm(args)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out_b[k]),
                                      np.asarray(out_m[k]))
        assert out_b[k].dtype == out_m[k].dtype


def test_bucketed_scatter_bf16_wire_tolerance(mesh8, rng):
    tree = _tree(rng)
    args = _per_replica(tree)
    fb, fm = _roundtrip_bucketed(mesh8, tree, dtype=jnp.bfloat16)
    out_b, out_m = fb(args), fm(args)
    identical = True
    for k in tree:
        a, m = np.asarray(out_b[k]), np.asarray(out_m[k])
        np.testing.assert_allclose(a, m, atol=np.abs(m).max() * 8e-3)
        identical &= bool(np.array_equal(a, m))
    assert not identical, "bf16 wire produced bitwise f32 — never cast?"


def test_bucketed_quant_scatter_and_per_bucket_residuals(mesh8, rng):
    from jax.sharding import PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    tree = _tree(rng)
    args = _per_replica(tree)
    res = quant.init_residuals(tree, WORLD, BLOCK, bucket_bytes=BB)
    plan = bucketing.plan_for_tree(tree, WORLD, BB, block_size=BLOCK,
                                   int8=True)
    # Residuals keyed by the composition of each QUANTIZING bucket.
    assert set(res) == {b.key for b in plan if b.quantizes}

    def via_q(t, r):
        sh, nr, st = collectives.psum_scatter_quant_bucketed(
            t, r, dist.DATA_AXIS, world=WORLD, mean=True,
            block_size=BLOCK, bucket_bytes=BB)
        full = collectives.all_gather(sh, t, dist.DATA_AXIS)
        st = {k: collectives.psum(v, dist.DATA_AXIS) for k, v in st.items()}
        return full, nr, st

    fq = jax.jit(_shard_map(
        via_q, mesh8, (P(dist.DATA_AXIS), P(dist.DATA_AXIS)),
        (P(), P(dist.DATA_AXIS), P())))
    _, fm = _roundtrip_bucketed(mesh8, tree)
    (out_q, new_res, stats), out_m = fq(args, res), fm(args)
    for k in tree:
        a, m = np.asarray(out_q[k]), np.asarray(out_m[k])
        assert np.abs(a - m).max() <= np.abs(m).max() * 0.01 + 1e-6, k
    # The SMALL leaf compressed inside its bucket (not the f32 fallback
    # the per-leaf layout forced): provably non-bitwise.
    assert not np.array_equal(np.asarray(out_q["b"]),
                              np.asarray(out_m["b"]))
    assert int(stats["overflow"]) == 0
    for key, leaf in new_res.items():
        assert np.abs(np.asarray(leaf)).max() > 0, key


def test_sub_threshold_bucket_rides_f32_fallback(mesh8, rng):
    """A bucket below world*block elements keeps the plain f32 wire and
    carries no residual — bitwise vs the monolithic f32 scatter."""
    from jax.sharding import PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    # Pytree (sorted-key) order is a_tiny, z_big; reverse production
    # order walks it backwards: "z_big" closes bucket 0 alone, "a_tiny"
    # (40 < world*block = 512) is the trailing sub-threshold bucket.
    tree = {"a_tiny": jnp.asarray(rng.normal(size=(40,)).astype(np.float32)),
            "z_big": jnp.asarray(rng.normal(size=(4096,)).astype(np.float32))}
    args = _per_replica(tree)
    bb = 2 * 1024
    plan = bucketing.plan_for_tree(tree, WORLD, bb, block_size=BLOCK,
                                   int8=True)
    assert [b.keys for b in plan] == [("z_big",), ("a_tiny",)]
    assert [b.quantizes for b in plan] == [True, False]
    res = quant.init_residuals(tree, WORLD, BLOCK, bucket_bytes=bb)
    assert set(res) == {"z_big"}

    def via_q(t, r):
        sh, nr, st = collectives.psum_scatter_quant_bucketed(
            t, r, dist.DATA_AXIS, world=WORLD, mean=True,
            block_size=BLOCK, bucket_bytes=bb)
        return collectives.all_gather(sh, t, dist.DATA_AXIS)

    fq = jax.jit(_shard_map(
        via_q, mesh8, (P(dist.DATA_AXIS), P(dist.DATA_AXIS)), P()))
    _, fm = _roundtrip_bucketed(mesh8, tree, bucket_bytes=bb)
    out_q, out_m = fq(args, res), fm(args)
    np.testing.assert_array_equal(np.asarray(out_q["a_tiny"]),
                                  np.asarray(out_m["a_tiny"]))


def test_compiled_schedule_keeps_k_separate_buckets(mesh8, rng):
    """The compiled module carries exactly K separate reduce-scatters, one
    per planned bucket — the `optimization_barrier` token chain is what
    keeps the optimizer passes from globbing them back into one exchange.
    Their textual order is not asserted: this round trip has no backward
    pass to produce the buckets one after another, so the CPU scheduler is
    free to place two independent exchanges either way round (it swapped
    them between XLA versions)."""
    from tpu_dp.analysis.hlo import _shape_elements, collect_ops

    tree = _tree(rng)
    args = _per_replica(tree)
    plan = bucketing.plan_for_tree(tree, WORLD, BB)
    fb, _ = _roundtrip_bucketed(mesh8, tree)
    text = fb.lower(args).compile().as_text()
    scatters = [op for op in collect_ops(text)
                if op.kind == "reduce-scatter"]
    assert len(scatters) == len(plan) >= 2
    got = sorted(_shape_elements(op.shape) for op in scatters)
    want = sorted(sum(collectives.shard_size(n, WORLD) for n in b.sizes)
                  for b in plan)
    assert got == want


# --------------------------------------------------------------------------
# 3. step level
# --------------------------------------------------------------------------

def _states(bucket_mb=0.05):
    model = Net()
    opt = SGD(momentum=0.9)
    sopt = shard_optimizer(SGD(momentum=0.9), WORLD)
    rng = jax.random.PRNGKey(0)
    state_r = create_train_state(model, rng, _sample(), opt)
    state_s = create_train_state(model, rng, _sample(), sopt)
    state_q = state_s.replace(residuals=quant.init_residuals(
        state_s.params, WORLD, 256,
        bucket_bytes=bucketing.parse_bucket_mb(bucket_mb)))
    return model, opt, sopt, state_r, state_s, state_q


def test_bucketed_multi_step_window_tracks_f32(mesh8):
    """Bucketing composes with the windowed device-side loop."""
    model, opt, sopt, state_r, state_s, _ = _states()
    K = 4
    loop_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             feed="window", num_steps=K)
    loop_b = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             feed="window", num_steps=K,
                             update_sharding="sharded",
                             bucket_mb=0.05)
    batches = [_make_batch(100 + i) for i in range(K)]
    pool = {"image": np.stack([b["image"] for b in batches]),
            "label": np.stack([b["label"] for b in batches])}
    sr, _ = loop_r(_copy(state_r), pool)
    sb, _ = loop_b(_copy(state_s), pool)
    assert int(sb.step) == K
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_factory_and_config_validation():

    model, opt, sopt, *_ = _states()
    mesh = dist.data_mesh()
    with pytest.raises(ValueError, match="bucket_mb"):
        make_train_step(model, opt, mesh, constant_lr(0.1),
                        bucket_mb=1.0, explicit=True)  # replicated mode
    with pytest.raises(ValueError, match="bucket_mb"):
        make_train_step(model, sopt, mesh, constant_lr(0.1),
                        update_sharding="sharded", bucket_mb=-1)
    # The windowed factories refuse too — a silently-dropped bucket_mb
    # would leave the caller believing the overlap schedule is armed.
    with pytest.raises(ValueError, match="bucket_mb"):
        make_train_step(model, opt, mesh, constant_lr(0.1), feed="window", num_steps=2,
                        bucket_mb=1.0)
    with pytest.raises(ValueError, match="bucket_mb"):
        make_train_step(model, opt, mesh, constant_lr(0.1),
                        feed="resident", num_steps=2, sample_shapes={},
                        bucket_mb=1.0)
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    cfg = Config()
    cfg.data.dataset = "synthetic"
    cfg.data.synthetic_train_size = 16
    cfg.data.synthetic_test_size = 8
    cfg.train.bucket_mb = 1.0  # replicated update: must refuse
    with pytest.raises(ValueError, match="bucket_mb"):
        Trainer(cfg)


# --------------------------------------------------------------------------
# 4. analyzer
# --------------------------------------------------------------------------

@pytest.mark.analysis
def test_gradsync_bucketed_exactly_once():
    from tpu_dp.analysis import gradsync

    for wire in (None, "int8"):
        findings, report = gradsync.verify_repo_step(
            update_sharding="sharded", collective_dtype=wire,
            bucket_mb=0.05,
        )
        assert findings == [], [f.message for f in findings]
        assert report and all(c == 1 for c in report.values()), report


@pytest.fixture(scope="module")
def _bucketed_program():
    """One compiled bucketed sharded train step + its plan (module-scoped:
    the compile is the expensive part, every analyzer/commprof test below
    shares it)."""
    model, opt, sopt, state_r, state_s, _ = _states()
    mesh = dist.data_mesh()
    step = make_train_step(
        model, sopt, mesh, constant_lr(0.05), update_sharding="sharded",
        bucket_mb=0.05)
    plan = bucketing.plan_for_tree(
        state_s.params, WORLD, bucketing.parse_bucket_mb(0.05))
    batch = _make_batch(0)
    return step, _copy(state_s), batch, plan


@pytest.mark.analysis
def test_dp301_accepts_k_bucket_schedule(_bucketed_program, tmp_path):
    from tpu_dp.analysis.hlo import (
        analyze_module,
        bucket_expectations,
        lower_and_compile,
        write_fingerprint_artifact,
    )

    step, state, batch, plan = _bucketed_program
    text, _, warns = lower_and_compile(step, (state, batch))
    layout = bucket_expectations(plan, WORLD, 256)
    findings, record = analyze_module(
        text, label="bucketed", where=("x.py", 1), world=WORLD,
        donated_leaves=len(jax.tree_util.tree_leaves(state)),
        metric_reductions=2, expect_grad_reduce=True,
        donation_warnings=warns, update_sharding="sharded",
        bucket_layout=layout,
    )
    assert findings == [], [f.message for f in findings]
    # DP304: the fingerprint artifact round-trips the bucket layout.
    art = {"version": 1, "world": WORLD, "backend": "cpu", "digest": "x",
           "programs": {"bucketed": record}}
    path = tmp_path / "fp.json"
    write_fingerprint_artifact(str(path), art)
    back = json.loads(path.read_text())
    assert back["programs"]["bucketed"]["buckets"] == layout
    assert len(back["programs"]["bucketed"]["buckets"]) == len(plan) >= 2


@pytest.mark.analysis
def test_dp301_rejects_dropped_and_duplicated_bucket(_bucketed_program):
    from tpu_dp.analysis.hlo import (
        analyze_module,
        bucket_expectations,
        lower_and_compile,
    )

    step, state, batch, plan = _bucketed_program
    text, _, _ = lower_and_compile(step, (state, batch))
    layout = bucket_expectations(plan, WORLD, 256)

    def run(declared):
        findings, _ = analyze_module(
            text, label="bucketed", where=("x.py", 1), world=WORLD,
            metric_reductions=2, expect_grad_reduce=True,
            update_sharding="sharded", bucket_layout=declared,
        )
        return [f for f in findings if f.rule == "DP301"]

    # Declaring a bucket the program does not compile == the program
    # DROPPED a declared bucket (those leaves never reduce).
    extra_bucket = layout + [{"wire": "f32", "shard_elements": 4242}]
    got = run(extra_bucket)
    assert got and any("MISSING" in f.message for f in got)
    # Declaring FEWER buckets than compiled == a duplicated/stray
    # exchange beyond the plan.
    got = run(layout[:1])
    assert got and any("EXTRA" in f.message for f in got)


# --------------------------------------------------------------------------
# 5. commprof: K buckets reconcile on a real profiled capture
# --------------------------------------------------------------------------

def test_commprof_reconciles_k_buckets_on_profiled_capture(
        _bucketed_program, tmp_path):
    """A real jax.profiler capture of the bucketed program reconciles
    exactly K reduce-scatters per step per device against the fingerprint
    schedule, with the grad-exchange bytes byte-exact vs the bucketed
    `quant.wire_report` — and a tampered expectation must NOT reconcile."""
    from tpu_dp.obs import commprof, xplane

    step, state0, batch, plan = _bucketed_program
    expected = commprof.expected_schedule(step, (_copy(state0), batch))
    state = _copy(state0)
    state, m = step(state, batch)  # warmup outside the trace...
    jax.block_until_ready(m)       # ...so it must have finished by then
    trace_dir = tmp_path / "trace"
    with jax.profiler.trace(str(trace_dir)):
        state, m = step(state, batch)
        state, m = step(state, batch)
        jax.block_until_ready(m)
    summary = xplane.summarize_robust(str(trace_dir))
    wire_rep = quant.wire_report(
        state.params, WORLD, 256,
        bucket_bytes=bucketing.parse_bucket_mb(0.05))
    steps = 2
    rep = commprof.breakdown(
        summary, steps=steps,
        devices=WORLD if summary.get("source") == "host" else 1,
        expected_total={k: v * steps for k, v in expected["counts"].items()},
        collectives=expected["collectives"], world=WORLD,
        wire_report=wire_rep, wire_dtype="",
    )
    recon = rep["reconciliation"]
    assert recon["ok"], recon
    assert recon["by_kind"]["reduce-scatter"]["per_step_observed"] == \
        len(plan)
    assert rep["wire"]["reconciliation"]["ok"], rep["wire"]
    assert rep["wire"]["reconciliation"]["schedule_bytes_per_step"] == \
        wire_rep["wire_bytes_per_step"]["f32"]
    # Tamper: expect one extra scatter per step -> must NOT reconcile.
    bad = dict(expected["counts"])
    bad["reduce-scatter"] = bad.get("reduce-scatter", 0) + 1
    rep_bad = commprof.breakdown(
        summary, steps=steps,
        devices=WORLD if summary.get("source") == "host" else 1,
        expected_total={k: v * steps for k, v in bad.items()},
    )
    assert not rep_bad["reconciliation"]["ok"]


# --------------------------------------------------------------------------
# 6. checkpoint: bucket-exact residual resharding
# --------------------------------------------------------------------------

def _fill_residuals(state, gen):
    """Recognizable nonzero residuals, zero outside valid element slots
    (the invariant a real trajectory maintains) — built by composing
    known per-leaf pending vectors into each key's layout."""
    sizes = {
        "/".join(str(getattr(x, "key", x)) for x in path): leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    pend = {k: gen.normal(size=n).astype(np.float32) * 1e-3
            for k, n in sizes.items()}
    filled = {
        key: jnp.asarray(quant.compose_residual(pend, np.asarray(leaf),
                                                sizes, key))
        for key, leaf in state.residuals.items()
    }
    return state.replace(residuals=filled), pend, sizes


def _pendings(state, sizes):
    out = {}
    for key, leaf in state.residuals.items():
        out.update(quant.decompose_residual(np.asarray(leaf), sizes, key))
    return out


def test_bucketed_residuals_roundtrip_same_layout_bitwise(tmp_path):
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    *_, state_q = _states()
    state_q, _, _ = _fill_residuals(state_q, np.random.default_rng(1))
    save_checkpoint(tmp_path, state_q, {"epoch": 0})
    restored, _ = load_checkpoint(tmp_path, _states()[5])
    for key, leaf in state_q.residuals.items():
        np.testing.assert_array_equal(np.asarray(restored.residuals[key]),
                                      np.asarray(leaf))


@pytest.mark.parametrize("src_mb,dst_mb", [
    (0.0, 0.05),    # per-leaf layout -> bucketed
    (0.05, 0.0),    # bucketed -> per-leaf
    (0.05, 0.01),   # bucket-size retune
], ids=["leaf->bucket", "bucket->leaf", "bucket-resize"])
def test_residual_reshard_across_bucket_layouts_preserves_pending(
        tmp_path, src_mb, dst_mb):
    """The acceptance contract: resume across a bucket-layout change
    preserves the pending error-feedback correction LEAF-exactly (total
    debt per params leaf; replica 0 owes it all in the new layout)."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    src = _states(bucket_mb=src_mb)[5]
    src, pend, sizes = _fill_residuals(src, np.random.default_rng(7))
    save_checkpoint(tmp_path, src, {"epoch": 0})
    dst = _states(bucket_mb=dst_mb)[5]
    restored, _ = load_checkpoint(tmp_path, dst)
    assert set(restored.residuals) == set(dst.residuals)
    got = _pendings(restored, sizes)
    src_pend = _pendings(src, sizes)
    # Leaves covered by BOTH layouts carry their pending debt exactly;
    # leaves the new layout covers but the old one did not (a small leaf
    # entering a quantizing bucket) start clean; leaves the new layout
    # stopped covering are deliberately forfeited.
    carried = set(src_pend) & set(got)
    assert carried, "no leaf covered by both layouts — vacuous test"
    for k in carried:
        np.testing.assert_allclose(got[k], src_pend[k], atol=1e-7,
                                   err_msg=k)
    for k in set(got) - set(src_pend):
        np.testing.assert_array_equal(got[k], 0.0)
    # The debt sits on replica 0; everyone else starts clean.
    for key, leaf in restored.residuals.items():
        np.testing.assert_array_equal(np.asarray(leaf)[1:], 0.0)


def test_bucketed_residuals_drop_when_codec_off(tmp_path):
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model, opt, sopt, state_r, state_s, state_q = _states()
    state_q, _, _ = _fill_residuals(state_q, np.random.default_rng(2))
    save_checkpoint(tmp_path, state_q, {"epoch": 0})
    dropped, _ = load_checkpoint(tmp_path, state_s.replace(residuals={}))
    assert dropped.residuals == {}


def test_real_run_residuals_survive_bucket_resize(tmp_path, mesh8):
    """End-to-end: REAL residuals from a few bucketed int8 steps, saved,
    restored into a different bucket size — per-leaf pending corrections
    carried over exactly; training continues without shape errors."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model, opt, sopt, state_r, state_s, state_q = _states(bucket_mb=0.05)
    step = make_train_step(
        model, sopt, mesh8, constant_lr(0.05), update_sharding="sharded",
        collective_dtype="int8", bucket_mb=0.05)
    s = _copy(state_q)
    for i in range(3):
        s, _ = step(s, _make_batch(i))
    save_checkpoint(tmp_path, s, {"epoch": 0})

    sizes = {
        "/".join(str(getattr(x, "key", x)) for x in path): leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(s.params)
    }
    before = _pendings(s, sizes)
    dst = _states(bucket_mb=0.01)[5]
    restored, _ = load_checkpoint(tmp_path, dst)
    after = _pendings(restored, sizes)
    carried = set(before) & set(after)
    assert carried
    for k in carried:
        np.testing.assert_allclose(after[k], before[k], atol=1e-6,
                                   err_msg=k)
    for k in set(after) - set(before):
        np.testing.assert_array_equal(after[k], 0.0)
    step2 = make_train_step(
        model, sopt, mesh8, constant_lr(0.05), update_sharding="sharded",
        collective_dtype="int8", bucket_mb=0.01)
    s2, m = step2(_copy(restored), _make_batch(9))
    assert int(s2.step) == 4 and np.isfinite(float(m["loss"]))


# --------------------------------------------------------------------------
# 7. input feed: async double-buffered placement
# --------------------------------------------------------------------------

def _timed_pipeline(monkeypatch, transfer_s, sync, prefetch):
    """A DataPipeline whose device placement 'transfer' completes
    ``transfer_s`` after dispatch: `shard_batch` is an async dispatch
    (returns immediately, stamps a ready time), `jax.block_until_ready`
    waits it out — the model of a real h2d copy."""
    from tpu_dp.data import pipeline as pl
    from tpu_dp.data.cifar import make_synthetic

    def fake_shard_batch(batch, mesh, spec=None):
        return dict(batch, _ready_at=time.perf_counter() + transfer_s)

    def fake_block(x):
        if isinstance(x, dict) and "_ready_at" in x:
            time.sleep(max(0.0, x["_ready_at"] - time.perf_counter()))
        return x

    monkeypatch.setattr(pl, "shard_batch", fake_shard_batch)
    monkeypatch.setattr(jax, "block_until_ready", fake_block)
    ds = make_synthetic(64, 10, seed=0, name="synthetic")
    mesh = dist.data_mesh()
    return pl.DataPipeline(ds, 8, mesh, shuffle=False, prefetch=prefetch,
                           sync_placement=sync)


def _consume(pipe, work_s=0.0):
    """Iterate the pipeline; return total time blocked in next() — the
    data_wait span the trainer records."""
    waits = []
    it = iter(pipe)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            break
        waits.append(time.perf_counter() - t0)
        assert "image" in item
        if work_s:
            time.sleep(work_s)  # the consumer's "step"
    return sum(waits), len(waits)


def test_async_placement_shrinks_data_wait(monkeypatch):
    """The satellite's proof: with a per-batch 'transfer' of 30 ms and a
    30 ms consumer step, the sync-placement pipeline (the old world: a
    host sync per batch) pays the transfer on the data_wait span every
    batch; the async double-buffered default hides it under the step.
    Coarse margins — sleeps, not wall-clock guesses."""
    sync_wait, n1 = _consume(
        _timed_pipeline(monkeypatch, 0.03, sync=True, prefetch=0),
        work_s=0.03)
    async_wait, n2 = _consume(
        _timed_pipeline(monkeypatch, 0.03, sync=False, prefetch=0),
        work_s=0.03)
    assert n1 == n2 == 8
    assert sync_wait > 0.03 * (n1 - 1), (sync_wait, n1)
    assert async_wait < sync_wait * 0.5, (async_wait, sync_wait)


def test_double_buffer_keeps_next_placement_in_flight(monkeypatch):
    """Batch k+1's placement is DISPATCHED before the consumer finishes
    batch k — the two-slot double buffer, observable from dispatch
    timestamps even with the prefetch thread off."""
    from tpu_dp.data import pipeline as pl
    from tpu_dp.data.cifar import make_synthetic

    dispatches = []

    def fake_shard_batch(batch, mesh, spec=None):
        dispatches.append(time.perf_counter())
        return batch

    monkeypatch.setattr(pl, "shard_batch", fake_shard_batch)
    ds = make_synthetic(32, 10, seed=0, name="synthetic")
    pipe = pl.DataPipeline(ds, 8, dist.data_mesh(), shuffle=False,
                           prefetch=0)
    it = iter(pipe)
    next(it)
    # Before the consumer asks for batch 1, its placement is in flight.
    assert len(dispatches) >= 2
    consumed_at = time.perf_counter()
    next(it)
    assert dispatches[1] <= consumed_at


def test_sync_placement_knob_blocks_per_batch(monkeypatch):
    """The escape hatch really is the old world: sync_placement=True
    calls block_until_ready once per placed batch."""
    from tpu_dp.data import pipeline as pl
    from tpu_dp.data.cifar import make_synthetic

    blocks = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocks.append(1) or x)
    ds = make_synthetic(32, 10, seed=0, name="synthetic")
    mesh = dist.data_mesh()
    _consume(pl.DataPipeline(ds, 8, mesh, shuffle=False, prefetch=0,
                             sync_placement=True))
    assert len(blocks) == 4
    blocks.clear()
    _consume(pl.DataPipeline(ds, 8, mesh, shuffle=False, prefetch=0))
    assert blocks == []  # the async default never host-syncs per batch


def test_windows_path_double_buffers_and_matches(monkeypatch):
    """The windowed feed rides the same double buffer and yields the same
    windows (order + content) as before."""
    from tpu_dp.data import pipeline as pl
    from tpu_dp.data.cifar import make_synthetic

    ds = make_synthetic(64, 10, seed=0, name="synthetic")
    mesh = dist.data_mesh()
    pipe = pl.DataPipeline(ds, 8, mesh, shuffle=False, prefetch=2)
    got = [(n, np.asarray(item["label"]).copy())
           for n, item in pipe.windows(3)]
    assert [n for n, _ in got] == [3, 3, 1, 1]
    flat = np.concatenate([lab.reshape(-1) for _, lab in got])
    np.testing.assert_array_equal(flat, ds.labels)
