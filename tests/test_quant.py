"""Quantized collectives (`train.collective_dtype=int8`; docs/PERF.md
"Quantized collectives").

The correctness story of the blockwise-scaled int8 wire codec
(`tpu_dp/parallel/quant.py` + `collectives.psum_scatter_quant`), proven on
the 8-device CPU mesh:

1. **Codec units** — blockwise absmax round-trip error bound, zero blocks,
   NaN/Inf propagation through the scales (a corrupt gradient can never be
   laundered into a finite int8 value), overflow/clip accounting, layout
   math, wire-byte accounting.
2. **Collective level** — quantized reduce-scatter ≈ f32 reduce-scatter
   within the codec bound; small-leaf fallback bitwise; shard layout
   aligned with `shard_slice` (the sharded optimizer's contract); the
   codec-enabled `all_gather`.
3. **The wire-dtype parity harness** — ONE fixed-seed short-run A/B
   comparing every wire format (f32 / bf16 / int8) against the replicated
   f32 reference: f32 bitwise, bf16 and int8 within their documented
   tolerances and provably NOT bitwise (the compressed path really ran).
   This backfills the bf16 accuracy A/B that PR 4 left at bitwise-f32-only.
4. **Error feedback does real work** — the no-EF ablation lands measurably
   farther from the f32 trajectory than the EF run.
5. **Guardrails interaction** — the sentinel's health summary reads the
   *dequantized post-reduce* gradients; an injected NaN propagates through
   the codec, triggers the on-device skip, and the reverted state includes
   the residuals (a quarantined batch's rounding error is forgotten with
   the batch). Plus the Trainer-level `TPU_DP_FAULT` nan smoke.
6. **Checkpoint/resume** — residual round trip, resharding across world
   sizes (pending-correction preserving) and mode flips, pre-codec
   checkpoints loading with zero residuals, and the kill+auto-resume
   contract with int8 + residuals (bitwise vs an uninterrupted run).
7. **Analyzer** — gradsync counts the int8 payload exchange as THE
   reduction (scales uncounted), and a double exchange still fires DP202.
8. **obs** — quant.overflow / quant.clip_blocks counters flow from the
   per-window fetch into schema-3 records and gate through `obsctl diff`.

Fast lane: ``pytest -m quant``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dp.data.cifar import make_synthetic, normalize
from tpu_dp.models import Net
from tpu_dp.parallel import collectives, dist, quant
from tpu_dp.train import (
    SGD,
    constant_lr,
    create_train_state,
    make_train_step,
    shard_optimizer,
)

pytestmark = pytest.mark.quant

WORLD = 8
BLOCK = 256


def _sample():
    return np.zeros((1, 32, 32, 3), np.float32)


def _make_batch(seed, n=16):
    ds = make_synthetic(n, 10, seed=seed, name="synthetic")
    return {"image": normalize(ds.images), "label": ds.labels}


def _copy(state):
    return jax.tree_util.tree_map(jnp.array, state)


def _states(momentum=0.9, block=BLOCK):
    model = Net()
    opt = SGD(momentum=momentum)
    sopt = shard_optimizer(SGD(momentum=momentum), WORLD)
    rng = jax.random.PRNGKey(0)
    state_r = create_train_state(model, rng, _sample(), opt)
    state_s = create_train_state(model, rng, _sample(), sopt)
    state_q = state_s.replace(
        residuals=quant.init_residuals(state_s.params, WORLD, block)
    )
    return model, opt, sopt, state_r, state_q


def _leaves_bytes(tree):
    return [(np.asarray(x).dtype.str, np.asarray(x).tobytes())
            for x in jax.tree_util.tree_leaves(tree)]


def _l2(a, b):
    return float(np.sqrt(sum(
        float(np.sum((np.asarray(x) - np.asarray(y)) ** 2))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )))


# --------------------------------------------------------------------------
# 1. codec units
# --------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound(rng):
    """Dequantize(quantize(x)) is within half a quantization step of x for
    every element: |err| <= absmax/254 per block (absmax scaling, round to
    nearest)."""
    x = jnp.asarray(rng.normal(size=(4 * BLOCK,)).astype(np.float32))
    q, scales = quant.quantize_blocks(x, BLOCK)
    back = quant.dequantize_blocks(q, scales, BLOCK)
    err = np.abs(np.asarray(back) - np.asarray(x)).reshape(4, BLOCK)
    bound = np.abs(np.asarray(x)).reshape(4, BLOCK).max(axis=1) / 254.0
    assert (err.max(axis=1) <= bound + 1e-7).all()
    assert q.dtype == jnp.int8 and scales.dtype == jnp.float32


def test_quantize_zero_block_exact():
    x = jnp.zeros((BLOCK,), jnp.float32)
    q, scales = quant.quantize_blocks(x, BLOCK)
    back = quant.dequantize_blocks(q, scales, BLOCK)
    np.testing.assert_array_equal(np.asarray(back), 0.0)
    assert not np.isnan(np.asarray(back)).any()


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_codec_never_launders_nonfinite(bad):
    """A non-finite value anywhere in a block makes the whole dequantized
    block non-finite (the scale carries the corruption) — the guard's
    finiteness sentinel sees it exactly as on the uncompressed path."""
    x = np.ones((2 * BLOCK,), np.float32)
    x[BLOCK + 3] = bad
    q, scales = quant.quantize_blocks(jnp.asarray(x), BLOCK)
    back = np.asarray(quant.dequantize_blocks(q, scales, BLOCK))
    assert np.isfinite(back[:BLOCK]).all()          # clean block untouched
    assert not np.isfinite(back[BLOCK:]).all()      # corrupt block flagged
    overflow, _ = quant.block_stats(q, scales)
    assert int(overflow) == 1


def test_block_stats_clip_counts_rail_crowding():
    # One value at absmax per block is structural (count 0); a second
    # value at the rail makes the block "clipping". Non-max values stay
    # well below 126.5/127 of the max so rounding cannot graze the rail.
    x = np.full((BLOCK,), 0.5, np.float32)
    x[-1] = 1.0
    q, s = quant.quantize_blocks(jnp.asarray(x), BLOCK)
    _, clip0 = quant.block_stats(q, s)
    x2 = x.copy()
    x2[:4] = 1.0  # five values at the rail
    q2, s2 = quant.quantize_blocks(jnp.asarray(x2), BLOCK)
    _, clip1 = quant.block_stats(q2, s2)
    assert int(clip0) == 0 and int(clip1) == 1


def test_layout_math_and_leaf_selection():
    assert quant.quant_padded_size(48000, 8, 256) == 49152
    assert quant.quant_padded_size(2048, 8, 256) == 2048
    # chunk-alignment identity: world * padded-chunk == quant_padded_size
    for n in (1, 450, 2400, 6001, 48000):
        pchunk = collectives.shard_size(n, 8)
        cpad = pchunk + (-pchunk) % 256
        assert 8 * cpad == quant.quant_padded_size(n, 8, 256), n
    assert quant.leaf_quantizes(2048, 8, 256)
    assert not quant.leaf_quantizes(2047, 8, 256)


def test_residual_init_covers_only_quantizable_leaves():
    _, _, _, _, state_q = _states()
    # Net on 8 devices at block 256: conv2/fc1/fc2 kernels quantize
    # (2400/48000/10080 elements); conv1 (450), fc3 (840) and all biases
    # ride the f32 fallback.
    assert set(state_q.residuals) == {
        "conv2/kernel", "fc1/kernel", "fc2/kernel",
    }
    for key, leaf in state_q.residuals.items():
        assert leaf.shape[0] == WORLD and leaf.dtype == jnp.float32
        assert leaf.shape[1] % (WORLD * BLOCK) == 0


def test_wire_report_compression():
    _, _, _, state_r, _ = _states()
    rep = quant.wire_report(state_r.params, WORLD, BLOCK)
    b = rep["wire_bytes_per_step"]
    assert b["bf16"] * 2 == b["f32"]
    assert b["int8"] < b["bf16"] < b["f32"]
    # Net is small-leaf-heavy; still >2.5x vs f32. ResNet-18 (all big
    # conv kernels) clears ~3.8x.
    assert rep["compression_vs_f32"] > 2.5
    assert rep["quantized_leaves"] == 3 and rep["leaves"] == 10


def test_make_wire_codec_parsing():
    assert quant.make_wire_codec("") is None
    assert quant.make_wire_codec("f32") is None
    assert isinstance(quant.make_wire_codec("bf16"), quant.CastCodec)
    c = quant.make_wire_codec("int8", block_size=64, error_feedback=False)
    assert isinstance(c, quant.Int8BlockCodec)
    assert c.block_size == 64 and not c.error_feedback
    with pytest.raises(ValueError, match="collective_dtype"):
        quant.make_wire_codec("int4")
    with pytest.raises(ValueError, match="quant_block_size"):
        quant.make_wire_codec("int8", block_size=0)


# --------------------------------------------------------------------------
# 2. collective level
# --------------------------------------------------------------------------

def _quant_roundtrip_fns(mesh8, mean=True, error_feedback=True):
    from jax.sharding import PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    def via_quant(t, r):
        shards, new_r, stats = collectives.psum_scatter_quant(
            t, r, dist.DATA_AXIS, world=WORLD, mean=mean,
            block_size=BLOCK, error_feedback=error_feedback,
        )
        full = collectives.all_gather(shards, t, dist.DATA_AXIS)
        stats = {k: collectives.psum(v, dist.DATA_AXIS)
                 for k, v in stats.items()}
        return full, new_r, stats

    def via_f32(t):
        return collectives.all_gather(
            collectives.psum_scatter(t, dist.DATA_AXIS, world=WORLD,
                                     mean=mean), t, dist.DATA_AXIS)

    fq = jax.jit(_shard_map(via_quant, mesh8,
                            (P(dist.DATA_AXIS), P(dist.DATA_AXIS)),
                            (P(), P(dist.DATA_AXIS), P())))
    ff = jax.jit(_shard_map(via_f32, mesh8, (P(dist.DATA_AXIS),), P()))
    return fq, ff


def _per_replica_tree(rng):
    tree = {
        "big": jnp.asarray(rng.normal(size=(400, 120)).astype(np.float32)),
        "small": jnp.asarray(rng.normal(size=(5, 5, 3, 6)).astype(np.float32)),
    }
    return tree, jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (i + 1) for i in range(WORLD)]), tree
    )


def test_quantized_scatter_tracks_f32_within_codec_bound(mesh8, rng):
    tree, args = _per_replica_tree(rng)
    res = quant.init_residuals(tree, WORLD, BLOCK)
    fq, ff = _quant_roundtrip_fns(mesh8)
    (out_q, new_res, stats), out_f = fq(args, res), ff(args)
    a, b = np.asarray(out_q["big"]), np.asarray(out_f["big"])
    assert np.abs(a - b).max() / np.abs(b).max() < 0.01
    assert not np.array_equal(a, b), "int8 wire produced bitwise f32?"
    # Small leaf took the f32 fallback: bitwise.
    np.testing.assert_array_equal(np.asarray(out_q["small"]),
                                  np.asarray(out_f["small"]))
    assert int(stats["overflow"]) == 0
    # The residual is exactly the rounding error of what went on the wire:
    # bounded by one quantization step of the largest block.
    step_bound = np.abs(np.asarray(args["big"])).max() / 126.0
    assert 0 < np.abs(np.asarray(new_res["big"])).max() < step_bound


def test_quantized_scatter_shard_layout_matches_shard_slice(mesh8, rng):
    """Replica i's quantized-reduced shard covers EXACTLY the elements
    `shard_slice` hands it for the params — the positional contract the
    sharded optimizer pairs them by. Proven by gathering the shards and
    comparing to the full quantized mean (already ≈f32): any chunk
    misalignment would garble the reassembled leaf entirely."""
    tree, args = _per_replica_tree(rng)
    res = quant.init_residuals(tree, WORLD, BLOCK)
    fq, ff = _quant_roundtrip_fns(mesh8)
    (out_q, _, _), out_f = fq(args, res), ff(args)
    # Alignment error would show as O(|x|) garbage, not O(absmax/254).
    for k in tree:
        a, b = np.asarray(out_q[k]), np.asarray(out_f[k])
        assert np.abs(a - b).max() <= np.abs(b).max() * 0.01 + 1e-6


def test_nan_propagates_through_quantized_scatter(mesh8, rng):
    tree, args = _per_replica_tree(rng)
    bad = dict(args)
    bad["big"] = bad["big"].at[3, 7, 7].set(np.nan)
    res = quant.init_residuals(tree, WORLD, BLOCK)
    fq, _ = _quant_roundtrip_fns(mesh8)
    out, _, stats = fq(bad, res)
    assert np.isnan(np.asarray(out["big"])).any()
    assert int(stats["overflow"]) >= 1


def test_all_gather_codecs_roundtrip(mesh8, rng):
    from jax.sharding import PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    x = jnp.asarray(rng.normal(size=(450,)).astype(np.float32))

    def roundtrip(codec):
        def f(v):
            shards = collectives.shard_slice(v, dist.DATA_AXIS, world=WORLD)
            return collectives.all_gather(shards, v, dist.DATA_AXIS,
                                          codec=codec)
        return jax.jit(_shard_map(f, mesh8, (P(),), P()))(x)

    np.testing.assert_array_equal(np.asarray(roundtrip(None)), np.asarray(x))
    bf = np.asarray(roundtrip(quant.CastCodec(jnp.bfloat16)))
    np.testing.assert_allclose(bf, np.asarray(x), rtol=0.01, atol=1e-2)
    q8 = np.asarray(roundtrip(quant.Int8BlockCodec(block_size=64)))
    np.testing.assert_allclose(q8, np.asarray(x), rtol=0.02, atol=2e-2)
    assert not np.array_equal(q8, np.asarray(x))


# --------------------------------------------------------------------------
# 3. the wire-dtype parity harness (f32 / bf16 / int8 vs replicated f32)
# --------------------------------------------------------------------------

#: (collective_dtype, bucket_mb, bitwise, atol) — the documented accuracy
#: contract of each wire format over a 6-step fixed-seed run (docs/PERF.md
#: table), unbucketed AND under the bucketed overlap schedule
#: (`train.bucket_mb`): bucketing concatenates, it never changes the
#: per-element cross-replica arithmetic, so each wire dtype keeps its
#: unbucketed tolerance (bucketed f32 stays bitwise on this backend —
#: the documented cross-backend contract is reduction-order tolerance).
WIRE_CONTRACT = [
    ("", 0.0, True, 0.0),
    ("bf16", 0.0, False, 4e-3),
    ("int8", 0.0, False, 8e-3),
    ("", 0.05, True, 0.0),
    ("bf16", 0.05, False, 4e-3),
    ("int8", 0.05, False, 8e-3),
]


@pytest.mark.parametrize("wire,bucket_mb,bitwise,atol", WIRE_CONTRACT,
                         ids=lambda v: str(v) if v != "" else "f32")
def test_wire_dtype_parity_harness(mesh8, wire, bucket_mb, bitwise, atol):
    """One harness, all three wire dtypes (the PR-4 bf16 path gains the
    fixed-seed tolerance A/B it never had), bucketed × unbucketed: sharded
    update with the given wire format vs the replicated f32 reference. f32
    must be bitwise; the compressed formats must be within their documented
    tolerance AND not bitwise (proof they actually ran compressed)."""
    from tpu_dp.parallel import bucketing

    model, opt, sopt, state_r, state_q = _states()
    if bucket_mb and wire == "int8":
        state_q = state_q.replace(residuals=quant.init_residuals(
            state_q.params, WORLD, BLOCK,
            bucket_bytes=bucketing.parse_bucket_mb(bucket_mb)))
    step_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             explicit=True)
    step_w = make_train_step(
        model, sopt, mesh8, constant_lr(0.05), update_sharding="sharded",
        collective_dtype=wire or None, bucket_mb=bucket_mb,
    )
    sr = _copy(state_r)
    sw = _copy(state_q if wire == "int8" else
               state_q.replace(residuals={}))
    for i in range(6):
        batch = _make_batch(i)
        sr, _ = step_r(sr, batch)
        sw, _ = step_w(sw, batch)
    identical = True
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(sw.params)):
        a, b = np.asarray(a), np.asarray(b)
        if bitwise:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, atol=atol)
        identical &= bool(np.array_equal(a, b))
    if not bitwise:
        assert not identical, f"{wire} wire produced bitwise-f32 results?"


def _flat(tree):
    return np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(tree)])


@pytest.mark.parametrize("bucket_mb", [0.0, 0.01],
                         ids=["per_leaf", "bucketed"])
def test_error_feedback_telescopes_over_24_steps(mesh8, bucket_mb):
    """The residual path does real work, shown on the real train step over
    24 fixed-seed steps, per leaf and per bucket (0.01 MB buckets):

        sum_k applied_k  =  sum_k true_k  -  mean_replicas(residual_K)

    where ``true_k`` is the f32 mean gradient AT the quantized run's own
    parameters (read from a plain-SGD lr=1 replicated step) and
    ``applied_k`` is what the int8 wire delivered (momentum-free SGD, so
    the parameter displacement is -lr * sum applied). With error feedback
    the accumulated wire error IS the pending residual — one step's
    rounding, however long the run; without it the errors add up.

    Final parameters are deliberately not compared with the f32
    *trajectory*: with 16 samples a step, a parameter difference of 8e-4
    flips one sample's ReLU/max-pool path and moves the f32 gradient by
    0.15 (a sixteenth of its norm, step 6 of the bucketed run on JAX
    0.9.0), after which the distance between trajectories measures that
    divergence and not the wire — there the EF run flips first and ends
    2.3x FARTHER from f32 than the ablation while its own accumulated
    wire error stays at one step's rounding."""
    from tpu_dp.parallel import bucketing

    lr = 0.01
    model, _, sopt, state_g, state_q = _states(momentum=0.0)
    if bucket_mb:
        state_q = state_q.replace(residuals=quant.init_residuals(
            state_q.params, WORLD, BLOCK,
            bucket_bytes=bucketing.parse_bucket_mb(bucket_mb)))
    probe = make_train_step(model, SGD(momentum=0.0), mesh8,
                            constant_lr(1.0), explicit=True)

    def true_grad(params, batch):
        before = _flat(params)
        after, _ = probe(_copy(state_g).replace(params=_copy(params)), batch)
        return before - _flat(after.params)

    def run(error_feedback):
        step = make_train_step(
            model, sopt, mesh8, constant_lr(lr), update_sharding="sharded",
            collective_dtype="int8", bucket_mb=bucket_mb,
            quant_error_feedback=error_feedback)
        state = _copy(state_q)
        start, true_sum, errs = _flat(state.params), 0.0, []
        for k in range(24):
            batch = _make_batch(k)
            true_sum = true_sum + true_grad(state.params, batch)
            state, _ = step(state, batch)
            applied_sum = (start - _flat(state.params)) / lr
            errs.append(float(np.linalg.norm(applied_sum - true_sum)))
        return state, errs

    state_ef, err_ef = run(True)
    state_no, err_no = run(False)
    pending = float(np.linalg.norm(np.concatenate(
        [np.asarray(r, np.float64).mean(axis=0)
         for r in jax.tree_util.tree_leaves(state_ef.residuals)])))
    # The identity, to the probe's f32 rounding.
    np.testing.assert_allclose(err_ef[-1], pending, rtol=1e-2)
    # Bounded by one step's rounding (the first step has no residual yet,
    # so it is the same number in both runs), at every horizon.
    assert err_ef[0] == pytest.approx(err_no[0], rel=1e-6)
    assert max(err_ef) < 1.5 * err_ef[0], err_ef
    # The ablation accumulates: measured ~4x by step 24, asserted at 2x.
    assert err_no[-1] > 2 * err_ef[-1], (err_ef[-1], err_no[-1])
    assert err_no[-1] > 3 * err_no[0], err_no
    # The ablation's residuals were never consumed nor updated.
    for leaf in jax.tree_util.tree_leaves(state_no.residuals):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    for leaf in jax.tree_util.tree_leaves(state_ef.residuals):
        assert np.abs(np.asarray(leaf)).max() > 0


def test_int8_multi_step_window_tracks_f32(mesh8):
    """The quantized wire composes with the windowed device-side loop."""
    model, opt, sopt, state_r, state_q = _states()
    K = 4
    loop_r = make_train_step(model, opt, mesh8, constant_lr(0.05),
                             feed="window", num_steps=K)
    loop_q = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                             feed="window", num_steps=K,
                             update_sharding="sharded",
                             collective_dtype="int8")
    batches = [_make_batch(100 + i) for i in range(K)]
    pool = {
        "image": np.stack([b["image"] for b in batches]),
        "label": np.stack([b["label"] for b in batches]),
    }
    sr, _ = loop_r(_copy(state_r), pool)
    sq, mq = loop_q(_copy(state_q), pool)
    assert int(sq.step) == K
    assert mq["quant_overflow"].shape == (K,)
    for a, b in zip(jax.tree_util.tree_leaves(sr.params),
                    jax.tree_util.tree_leaves(sq.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=6e-3)


def test_residual_memory_is_flat_sharded(mesh8):
    """Residuals live like the opt state: per-replica addressable shard =
    one [1, qpad] row per leaf — world-sharded, never replicated."""
    model, _, sopt, _, state_q = _states()
    step = make_train_step(model, sopt, mesh8, constant_lr(0.05),
                           update_sharding="sharded",
                           collective_dtype="int8")
    new_state, _ = step(_copy(state_q), _make_batch(0))
    for key, leaf in new_state.residuals.items():
        shards = leaf.addressable_shards
        assert len(shards) == WORLD, key
        assert shards[0].data.shape == (1, leaf.shape[1]), key


def test_factory_validation():
    mesh = dist.data_mesh()
    sopt = shard_optimizer(SGD(momentum=0.9), WORLD)
    with pytest.raises(ValueError, match="quant_block_size"):
        make_train_step(Net(), sopt, mesh, constant_lr(0.05),
                        update_sharding="sharded",
                        collective_dtype="int8",
                        quant_block_size=0)
    with pytest.raises(ValueError, match="collective_dtype"):
        make_train_step(Net(), SGD(momentum=0.9), mesh,
                        constant_lr(0.05),
                        collective_dtype="int8", explicit=True)


# --------------------------------------------------------------------------
# 5. guardrails interaction
# --------------------------------------------------------------------------

def test_sentinel_reads_dequantized_health_and_skips_nan(mesh8):
    """The sentinel's health summary sits AFTER dequantize-and-sum: a clean
    step reports a finite grad norm from the dequantized shards; an
    injected NaN survives the codec (scale propagation), the grad norm
    goes non-finite, the update is withheld, and the ENTIRE state —
    params, opt shards, step counter, AND the error-feedback residuals —
    is bitwise the pre-step state."""
    from tpu_dp.train.step import default_guard_in

    model, _, sopt, _, state_q = _states()
    step = make_train_step(
        model, sopt, mesh8, constant_lr(0.05), update_sharding="sharded",
        collective_dtype="int8", sentinel=True,
    )
    s0 = _copy(state_q)
    before = _leaves_bytes(s0)

    clean, m_clean = step(s0, _make_batch(0), default_guard_in())
    assert int(m_clean["applied"]) == 1
    assert np.isfinite(float(m_clean["grad_norm"]))
    assert float(m_clean["grad_norm"]) > 0

    gi = default_guard_in()
    gi["fault_step"] = np.int32(1)  # clean step advanced the counter to 1
    gi["fault_scale"] = np.float32(np.nan)
    poisoned, m_bad = step(_copy(clean), _make_batch(1), gi)
    assert int(m_bad["applied"]) == 0
    assert not np.isfinite(float(m_bad["grad_norm"]))
    # Quarantine contract, residuals included: as if the batch never was.
    assert _leaves_bytes(poisoned) == _leaves_bytes(clean)
    assert _leaves_bytes(clean) != before  # ...and the clean step did apply


def test_trainer_nan_fault_skips_under_int8(tmp_path):
    """`TPU_DP_FAULT`-style nan injection through the full Trainer with
    int8 collectives + guard.action=skip behaves exactly like the
    uncompressed guard lane: one quarantine record, the run completes, the
    final params are finite."""
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 16
    c.data.batch_size = 8
    c.data.prefetch = 1
    c.train.epochs = 1
    c.train.log_every = 100
    c.train.eval_at_end = False
    c.train.steps_per_call = 1
    c.train.ckpt_dir = str(tmp_path / "ck")
    c.train.update_sharding = "sharded"
    c.train.collective_dtype = "int8"
    c.optim.lr = 0.05
    c.guard.enabled = True
    c.guard.action = "skip"
    c.resilience.fault = "nan:step=3"

    t = Trainer(c)
    t.fit()
    recs = [json.loads(line) for line in
            t.quarantine_path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["quarantine"]
    assert recs[0]["step"] in (3, 4)  # the armed fault's boundary step
    # The skipped step withheld its update: 8 planned, 7 applied.
    assert int(t.state.step) == 7
    for leaf in jax.tree_util.tree_leaves(t.state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # Codec counters flowed through the guard's per-window fetch.
    from tpu_dp.obs.counters import counters
    assert counters.get("quant.overflow") >= 1  # the nan-poisoned blocks


# --------------------------------------------------------------------------
# 6. checkpoint / resume
# --------------------------------------------------------------------------

def test_residuals_roundtrip_same_layout(tmp_path):
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model, _, sopt, _, state_q = _states()
    state_q = state_q.replace(residuals={
        k: v + np.float32(0.25) * (i + 1)
        for i, (k, v) in enumerate(sorted(state_q.residuals.items()))
    })
    save_checkpoint(tmp_path, state_q, {"epoch": 0})
    restored, _ = load_checkpoint(
        tmp_path, _states()[4])
    assert _leaves_bytes(restored.residuals) == _leaves_bytes(
        state_q.residuals)


def test_residuals_reshard_across_world_sizes(tmp_path):
    """World 8 → world 4: the TOTAL pending correction (sum of every
    replica's residual, in leaf element order) is preserved exactly —
    replica 0 of the new world owes the whole debt, everyone else zero."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model = Net()
    rng = jax.random.PRNGKey(0)
    opt8 = shard_optimizer(SGD(momentum=0.9), 8)
    opt4 = shard_optimizer(SGD(momentum=0.9), 4)
    state8 = create_train_state(model, rng, _sample(), opt8)
    res8 = quant.init_residuals(state8.params, 8, BLOCK)
    # Recognizable per-replica errors, zero in each chunk's pad region
    # (the invariant a real trajectory maintains).
    filled = {}
    gen = np.random.default_rng(3)
    for key, leaf in res8.items():
        n = {p: l for p, l in
             [("/".join(str(getattr(x, 'key', x)) for x in path), lf)
              for path, lf in
              jax.tree_util.tree_leaves_with_path(state8.params)]
             }[key].size
        pchunk = collectives.shard_size(n, 8)
        cpad = leaf.shape[1] // 8
        rows = gen.normal(size=(8, 8, cpad)).astype(np.float32) * 1e-3
        rows[:, :, pchunk:] = 0.0
        filled[key] = jnp.asarray(rows.reshape(8, -1))
    state8 = state8.replace(residuals=filled)
    save_checkpoint(tmp_path / "w8", state8, {"epoch": 0})

    state4 = create_train_state(model, rng, _sample(), opt4)
    state4 = state4.replace(
        residuals=quant.init_residuals(state4.params, 4, BLOCK))
    restored, _ = load_checkpoint(tmp_path / "w8", state4)
    param_sizes = {
        "/".join(str(getattr(x, "key", x)) for x in path): leaf.size
        for path, leaf in jax.tree_util.tree_leaves_with_path(state8.params)
    }
    # conv2 (2400 elems) stops quantizing at world 4 (needs >= 4*256*...?
    # 2400 >= 1024: still quantizes). Compare pending sums leaf-wise.
    for key, saved in filled.items():
        n = param_sizes[key]
        pchunk8 = collectives.shard_size(n, 8)
        pending = (np.asarray(saved).sum(axis=0)
                   .reshape(8, -1)[:, :pchunk8].reshape(-1)[:n])
        got = np.asarray(restored.residuals[key])
        pchunk4 = collectives.shard_size(n, 4)
        got_pending = (got.sum(axis=0)
                       .reshape(4, -1)[:, :pchunk4].reshape(-1)[:n])
        np.testing.assert_allclose(got_pending, pending, atol=1e-7)
        np.testing.assert_array_equal(got[1:], 0.0)


def test_precodec_checkpoint_loads_with_zero_residuals(tmp_path):
    """A checkpoint written with the codec OFF (residuals={} — byte-wise
    what every pre-codec checkpoint serializes to) restores into an
    int8-enabled target with zero-initialized residuals; and a quantized
    checkpoint restores into a codec-off target with residuals dropped."""
    from tpu_dp.checkpoint import load_checkpoint, save_checkpoint

    model, _, sopt, _, state_q = _states()
    plain = state_q.replace(residuals={})
    save_checkpoint(tmp_path / "plain", plain, {"epoch": 0})
    restored, _ = load_checkpoint(tmp_path / "plain", state_q)
    assert set(restored.residuals) == set(state_q.residuals)
    for leaf in jax.tree_util.tree_leaves(restored.residuals):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)

    state_q2 = state_q.replace(residuals={
        k: v + 1.0 for k, v in state_q.residuals.items()})
    save_checkpoint(tmp_path / "quant", state_q2, {"epoch": 0})
    dropped, _ = load_checkpoint(tmp_path / "quant", plain)
    assert dropped.residuals == {}

    # A GENUINELY old checkpoint (pre-codec msgpack: no "residuals" key at
    # all, the byte format every earlier PR wrote) restores the same way.
    from flax import serialization

    from tpu_dp.checkpoint import _to_host

    sd = serialization.to_state_dict(_to_host(plain))
    del sd["residuals"]
    old = tmp_path / "old"
    old.mkdir()
    (old / "state.msgpack").write_bytes(serialization.msgpack_serialize(sd))
    (old / "meta.json").write_text("{}")
    from_old, _ = load_checkpoint(old, state_q)
    assert set(from_old.residuals) == set(state_q.residuals)
    for leaf in jax.tree_util.tree_leaves(from_old.residuals):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


def test_preempt_resume_with_int8_residuals(tmp_path):
    """The kill+auto-resume contract with the quantized wire: a preempted
    int8 run resumes from its snapshot (error-feedback residuals included)
    and finishes bitwise-identical — residuals too — to an uninterrupted
    int8 run."""
    from tpu_dp.resilience import PreemptedError
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    def int8_cfg(sub, **kw):
        c = Config()
        c.data.dataset = "synthetic"
        c.data.synthetic_train_size = 64
        c.data.synthetic_test_size = 16
        c.data.batch_size = 8
        c.data.prefetch = 1
        c.train.epochs = 2
        c.train.log_every = 100
        c.train.eval_at_end = False
        c.train.ckpt_dir = str(tmp_path / sub / "ck")
        c.train.update_sharding = "sharded"
        c.train.collective_dtype = "int8"
        c.optim.lr = 0.05
        for k, v in kw.items():
            section, name = k.split(".")
            setattr(getattr(c, section), name, v)
        return c

    control = Trainer(int8_cfg("control"))
    control.fit()
    assert int(control.state.step) == 16
    assert any(np.abs(np.asarray(v)).max() > 0
               for v in jax.tree_util.tree_leaves(control.state.residuals))

    cfg = int8_cfg("run")
    cfg.resilience.snapshot_every_steps = 3
    cfg.resilience.fault = "preempt:step=11"
    with pytest.raises(PreemptedError):
        Trainer(cfg).fit()

    cfg2 = int8_cfg("run")
    cfg2.resilience.snapshot_every_steps = 3
    cfg2.train.resume = True
    resumed = Trainer(cfg2)
    resumed.fit()
    assert int(resumed.state.step) == 16
    assert _leaves_bytes(resumed.state) == _leaves_bytes(control.state)


# --------------------------------------------------------------------------
# 7. analyzer (Level 2; Level 3 lives in test_hlo_analysis.py)
# --------------------------------------------------------------------------

@pytest.mark.analysis
def test_gradsync_counts_int8_exchange_exactly_once():
    from tpu_dp.analysis import gradsync

    for accum in (1, 2):
        findings, report = gradsync.verify_repo_step(
            accum_steps=accum, update_sharding="sharded",
            collective_dtype="int8",
        )
        assert findings == []
        assert report and all(c == 1 for c in report.values()), report


@pytest.mark.analysis
def test_gradsync_double_int8_exchange_fires_dp202():
    """A gradient routed through TWO int8 exchanges counts twice (DP202);
    the f32 scales exchange alongside a single payload exchange does NOT
    inflate the count (it is wire metadata, like the params all-gather)."""
    from jax import lax

    from tpu_dp.analysis.gradsync import verify_local_step

    def exchange(v):
        q = jnp.clip(jnp.round(v), -127, 127).astype(jnp.int8)
        scales = jnp.ones((8,), jnp.float32)
        qx = lax.all_to_all(q.reshape(8, -1), "data",
                            split_axis=0, concat_axis=0, tiled=True)
        sx = lax.all_to_all(scales.reshape(8, 1), "data",
                            split_axis=0, concat_axis=0, tiled=True)
        return (jnp.sum(qx.astype(jnp.float32), axis=0)
                * jnp.sum(sx) / jnp.sum(sx))

    def single(state, batch):
        g = state["params"]["w"]
        shard = exchange(g)
        return {"params": {"w": state["params"]["w"][: shard.size] - shard}}

    def double(state, batch):
        g = state["params"]["w"]
        shard = exchange(jnp.tile(exchange(g), 8))
        return {"params": {"w": state["params"]["w"][: shard.size] - shard}}

    state = {"params": {"w": jnp.zeros((64,), jnp.float32)}}
    ok, report = verify_local_step(single, (state, None), world=8)
    assert ok == [] and list(report.values()) == [1]
    bad, report2 = verify_local_step(double, (state, None), world=8)
    assert [f.rule for f in bad] == ["DP202"] and list(
        report2.values()) == [2]


# --------------------------------------------------------------------------
# 8. obs: counters → schema-3 records → obsctl diff
# --------------------------------------------------------------------------

@pytest.mark.obs
def test_trainer_publishes_quant_counters_into_metrics(tmp_path):
    """An obs=full int8 run stamps quant.overflow / quant.clip_blocks into
    its schema-3 records via the counter snapshots, and `obsctl diff`
    gates on them: identical baseline passes, a lower-count baseline makes
    the run a regression."""
    from tpu_dp.config import Config
    from tpu_dp.obs.counters import counters
    from tpu_dp.obs.obsctl import (
        RunArtifacts, diff_verdict, load_baseline, run_efficiency,
    )
    from tpu_dp.train.trainer import Trainer

    counters.reset()
    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 32
    c.data.synthetic_test_size = 16
    c.data.batch_size = 8
    c.data.prefetch = 1
    c.train.epochs = 1
    c.train.log_every = 100
    c.train.eval_at_end = False
    c.train.obs = "full"
    c.train.ckpt_dir = str(tmp_path / "ck")
    c.train.update_sharding = "sharded"
    c.train.collective_dtype = "int8"
    t = Trainer(c)
    t.fit()

    records = [json.loads(line) for line in
               (tmp_path / "ck" / "metrics.jsonl").read_text().splitlines()]
    stamped = [r for r in records
               if isinstance(r.get("counters"), dict)
               and "quant.overflow" in r["counters"]]
    assert stamped, "no schema-3 record carries the quant counters"
    assert all(r.get("schema") == 3 for r in stamped)
    last = stamped[-1]["counters"]
    assert last["quant.overflow"] == 0  # clean run: explicit zero

    run = run_efficiency(RunArtifacts(tmp_path / "ck"))
    # Rates, not cumulative counts: a long healthy run must not read as a
    # regression against a short bench baseline (same unit both sides).
    assert run["quant_overflow_per_step"] == 0
    assert run["quant_clip_blocks_per_step"] is not None

    base_ok = {"mfu": None, "goodput": None, "p95_ms": None,
               "quant_overflow_per_step": run["quant_overflow_per_step"],
               "quant_clip_blocks_per_step":
                   run["quant_clip_blocks_per_step"]}
    v = diff_verdict(run, base_ok, tolerance=0.1)
    assert not v["regressed"]
    base_strict = dict(base_ok, quant_clip_blocks_per_step=-1)
    v2 = diff_verdict(run, base_strict, tolerance=0.1)
    if run["quant_clip_blocks_per_step"] > 0:
        assert v2["regressed"]

    # BENCH-record shape: the quant block's N-step totals normalize to
    # per-step rates in the baseline loader.
    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps({
        "mfu": 0.5,
        "quant": {"overflow": 0, "clip_blocks": 8, "stats_steps": 4},
    }))
    loaded = load_baseline(bench)
    assert loaded["quant_overflow_per_step"] == 0
    assert loaded["quant_clip_blocks_per_step"] == 2.0


@pytest.mark.obs
def test_diff_verdict_skips_quant_for_unquantized_runs():
    from tpu_dp.obs.obsctl import diff_verdict

    run = {"mfu": 0.5, "goodput": 0.9, "p95_ms": 10.0,
           "quant_overflow_per_step": None,
           "quant_clip_blocks_per_step": None}
    base = {"mfu": 0.5, "goodput": 0.9, "p95_ms": 10.0}
    v = diff_verdict(run, base, tolerance=0.05)
    assert not v["regressed"]
    skipped = {c["signal"] for c in v["checks"]
               if c["verdict"] == "skipped"}
    # The comm-attribution signals follow the same contract: a run that
    # never profiled a comm window is skipped, never compared as 0 — as
    # is the throughput headline when neither side measured it.
    assert skipped == {"quant_overflow_per_step",
                       "quant_clip_blocks_per_step",
                       "comm_ms", "exposed_comm_ms", "overlap_frac",
                       "img_per_sec_per_chip"}
