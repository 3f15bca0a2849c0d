"""Level-3 dplint (`tpu_dp.analysis.hlo` + `recompile`) — the compiled
artifact.

What levels 1–2 cannot see is exactly what this file proves:

1. The *shipped* step programs compile to the artifact the paper's
   DDP-parity claim rests on — one combinable gradient all-reduce group
   plus the two metric reductions, no all-gathers, every donated buffer
   aliased (DP303's "shipped steps are proven aliased" half).
2. The collective-schedule fingerprint is deterministic (same program →
   same digest; different program → different digest) and the cross-rank
   startup hook accepts/validates digests.
3. Dropped donation is demonstrably caught: a program whose donated buffer
   cannot alias (dtype change) fails DP303.
4. The RecompileGuard counts real post-warmup retraces and only those.

Fast lane: ``pytest -m analysis``.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tpu_dp.analysis import hlo, recompile
from tpu_dp.analysis.recompile import RecompileError, RecompileGuard
from tpu_dp.parallel.collectives import all_gather_invariant

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. the shipped compiled artifact ------------------------------------

@pytest.fixture(scope="module")
def repo_hlo():
    findings, artifact = hlo.verify_repo_hlo(accum_steps=(1,), world=8)
    return findings, artifact


def test_shipped_steps_compile_clean(repo_hlo):
    findings, _ = repo_hlo
    assert findings == []


def test_shipped_train_steps_are_proven_aliased(repo_hlo):
    """Every donated buffer of every train-step program survives as a real
    input_output_alias entry in the compiled module — donation was not
    silently dropped (DP303's positive half)."""
    _, artifact = repo_hlo
    train_programs = {k: v for k, v in artifact["programs"].items()
                      if k != "eval_step"}
    assert train_programs
    for name, rec in train_programs.items():
        assert rec["donated_inputs"] > 0, name
        assert rec["aliased_inputs"] == rec["donated_inputs"], (
            f"{name}: {rec['aliased_inputs']}/{rec['donated_inputs']} "
            f"donated buffers aliased"
        )


def test_shipped_steps_have_one_combinable_gradient_group(repo_hlo):
    """Replicated-mode train-step modules contain only all-reduces: a
    single combinable gradient group (full-mesh replica groups, add) plus
    the two metric scalars — no all-gather/reduce-scatter/permute
    anywhere. (Serve programs have their own schedule contract —
    `test_serve_programs_in_artifact`.)"""
    _, artifact = repo_hlo
    checked = 0
    for name, rec in artifact["programs"].items():
        if rec["update_sharding"] != "replicated" \
                or name.startswith("serve_step"):
            continue
        checked += 1
        assert set(rec["counts"]) <= {"all-reduce"}, (name, rec["counts"])
        groups = {op["replica_groups"] for op in rec["collectives"]}
        assert len(groups) <= 1, (name, groups)
        if name != "eval_step":
            assert rec["grad_reduce_ops"] >= 1, name
        assert rec["metric_allreduce_ops"] == 2, (name, rec)
    assert checked >= 3


def test_serve_programs_in_artifact(repo_hlo):
    """The serving forwards are fingerprinted alongside the train steps
    (docs/SERVING.md "Analyzer contract"): a world-divisible bucket
    compiles to exactly the two stats reductions (one [C] vector, one
    scalar; identical full-mesh groups, add) with nothing else, a
    sub-world bucket compiles to ZERO collectives, and the donated
    ServeStats leaves are proven aliased in both."""
    _, artifact = repo_hlo
    serve = {k: v for k, v in artifact["programs"].items()
             if k.startswith("serve_step")}
    assert set(serve) == {"serve_step@b16", "serve_step@b2"}
    big, small = serve["serve_step@b16"], serve["serve_step@b2"]
    # Fan-out bucket: batch sharded over data; only the stats reduce.
    # (XLA's combiner may merge the two into one tuple-shaped all-reduce.)
    assert set(big["counts"]) == {"all-reduce"}, big["counts"]
    assert big["metric_allreduce_ops"] == 1
    assert sum("f32[10]" in op["shape"] for op in big["collectives"]) == 1
    groups = {op["replica_groups"] for op in big["collectives"]}
    reductions = {op["reduction"] for op in big["collectives"]}
    assert len(groups) == 1 and reductions == {"add"}, (groups, reductions)
    # Sub-world bucket: replicated compute, zero collectives.
    assert small["counts"] == {}, small["counts"]
    # Donated-buffer forward: the ServeStats pytree aliases in place.
    for name, rec in serve.items():
        assert rec["aliased_inputs"] == rec["donated_inputs"] == 2, (
            name, rec)
    assert big["digest"] != small["digest"]


def test_shipped_sharded_steps_have_scatter_update_gather_schedule(repo_hlo):
    """Sharded-mode train-step modules compile to the second legal
    schedule: one combinable reduce-scatter group + one all-gather group
    over the identical full-mesh replica groups, the two metric scalars,
    and NO non-scalar all-reduce (the gradient path really went through
    the scatter)."""
    _, artifact = repo_hlo
    sharded = {k: v for k, v in artifact["programs"].items()
               if v["update_sharding"] == "sharded"
               and v.get("wire", "f32") != "int8"}
    assert sharded, "no sharded programs in the shipped artifact"
    for name, rec in sharded.items():
        counts = rec["counts"]
        assert set(counts) == {"reduce-scatter", "all-gather", "all-reduce"}, (
            name, counts)
        by_kind = {}
        for op in rec["collectives"]:
            by_kind.setdefault(op["kind"], []).append(op)
        # One combinable group per collective kind, scatter == gather.
        scatter_groups = {op["replica_groups"]
                          for op in by_kind["reduce-scatter"]}
        gather_groups = {op["replica_groups"] for op in by_kind["all-gather"]}
        assert len(scatter_groups) == 1 and scatter_groups == gather_groups, (
            name, scatter_groups, gather_groups)
        assert all(op["reduction"] == "add"
                   for op in by_kind["reduce-scatter"]), name
        # Every all-reduce left is a declared metric scalar: 2 for the
        # plain programs, 3 with the sentinel (its cross-shard grad-norm
        # psum is the one collective guardrails add — see
        # `test_sentinel_programs_in_artifact`).
        declared = 3 if "sentinel" in name else 2
        assert not any(re.search(r"\[\d", op["shape"])
                       for op in by_kind["all-reduce"]), (
            name, by_kind["all-reduce"])
        assert rec["metric_allreduce_ops"] == declared, (
            name, rec["metric_allreduce_ops"])
        assert rec["grad_reduce_ops"] == len(by_kind["reduce-scatter"]) >= 1
        # Donation survives the sharded layout: opt-state shards alias too.
        assert rec["aliased_inputs"] == rec["donated_inputs"] > 0, name


def test_shipped_int8_steps_have_quantized_schedule(repo_hlo):
    """The quantized-wire programs (`train.collective_dtype=int8`) compile
    to the THIRD legal schedule: int8 payload all-to-alls + f32 scale
    all-to-alls over the one full-mesh group for the quantizable leaves,
    plain reduce-scatters for the small-leaf fallback, the params
    all-gather, 4 declared metric scalars (loss, correct, overflow, clip;
    +1 for the sentinel's grad-norm psum) — and NO non-scalar all-reduce
    (every gradient leaf really went through a scatter path). Donation
    survives, residual buffers included."""
    _, artifact = repo_hlo
    int8 = {k: v for k, v in artifact["programs"].items()
            if v.get("wire") == "int8"}
    assert set(int8) == {
        "train_step[shard_map,sharded,int8]@accum1",
        "multi_step[sharded,int8]@w2",
        "train_step[shard_map,sharded,int8,sentinel]@accum1",
        "train_step[shard_map,sharded,int8,bucketed]@accum1",
    }
    for name, rec in int8.items():
        counts = rec["counts"]
        assert counts.get("all-to-all", 0) >= 2, (name, counts)
        by_kind = {}
        for op in rec["collectives"]:
            by_kind.setdefault(op["kind"], []).append(op)
        payload = [op for op in by_kind["all-to-all"] if "s8[" in op["shape"]]
        scales = [op for op in by_kind["all-to-all"] if "f32[" in op["shape"]]
        assert payload, (name, "no int8 payload exchange compiled")
        assert len(payload) + len(scales) == len(by_kind["all-to-all"])
        # One exchange group, matching the params gather's.
        groups = {op["replica_groups"] for op in by_kind["all-to-all"]}
        gather_groups = {op["replica_groups"] for op in by_kind["all-gather"]}
        assert len(groups) == 1 and groups == gather_groups, (
            name, groups, gather_groups)
        # Small-leaf fallback keeps the uncompressed scatter — except in
        # the bucketed schedule when every bucket clears the quantization
        # threshold (small leaves compress INSIDE their bucket, which is
        # the bucketed world's point; the recorded layout says which).
        buckets = rec.get("buckets")
        expect_rs = (any(b["wire"] != "int8" for b in buckets)
                     if buckets is not None else True)
        assert bool(by_kind.get("reduce-scatter")) == expect_rs, name
        non_scalar_ar = [op for op in by_kind.get("all-reduce", [])
                         if "[]" not in op["shape"]]
        assert non_scalar_ar == [], (name, non_scalar_ar)
        declared = 5 if "sentinel" in name else 4
        assert rec["metric_allreduce_ops"] == declared, (
            name, rec["metric_allreduce_ops"])
        # Donation survives the residual state: every donated leaf —
        # params, opt shards, AND the f32[world, qpad] residuals — aliases.
        assert rec["aliased_inputs"] == rec["donated_inputs"] > 0, name
    # The wire format is fingerprint-visible: an int8-configured rank
    # cannot impersonate an uncompressed one (DP304 catches the config
    # divergence before the first mismatched collective deadlocks).
    progs = artifact["programs"]
    assert (progs["train_step[shard_map,sharded,int8]@accum1"]["digest"]
            != progs["train_step[shard_map,sharded]@accum1"]["digest"])
    # ... and so is the bucket layout: a rank whose train.bucket_mb
    # diverged compiles a different ordered schedule.
    assert (progs["train_step[shard_map,sharded,int8,bucketed]@accum1"]
            ["digest"]
            != progs["train_step[shard_map,sharded,int8]@accum1"]["digest"])


def test_no_int8_wire_ops_outside_opted_in_programs(repo_hlo):
    """The blanket no-leak guarantee: across EVERY shipped program that did
    not opt into the quantized wire — GSPMD and shard_map train steps,
    sharded f32/bf16 steps, multi-step windows, eval, serve buckets,
    sentinel variants — the compiled module contains zero all-to-all ops
    and zero int8-typed collectives of any kind. Compression can never
    silently leak into a program that didn't ask for it."""
    _, artifact = repo_hlo
    checked = 0
    for name, rec in artifact["programs"].items():
        if rec.get("wire") == "int8":
            continue
        checked += 1
        assert "all-to-all" not in rec["counts"], (name, rec["counts"])
        int8_ops = [op for op in rec["collectives"]
                    if "s8[" in op["shape"] or "u8[" in op["shape"]]
        assert int8_ops == [], (name, int8_ops)
    assert checked >= 10  # the full non-quantized program matrix


def test_dp301_fires_on_int8_leak_and_missing_payload():
    """DP301's int8 rules both ways: an all-to-all in a NON-int8 program
    is flagged as a compression leak, and an int8-declared program with no
    s8 exchange is flagged as silently uncompressed."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel import dist
    from tpu_dp.train.step import _shard_map

    mesh = dist.data_mesh()

    def leak(g):
        q = jnp.clip(jnp.round(g), -127, 127).astype(jnp.int8)
        qx = jax.lax.all_to_all(q.reshape(8, -1), dist.DATA_AXIS,
                                split_axis=0, concat_axis=0, tiled=True)
        return jnp.sum(qx.astype(jnp.float32), axis=0)

    fn = jax.jit(_shard_map(leak, mesh, (P(dist.DATA_AXIS),),
                            P(dist.DATA_AXIS)))
    text, _, _ = hlo.lower_and_compile(
        fn, (jnp.zeros((8, 64), jnp.float32),))
    findings, _ = hlo.analyze_module(
        text, label="leak", where=("x.py", 1), world=8,
        update_sharding="sharded",
    )
    assert any("leaked" in f.message and f.rule == "DP301"
               for f in findings), findings

    # Same module declared int8 passes the leak rule...
    ok, rec = hlo.analyze_module(
        text, label="ok", where=("x.py", 1), world=8,
        update_sharding="sharded", wire="int8",
    )
    assert not any("leaked" in f.message for f in ok)
    assert rec["wire"] == "int8"

    # ...and an int8-declared program with NO s8 exchange fires.
    def plain(g):
        flat = jnp.pad(g.reshape(-1), (0, (-g.size) % 8))
        shard = jax.lax.psum_scatter(flat, dist.DATA_AXIS,
                                     scatter_dimension=0, tiled=True)
        return all_gather_invariant(shard, dist.DATA_AXIS, axis=0,
                                    tiled=True)[: g.size]

    fn2 = jax.jit(_shard_map(plain, mesh, (P(),), P()))
    text2, _, _ = hlo.lower_and_compile(fn2, (jnp.zeros((64,), jnp.float32),))
    findings2, _ = hlo.analyze_module(
        text2, label="uncompressed", where=("x.py", 1), world=8,
        update_sharding="sharded", wire="int8", expect_grad_reduce=True,
    )
    assert any("NO int8" in f.message for f in findings2), findings2


def test_sentinel_programs_in_artifact(repo_hlo):
    """The guardrail sentinel variants are fingerprinted alongside the
    plain steps (docs/RESILIENCE.md "Guardrails"): replicated/GSPMD
    sentinels add ZERO collectives (health computed from already-reduced
    gradients — same 2 metric scalars, all-reduce-only schedule), the
    sharded sentinel adds exactly ONE scalar psum (the cross-shard
    grad-norm sum), and donation survives the guarded select in every
    variant (the skip path's jnp.where must not cost double params
    memory)."""
    _, artifact = repo_hlo
    progs = artifact["programs"]
    sentinel = {k: v for k, v in progs.items() if "sentinel" in k}
    assert set(sentinel) == {
        "train_step[gspmd,sentinel]@accum1",
        "train_step[shard_map,sentinel]@accum1",
        "train_step[shard_map,sharded,sentinel]@accum1",
        "train_step[shard_map,sharded,int8,sentinel]@accum1",
        "multi_step[sentinel]@w2",
    }
    for name, rec in sentinel.items():
        assert rec["aliased_inputs"] == rec["donated_inputs"] > 0, name
        if rec.get("wire", "f32") == "int8":
            # Sharded sentinel's 3 plus the codec's overflow/clip psums.
            assert rec["metric_allreduce_ops"] == 5, name
        elif rec["update_sharding"] == "sharded":
            assert rec["metric_allreduce_ops"] == 3, name
        else:
            assert set(rec["counts"]) <= {"all-reduce"}, (name, rec["counts"])
            assert rec["metric_allreduce_ops"] == 2, name
    # The sharded sentinel's extra scalar is fingerprint-visible: a
    # guard-enabled rank cannot impersonate a guard-off one (DP304 would
    # catch the config divergence before the first deadlocked collective).
    assert (sentinel["train_step[shard_map,sharded,sentinel]@accum1"]["digest"]
            != progs["train_step[shard_map,sharded]@accum1"]["digest"])


def test_fingerprint_distinguishes_update_sharding_modes(repo_hlo):
    """The collective-schedule digest separates the two legal schedules:
    the sharded step cannot impersonate the replicated one (DP304's
    cross-rank check would catch a mode-diverged rank)."""
    _, artifact = repo_hlo
    progs = artifact["programs"]
    d_repl = progs["train_step[shard_map]@accum1"]["digest"]
    d_shard = progs["train_step[shard_map,sharded]@accum1"]["digest"]
    assert d_repl != d_shard
    assert progs["train_step[shard_map,sharded]@accum1"][
        "update_sharding"] == "sharded"


def test_dp301_fires_on_mismatched_scatter_gather_axes():
    """A sharded-update program whose reduce-scatter and all-gather run
    over different axes (the dp306 fixture's bug) — and one whose gradient
    bypassed the scatter into a plain all-reduce — both fail DP301's
    sharded-mode classification."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from tpu_dp.train.step import _shard_map

    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = Mesh(devices, ("data", "model"))

    def bad_axes(g):
        shard = jax.lax.psum_scatter(g, "model", scatter_dimension=0,
                                     tiled=True)
        return jax.lax.all_gather(shard - 0.1 * shard, "data", axis=0,
                                  tiled=True)[: g.size]

    # The result still varies over `model`, so replication checking would
    # refuse this program at trace time; it is switched off here so that the
    # compiled-artifact rule is what catches it.
    fn = jax.jit(jax.shard_map(bad_axes, mesh=mesh2d, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    text, _, _ = hlo.lower_and_compile(fn, (jnp.zeros((32,), jnp.float32),))
    findings, _ = hlo.analyze_module(
        text, label="bad", where=("x.py", 1), world=8,
        update_sharding="sharded", expect_grad_reduce=True,
    )
    assert any("do not match all-gather replica groups" in f.message
               for f in findings), findings
    assert all(f.rule == "DP301" for f in findings)

    # Gradient bypassing the scatter: a non-scalar all-reduce in sharded
    # mode is its own DP301.
    from tpu_dp.parallel import collectives as coll
    from tpu_dp.parallel import dist

    mesh1d = dist.data_mesh()

    def bypass(g):
        return coll.pmean(g, dist.DATA_AXIS)

    fn2 = jax.jit(_shard_map(bypass, mesh1d, (P(dist.DATA_AXIS),), P()))
    text2, _, _ = hlo.lower_and_compile(fn2, (jnp.zeros((16, 4),
                                                        jnp.float32),))
    findings2, _ = hlo.analyze_module(
        text2, label="bypass", where=("x.py", 1), world=8,
        update_sharding="sharded",
    )
    assert any("bypassed the reduce-scatter" in f.message
               for f in findings2), findings2


def test_dp301_accepts_legal_sharded_schedule_unit():
    """The minimal legal sharded schedule (scatter → update → gather over
    one axis) passes sharded-mode DP301 — and fails replicated-mode DP301
    (the schedule split really keys off the declared mode)."""
    from jax.sharding import PartitionSpec as P

    from tpu_dp.parallel import dist
    from tpu_dp.train.step import _shard_map

    mesh = dist.data_mesh()

    def good(g):
        flat = jnp.pad(g.reshape(-1), (0, (-g.size) % 8))
        shard = jax.lax.psum_scatter(flat, dist.DATA_AXIS,
                                     scatter_dimension=0, tiled=True) / 8.0
        new = shard - 0.1 * shard
        full = all_gather_invariant(new, dist.DATA_AXIS, axis=0, tiled=True)
        return full[: g.size].reshape(g.shape)

    fn = jax.jit(_shard_map(good, mesh, (P(),), P()))
    text, _, _ = hlo.lower_and_compile(fn, (jnp.zeros((30,), jnp.float32),))
    ok, _ = hlo.analyze_module(text, label="good", where=("x.py", 1),
                               world=8, update_sharding="sharded",
                               expect_grad_reduce=True)
    assert ok == []
    bad, _ = hlo.analyze_module(text, label="good-as-repl",
                                where=("x.py", 1), world=8,
                                update_sharding="replicated",
                                expect_grad_reduce=True)
    assert bad, "replicated-mode DP301 accepted a scatter/gather schedule"


def test_artifact_records_compile_stats(repo_hlo):
    _, artifact = repo_hlo
    for rec in artifact["programs"].values():
        assert rec["lowering_ms"] >= 0
        assert rec["compile_ms"] >= 0
    assert len(artifact["digest"]) == 64


# -- 2. fingerprints -----------------------------------------------------

def _compile_text(fn, *args):
    text, _, _ = hlo.lower_and_compile(jax.jit(fn), args)
    return text


def test_schedule_digest_is_deterministic():
    from tpu_dp.parallel import collectives, dist
    from tpu_dp.train.step import _shard_map

    mesh = dist.data_mesh()
    from jax.sharding import PartitionSpec as P

    def per_shard(x):
        return collectives.psum(x, dist.DATA_AXIS)

    def build():
        f = jax.jit(_shard_map(per_shard, mesh, (P(dist.DATA_AXIS),), P()))
        text, _, _ = hlo.lower_and_compile(
            f, (jnp.zeros((16, 4), jnp.float32),)
        )
        return hlo.schedule_digest(hlo.collect_ops(text))

    d1, d2 = build(), build()
    assert d1 == d2
    assert len(d1) == 64
    # A different program digests differently.
    d3 = hlo.schedule_digest(
        hlo.collect_ops(_compile_text(lambda x: x * 2, jnp.zeros((4,))))
    )
    assert d3 != d1


def test_collect_ops_reads_tpu_tiled_layouts():
    """Text compiled for a TPU carries tiled layouts with parentheses of
    their own; a tuple-shaped collective must still be seen, with its
    members, groups and reduction (lines from a v5e:2x2 compile)."""
    text = """
%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.1 = f32[]{:T(128)} add(%a, %b)
}
  %all-reduce.134 = (f32[64]{0:T(128)S(1)}, f32[36864]{0:T(1024)S(1)}, /*index=2*/f32[]{:T(128)}) all-reduce(%x, %y, %z), channel_id=5, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_1.2
  %psum_invariant.434 = f32[2,64]{1,0:T(2,128)S(1)} all-reduce(%f), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.2
  %all-gather.91 = f32[5120]{0:T(1024)} all-gather(%g), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
"""
    ops = hlo.collect_ops(text)
    assert [op.kind for op in ops] == ["all-reduce", "all-reduce",
                                       "all-gather"]
    combined = ops[0]
    assert combined.shape == "(f32[64],f32[36864],/*index=2*/f32[])"
    assert combined.replica_groups == "{{0,1,2,3}}"
    assert combined.reduction == "add"
    assert combined.scalar_results == 1 and not combined.is_scalar
    assert ops[1].shape == "f32[2,64]"
    assert hlo.count_collectives(text) == {"all-reduce": 2, "all-gather": 1}


def test_count_collectives_sees_the_allreduce():
    from tpu_dp.parallel import collectives, dist
    from tpu_dp.train.step import _shard_map
    from jax.sharding import PartitionSpec as P

    mesh = dist.data_mesh()
    f = jax.jit(_shard_map(
        lambda x: collectives.psum(x, dist.DATA_AXIS),
        mesh, (P(dist.DATA_AXIS),), P(),
    ))
    text, stats, _ = hlo.lower_and_compile(f, (jnp.zeros((16,), jnp.float32),))
    assert hlo.count_collectives(text).get("all-reduce", 0) >= 1
    assert stats["compile_ms"] >= 0


def test_verify_collective_fingerprint_single_process():
    from tpu_dp.parallel import dist

    digest = "ab" * 32
    assert dist.verify_collective_fingerprint(digest) == digest
    with pytest.raises(ValueError):
        dist.verify_collective_fingerprint("not-a-digest")


def test_verify_collective_fingerprint_every_rank_sees_mismatch(monkeypatch):
    """The matching rank (rank 0) must raise too — otherwise it sails past
    the check and hangs at its first collective waiting for the dead peer,
    the exact deadlock the hook exists to prevent."""
    import numpy as np
    from jax.experimental import multihost_utils

    from tpu_dp.parallel import dist

    digest = "ab" * 32
    monkeypatch.setattr(dist.jax, "process_count", lambda: 2)
    monkeypatch.setattr(dist.jax, "process_index", lambda: 0)
    gathered = np.stack([
        np.frombuffer(bytes.fromhex(digest), np.uint8),  # this rank (0)
        np.zeros(32, np.uint8),                          # divergent rank 1
    ])
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: gathered)
    with pytest.raises(RuntimeError, match="divergent ranks: \\[1\\]"):
        dist.verify_collective_fingerprint(digest)


def test_program_fingerprint_accepts_shape_structs():
    """The trainer's startup hook lowers from ShapeDtypeStructs — no real
    buffers needed to fingerprint the program about to run."""
    fp = hlo.program_fingerprint(
        jax.jit(lambda x: x + 1),
        (jax.ShapeDtypeStruct((8,), jnp.float32),),
    )
    assert len(fp) == 64


# -- 3. DP303 catches dropped donation -----------------------------------

def test_dp303_fires_on_dropped_donation():
    jitted = jax.jit(lambda x: (x.astype(jnp.bfloat16),),
                     donate_argnums=(0,))
    text, _, warns = hlo.lower_and_compile(
        jitted, (jnp.zeros((32, 32), jnp.float32),)
    )
    # JAX 0.9.0 no longer warns about the unusable donation under AOT
    # lower+compile (``warns`` is empty here), so the message a warning
    # must surface in is fed one by hand.
    warned = "Some donated buffers were not usable: f32[32,32]"
    findings, record = hlo.analyze_module(
        text, label="drop", where=("x.py", 1), world=8,
        donated_leaves=1, donation_warnings=[*warns, warned],
    )
    assert [f.rule for f in findings] == ["DP303"]
    assert record["aliased_inputs"] == 0
    # The lowering warning is surfaced in the finding, not swallowed.
    assert warned in findings[0].message


def test_dp303_clean_on_real_donation():
    jitted = jax.jit(lambda x: (x * 2,), donate_argnums=(0,))
    text, _, warns = hlo.lower_and_compile(
        jitted, (jnp.zeros((32, 32), jnp.float32),)
    )
    findings, record = hlo.analyze_module(
        text, label="ok", where=("x.py", 1), world=8,
        donated_leaves=1, donation_warnings=warns,
    )
    assert findings == []
    assert record["aliased_inputs"] == 1


# -- 4. RecompileGuard ---------------------------------------------------

def test_recompile_guard_counts_only_post_warmup_retraces():
    logged: list[str] = []
    guard = RecompileGuard(jax.jit(lambda x: x * 2), name="g",
                           warmup_calls=1, logger=logged.append)
    x4, x8 = jnp.zeros((4,)), jnp.zeros((8,))
    guard(x4)
    guard(x4)
    assert guard.retraces == 0 and logged == []
    guard(x8)  # new shape -> real retrace
    assert guard.retraces == 1
    assert len(logged) == 1 and "retrace" in logged[0]
    guard(x8)  # cached now
    assert guard.retraces == 1
    stats = guard.stats()
    assert stats["calls"] == 4 and stats["retraces"] == 1


def test_recompile_guard_raise_mode():
    guard = RecompileGuard(jax.jit(lambda x: x + 1), on_retrace="raise")
    guard(jnp.zeros((4,)))
    with pytest.raises(RecompileError):
        guard(jnp.zeros((16,)))


def test_recompile_guard_proxies_jit_introspection():
    jitted = jax.jit(lambda x: x + 1)
    guard = RecompileGuard(jitted)
    # AOT lowering still reachable through the guard (trainer fingerprint).
    assert guard.lower(jnp.zeros((4,))).compile() is not None
    with pytest.raises(ValueError):
        RecompileGuard(jitted, on_retrace="explode")


def test_trainer_wraps_train_step_in_guard(tmp_path):
    from tpu_dp.config import Config
    from tpu_dp.train.trainer import Trainer

    c = Config()
    c.data.dataset = "synthetic"
    c.data.synthetic_train_size = 64
    c.data.synthetic_test_size = 32
    c.data.batch_size = 16
    c.train.epochs = 1
    c.train.ckpt_dir = str(tmp_path / "ck")
    c.train.verify_fingerprint = True  # single-process: digest + log only
    trainer = Trainer(c)
    assert isinstance(trainer.train_step, RecompileGuard)
    assert trainer.train_step.retraces == 0

    c2 = Config()
    c2.data.dataset = "synthetic"
    c2.data.synthetic_train_size = 64
    c2.data.synthetic_test_size = 32
    c2.data.batch_size = 16
    c2.train.ckpt_dir = str(tmp_path / "ck2")
    c2.train.recompile_guard = "off"
    assert not isinstance(Trainer(c2).train_step, RecompileGuard)

    # Without drop_remainder the final partial batch (padded, weight leaf)
    # legitimately compiles a second variant every epoch: unguarded, so
    # 'raise' mode cannot kill a correct run at the end of epoch 1.
    c3 = Config()
    c3.data.dataset = "synthetic"
    c3.data.synthetic_train_size = 64
    c3.data.synthetic_test_size = 32
    c3.data.batch_size = 16
    c3.data.drop_remainder = False
    c3.train.ckpt_dir = str(tmp_path / "ck3")
    c3.train.recompile_guard = "raise"
    assert not isinstance(Trainer(c3).train_step, RecompileGuard)


# -- 5. DP305 static lint ------------------------------------------------

def test_dp305_flags_jit_in_loop_and_fresh_lambda():
    src = (
        "import jax\n"
        "def f(xs):\n"
        "    out = []\n"
        "    for x in xs:\n"
        "        out.append(jax.jit(step)(x))\n"
        "    return out\n"
        "def g(x):\n"
        "    return jax.jit(lambda v: v * v)(x)\n"
    )
    findings = recompile.lint_source("x.py", src)
    assert [(f.rule, f.line) for f in findings] == [("DP305", 5),
                                                    ("DP305", 8)]
    assert findings[0].symbol == "f" and findings[1].symbol == "g"


def test_dp305_does_not_flag_factory_idiom():
    """`make_train_step` returning jax.jit(named_fn) once is the shipped
    pattern — a named nested function jitted outside a loop is fine, and so
    is a module-scope jit(lambda) (one-time cost)."""
    src = (
        "import jax\n"
        "def make_step(model):\n"
        "    def step(state, batch):\n"
        "        return state\n"
        "    return jax.jit(step, donate_argnums=(0,))\n"
        "_barrier = jax.jit(lambda x: x.sum())\n"
    )
    assert recompile.lint_source("x.py", src) == []


def test_dp305_pragma_suppresses():
    src = (
        "import jax\n"
        "def f(xs):\n"
        "    for x in xs:\n"
        "        jax.jit(g)(x)  # dplint: allow(DP305)\n"
    )
    assert recompile.lint_source("x.py", src) == []


# -- 6. bench compile stats ----------------------------------------------

def test_bench_compile_with_flops_reports_stats():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    exe, _, stats = bench.compile_with_flops(
        jax.jit(lambda x: x @ x), jnp.zeros((16, 16), jnp.float32)
    )
    assert exe is not None
    assert stats["lowering_ms"] >= 0 and stats["compile_ms"] >= 0
    assert isinstance(stats["hlo_collectives"], dict)


# -- 7. the CI lane's artifact emission ----------------------------------

@pytest.mark.slow
def test_cli_writes_fingerprint_artifact(tmp_path):
    out = tmp_path / "fp.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_dp.analysis",
         os.path.join(REPO, "tpu_dp"), "--json", "--accum-steps", "1",
         "--fingerprint-out", str(out)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    artifact = json.loads(out.read_text())
    assert set(artifact["programs"]) >= {"train_step[gspmd]@accum1",
                                         "eval_step"}
