"""Level-3 dplint: verify the compiled XLA artifact (DP301–DP304).

Levels 1–2 prove the *source* and the *trace*; the properties the DDP-parity
claim actually rests on are decided later, by the GSPMD partitioner and the
XLA compiler: whether the gradient all-reduce is one combinable group or a
mess of reshards, whether ``donate_argnums`` survived as a real
``input_output_alias`` (XLA drops aliasing with only a warning, silently
doubling parameter memory), whether a host callback snuck into the hot loop.
This pass lowers the *real shipped step programs* (`tpu_dp.train.step`) on an
abstract data mesh, compiles them, and verifies the optimized HLO text:

- **DP301** — every collective in the module is classified against the
  step's declared update-sharding mode. *Replicated* (default): exactly one
  *combinable* gradient all-reduce group (non-scalar operands, identical
  full-mesh replica groups, add reduction — XLA's combiner pass fuses such
  a group into the single fused all-reduce on TPU; the CPU backend leaves
  the ops separate, so the check is on combinability, not op count) plus
  the declared scalar metric reductions; any all-gather / reduce-scatter /
  collective-permute / all-to-all, any second replica grouping, and any
  extra scalar reduction betrays a bad `PartitionSpec` in
  `parallel/sharding.py`. *Sharded* (`train.update_sharding=sharded`, the
  cross-replica sharded weight update): exactly one combinable gradient
  *reduce-scatter* group plus one params *all-gather* group over identical
  full-mesh replica groups, plus the metric scalars — a non-scalar
  all-reduce, a scatter/gather replica-group mismatch (wrong axis), or a
  scatter with no gather all fire.
- **DP302** — host transfers in the hot loop: infeed/outfeed/send/recv ops
  or host-callback custom-calls inside the step module.
- **DP303** — donation silently dropped: every donated buffer must appear
  in the compiled module's ``input_output_alias`` map.
- **DP304** — collective-schedule fingerprint: a deterministic digest of the
  ordered collective sequence + replica groups, emitted to
  ``artifacts/collective_fingerprint.json``; `tpu_dp.parallel.dist`
  cross-compares digests across ranks at startup so desynced binaries fail
  fast instead of deadlocking mid-step.

A standalone .py file can opt in by defining ``DPLINT_HLO_PROGRAM`` — a
zero-arg factory returning a dict with keys ``fn`` (callable to jit),
``args`` (example arguments), and optionally ``jit_kwargs``,
``metric_reductions``, ``expect_grad_reduce``, ``expect_fingerprint``,
``update_sharding`` ("replicated"/"sharded" — which DP301 schedule to hold
the module to) — which is how the adversarial fixtures drive the exact
pipeline the shipped steps go through.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import time
import warnings
from typing import Any, Callable, Sequence

from tpu_dp.analysis.report import Finding

# Collective/host ops as they appear in optimized HLO text. "-start" forms
# (async collectives on TPU) count as the op; "-done" halves are skipped so
# an async pair is one collective, not two.
_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
_HOST_KINDS = ("infeed", "outfeed", "send", "recv")

# A result shape is one array or a tuple of them; on the TPU each carries a
# tiled layout with parentheses of its own (``f32[64]{0:T(128)S(1)}``).
_SHAPE = r"(\((?:[^(){}]|\{[^{}]*\})*\)|\S+)"
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.~-]+\s*=\s*" + _SHAPE + r"\s+([a-z-]+)\("
)
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\[[^\]]*\]<=\[[^\]]*\](?:T\([\d,]+\))?"
    r"|\{\{[\d,]*\}(?:,\{[\d,]*\})*\})"
)
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.~-]+)")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_ALIAS_RE = re.compile(r"input_output_alias=\{(.*?)\}(?:,\s*[a-z_]+=|\s*$)")
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")

# custom_call_target substrings that mean "the compiled program calls back
# into the host" (CPU/TPU python callbacks, explicit host transfers).
_HOST_TARGET_MARKERS = ("callback", "host", "infeed", "outfeed")


@dataclasses.dataclass(frozen=True)
class HloOp:
    """One collective or host-transfer op in a compiled module."""

    kind: str            # "all-reduce", "all-gather", ..., "custom-call"
    shape: str           # layout-stripped result shape, e.g. "f32[120,400]"
    replica_groups: str  # raw replica_groups text ("" when absent)
    reduction: str       # root op of to_apply ("add", "maximum", ...; "")
    target: str          # custom_call_target ("" for non-custom-calls)

    @property
    def is_scalar(self) -> bool:
        # A rank-0 result (or tuple of rank-0s): "f32[]", "(f32[], s32[])".
        return "[" in self.shape and "[]" in self.shape and not re.search(
            r"\[\d", self.shape
        )

    @property
    def scalar_results(self) -> int:
        """Rank-0 members of the result. XLA's all-reduce combiner merges
        same-dtype reductions into one tuple-shaped op — the metric scalars
        with each other, the f32 loss with the gradient group — so the
        declared metric reductions are counted by member, not by op."""
        return len(re.findall(r"[a-z]+\d+\[\]", self.shape))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _computation_reductions(text: str) -> dict[str, str]:
    """Map computation name -> its ROOT op (the reduction kind)."""
    out: dict[str, str] = {}
    name = None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.~-]+)\s*\(", line)
        if m:
            name = m.group(1)
            continue
        if name and "ROOT" in line:
            r = re.search(r"ROOT\s+%[\w.~-]+\s*=\s*(?:\([^)]*\)|\S+)\s+"
                          r"([a-z-]+)\(", line)
            if r:
                out[name] = r.group(1)
    return out


def collect_ops(text: str) -> list[HloOp]:
    """Every collective/host op in a compiled module, in schedule order.

    Compiled HLO is scheduled (`is_scheduled=true`), so the textual order of
    the entry computation *is* the execution order — the property the DP304
    fingerprint digests. Ops inside nested computations (loop bodies) appear
    once, i.e. the fingerprint is the static schedule.
    """
    reductions = _computation_reductions(text)
    ops: list[HloOp] = []
    for line in text.splitlines():
        m = _OP_RE.match(line)
        if m is None:
            continue
        shape, kind = m.groups()
        if kind.endswith("-done"):
            continue  # the async pair's completion; counted at -start
        base = kind[:-6] if kind.endswith("-start") else kind
        if base not in _COLLECTIVE_KINDS and base not in _HOST_KINDS \
                and base != "custom-call":
            continue
        rg = _REPLICA_GROUPS_RE.search(line)
        ta = _TO_APPLY_RE.search(line)
        tgt = _TARGET_RE.search(line)
        ops.append(HloOp(
            kind=base,
            shape=_LAYOUT_RE.sub("", shape).replace(" ", ""),
            replica_groups=rg.group(1) if rg else "",
            reduction=reductions.get(ta.group(1), "") if ta else "",
            target=tgt.group(1) if tgt else "",
        ))
    return ops


def count_collectives(text: str) -> dict[str, int]:
    """Collective-op histogram of a compiled module (bench/report stat)."""
    counts: dict[str, int] = {}
    for op in collect_ops(text):
        if op.kind in _COLLECTIVE_KINDS:
            counts[op.kind] = counts.get(op.kind, 0) + 1
    return counts


def alias_param_indices(text: str) -> set[int]:
    """Parameter indices the compiled module aliases to outputs."""
    m = _ALIAS_RE.search(text.splitlines()[0] if text else "")
    if m is None:
        m = _ALIAS_RE.search(text)
    if m is None:
        return set()
    return {int(i) for i in _ALIAS_ENTRY_RE.findall(m.group(1))}


def schedule_digest(ops: Sequence[HloOp]) -> str:
    """Deterministic sha256 over the ordered collective schedule."""
    canon = [
        {"kind": op.kind, "shape": op.shape,
         "replica_groups": op.replica_groups, "reduction": op.reduction}
        for op in ops if op.kind in _COLLECTIVE_KINDS
    ]
    return hashlib.sha256(
        json.dumps(canon, sort_keys=True).encode()
    ).hexdigest()


def lower_and_compile(jitted: Callable, args: Sequence[Any]):
    """AOT lower+compile; returns (hlo_text, stats, lowering_warnings).

    ``stats``: lowering/compile wall times in ms (what `bench.py` reports as
    compile stats). Warnings matching XLA's dropped-donation message are
    captured for DP303's diagnostics instead of leaking to the console.
    """
    caught: list[str] = []
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
    for item in w:
        msg = str(item.message)
        if "donated" in msg.lower():
            caught.append(msg.splitlines()[0])
        else:
            warnings.warn_explicit(item.message, item.category,
                                   item.filename, item.lineno)
    stats = {
        "lowering_ms": round((t1 - t0) * 1e3, 2),
        "compile_ms": round((t2 - t1) * 1e3, 2),
    }
    return compiled.as_text(), stats, caught


def _shape_elements(shape: str) -> int:
    """Element count of an HLO result shape string ('f32[8,16]' -> 128).

    Tuple shapes sum their parts — the CPU backend's all-to-all returns a
    tuple of per-replica slices ('(s8[1,64],s8[1,64],...)'), whose total
    IS the exchanged payload."""
    total = 0
    for _, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]", shape):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def bucket_expectations(plan, world: int, block_size: int) -> list[dict]:
    """The grad-exchange ops a bucketed program must compile, per bucket.

    Derived from the SAME `bucketing.plan_buckets` plan the step factory,
    the residual init, and the wire report use — the single source of
    truth that makes DP301's exactly-once check meaningful. Per bucket:

    - plain (f32/bf16) bucket → one ``reduce-scatter`` whose result holds
      the bucket's concatenated shard (Σ per-leaf shard elements);
    - quantizing bucket → one int8-payload ``all-to-all`` of
      ``world * cpad`` elements plus one f32-scales ``all-to-all`` of
      ``world * cpad / block`` elements, ``cpad`` the block-padded chunk.
    """
    out = []
    for b in plan:
        if b.quantizes:
            qpad = b.quant_padded(world, block_size)
            out.append({
                "index": b.index, "wire": "int8",
                "payload_elements": qpad,
                "scale_elements": qpad // block_size,
            })
        else:
            out.append({
                "index": b.index, "wire": "f32",
                "shard_elements": b.shard_elements(world),
            })
    return out


def _check_bucket_schedule(collectives: list[HloOp],
                           bucket_layout: Sequence[dict],
                           emit) -> None:
    """DP301, bucketed mode: K bucketed reductions, exactly-once over the
    union of gradient leaves.

    Matches the compiled module's gradient-exchange ops against the
    declared per-bucket expectations as multisets of element counts: a
    missing entry is a DROPPED bucket (those leaves' gradients never
    reduce — silent replica divergence), an extra one a DUPLICATED /
    stray exchange (double-averaged gradients or a leaf reduced in two
    buckets). The params all-gather and the metric scalars are not part
    of the exchange and are classified by the surrounding sharded-mode
    checks as before.
    """
    from collections import Counter

    observed = Counter()
    for op in collectives:
        if op.kind == "reduce-scatter":
            observed[("reduce-scatter", _shape_elements(op.shape))] += 1
        elif op.kind == "all-to-all":
            k = "all-to-all[s8]" if "s8[" in op.shape else "all-to-all[f32]"
            observed[(k, _shape_elements(op.shape))] += 1
    expected = Counter()
    for b in bucket_layout:
        if b.get("wire") == "int8":
            expected[("all-to-all[s8]", int(b["payload_elements"]))] += 1
            expected[("all-to-all[f32]", int(b["scale_elements"]))] += 1
        else:
            expected[("reduce-scatter", int(b["shard_elements"]))] += 1
    missing = expected - observed
    extra = observed - expected
    for (kind, elems), n in sorted(missing.items()):
        emit("DP301",
             f"bucketed schedule is MISSING {n}x `{kind}` of {elems} "
             f"elements — a declared gradient bucket was dropped from the "
             f"compiled exchange, so its leaves' gradients never reduce "
             f"over the data axis (silent replica divergence); "
             f"expected {len(bucket_layout)} bucketed reductions covering "
             f"the union of gradient leaves exactly once")
    for (kind, elems), n in sorted(extra.items()):
        emit("DP301",
             f"bucketed schedule has {n} EXTRA `{kind}` of {elems} "
             f"elements beyond the declared bucket plan — a duplicated "
             f"bucket or a leaf exchanged twice double-averages those "
             f"gradients (the same DP202 rescaling bug at the compiled "
             f"level), or the compiler re-combined buckets against the "
             f"issue-order hints")


def analyze_module(
    text: str,
    *,
    label: str,
    where: tuple[str, int],
    world: int,
    donated_leaves: int = 0,
    metric_reductions: int = 0,
    expect_grad_reduce: bool = False,
    expect_fingerprint: str | None = None,
    donation_warnings: Sequence[str] = (),
    update_sharding: str = "replicated",
    wire: str = "f32",
    bucket_layout: Sequence[dict] | None = None,
) -> tuple[list[Finding], dict]:
    """Run DP301–DP304 over one compiled module's text.

    ``update_sharding`` selects which collective schedule DP301 accepts as
    legal. ``"replicated"`` (default): one combinable gradient all-reduce
    group plus the declared scalar metric reductions, nothing else.
    ``"sharded"`` (`train.update_sharding=sharded`): one combinable
    gradient *reduce-scatter* group plus one params *all-gather* group over
    the identical full-mesh replica groups, plus the metric scalars — and
    no non-scalar all-reduce (a gradient leaf that bypassed the scatter
    path and was all-reduced anyway defeats the sharded update).

    ``wire="int8"`` (with sharded mode — `train.collective_dtype=int8`)
    admits the THIRD legal schedule, the quantized reduce-scatter: the
    gradient exchange is `all-to-all` ops that must be **int8-typed
    payload** or **f32 scales** and nothing else, over the same full-mesh
    replica group as the params all-gather; at least one int8 exchange
    must exist (a "quantized" program with no s8 wire op silently ran
    uncompressed), small leaves may keep plain reduce-scatters, and a
    non-scalar float all-reduce still means a gradient bypassed the
    compressed path. Any all-to-all in a NON-int8 program stays illegal —
    the blanket guarantee that compression can never leak into a program
    that did not opt in.

    Returns (findings, record) where the record is the program's entry in
    the collective-fingerprint artifact.
    """
    path, line = where
    findings: list[Finding] = []
    ops = collect_ops(text)
    collectives = [op for op in ops if op.kind in _COLLECTIVE_KINDS]

    def emit(rule: str, message: str) -> None:
        findings.append(Finding(rule, path, line, f"{label}: {message}",
                                symbol=label))

    # -- DP301: classify every collective --------------------------------
    sharded = update_sharding == "sharded"
    int8_wire = wire == "int8"
    if int8_wire and not sharded:
        raise ValueError("wire='int8' applies to sharded-mode programs")
    if int8_wire:
        legal_kinds = ("all-reduce", "reduce-scatter", "all-gather",
                       "all-to-all")
    elif sharded:
        legal_kinds = ("all-reduce", "reduce-scatter", "all-gather")
    else:
        legal_kinds = ("all-reduce",)
    bad_kinds = [op for op in collectives if op.kind not in legal_kinds]
    for op in bad_kinds:
        if op.kind == "all-to-all":
            emit("DP301",
                 f"compiled program contains `all-to-all` {op.shape} "
                 f"(replica_groups={op.replica_groups or '?'}) — the "
                 f"quantized-wire exchange is legal ONLY in programs "
                 f"compiled with collective_dtype=int8; in this program "
                 f"it means wire compression leaked into a path that "
                 f"never opted in")
            continue
        emit("DP301",
             f"compiled program contains `{op.kind}` {op.shape} "
             f"(replica_groups={op.replica_groups or '?'}) — a "
             f"{'sharded-update' if sharded else 'pure-DP'} step "
             f"needs no {op.kind}; an extra collective here means a batch "
             f"or parameter dimension is sharded/replicated against the "
             f"declared PartitionSpec (parallel/sharding.py)")
    allreduces = [op for op in collectives if op.kind == "all-reduce"]
    scatters = [op for op in collectives if op.kind == "reduce-scatter"]
    gathers = [op for op in collectives if op.kind == "all-gather"]
    a2as = [op for op in collectives if op.kind == "all-to-all"]
    metric_scalars = sum(op.scalar_results for op in allreduces)
    if int8_wire:
        payload_a2as = [op for op in a2as if "s8[" in op.shape]
        scale_a2as = [op for op in a2as if "f32[" in op.shape]
        stray_a2as = [op for op in a2as
                      if op not in payload_a2as and op not in scale_a2as]
        for op in stray_a2as:
            emit("DP301",
                 f"`all-to-all` {op.shape} is neither the int8 payload "
                 f"nor the f32 scales — the quantized wire format is "
                 f"s8 payload + f32 scales, nothing else rides the "
                 f"gradient exchange")
        if expect_grad_reduce and world > 1 and not payload_a2as:
            emit("DP301",
                 "collective_dtype=int8 program compiles NO int8 "
                 "all-to-all — every gradient leaf silently took the "
                 "uncompressed path; the wire-compression knob did "
                 "nothing")
        a2a_groups = {op.replica_groups for op in a2as}
        if len(a2a_groups) > 1:
            emit("DP301",
                 f"quantized exchanges use {len(a2a_groups)} distinct "
                 f"replica groupings ({sorted(a2a_groups)}) — one data "
                 f"axis means one exchange group")
        gather_groups = {op.replica_groups for op in gathers}
        if a2as and gathers and a2a_groups != gather_groups:
            emit("DP301",
                 f"int8 exchange replica groups {sorted(a2a_groups)} do "
                 f"not match the params all-gather groups "
                 f"{sorted(gather_groups)} — the quantized scatter and "
                 f"the gather run over different axes")
    if sharded:
        grad_ars = scatters + ([op for op in a2as if "s8[" in op.shape]
                               if int8_wire else [])
        stray_ars = [op for op in allreduces if not op.is_scalar]
        for op in stray_ars:
            emit("DP301",
                 f"non-scalar `all-reduce` {op.shape} in a sharded-update "
                 f"step — that leaf's gradient bypassed the reduce-scatter "
                 f"path and is being fully reduced + updated on every "
                 f"replica, defeating train.update_sharding=sharded")
        scatter_groups = {op.replica_groups for op in scatters}
        gather_groups = {op.replica_groups for op in gathers}
        if len(scatter_groups) > 1:
            emit("DP301",
                 f"reduce-scatters use {len(scatter_groups)} distinct "
                 f"replica groupings ({sorted(scatter_groups)}) — one data "
                 f"axis means one combinable scatter group")
        if len(gather_groups) > 1:
            emit("DP301",
                 f"all-gathers use {len(gather_groups)} distinct replica "
                 f"groupings ({sorted(gather_groups)}) — one data axis "
                 f"means one combinable gather group")
        if scatters and gathers and scatter_groups != gather_groups:
            emit("DP301",
                 f"reduce-scatter replica groups {sorted(scatter_groups)} "
                 f"do not match all-gather replica groups "
                 f"{sorted(gather_groups)} — the update's scatter and the "
                 f"params gather run over different axes, so each replica "
                 f"updates one shard but gathers another (silently wrong "
                 f"params on every replica)")
        if scatters and not gathers and world > 1:
            emit("DP301",
                 "reduce-scatter with no matching all-gather — updated "
                 "parameter shards are never reassembled; the next step's "
                 "forward pass would run on stale full params")
        non_add = sorted({op.reduction for op in scatters
                          if op.reduction and op.reduction != "add"})
        if non_add:
            emit("DP301",
                 f"gradient reduce-scatter group mixes reduction kinds "
                 f"(add + {non_add}) — a non-add reduction on the gradient "
                 f"path cannot fuse into the single combined reduce-scatter")
        if expect_grad_reduce and world > 1 and not grad_ars:
            emit("DP301",
                 "no reduce-scatter in the compiled sharded-update train "
                 "step — the gradient reduction the DDP contract requires "
                 "was never materialized (replicas would silently diverge)")
    else:
        grad_ars = [op for op in allreduces if not op.is_scalar]
        groups = {op.replica_groups for op in allreduces}
        if len(groups) > 1:
            emit("DP301",
                 f"all-reduces use {len(groups)} distinct replica groupings "
                 f"({sorted(groups)}) — the data-parallel step has one axis, "
                 f"so every reduction must span the same full-mesh group")
        non_add = sorted({op.reduction for op in grad_ars
                          if op.reduction and op.reduction != "add"})
        if non_add:
            emit("DP301",
                 f"gradient all-reduce group mixes reduction kinds "
                 f"(add + {non_add}) — a non-add reduction on the gradient "
                 f"path cannot fuse into the single combined all-reduce")
        if expect_grad_reduce and world > 1 and not grad_ars:
            emit("DP301",
                 "no non-scalar all-reduce in the compiled train step — the "
                 "gradient all-reduce the DDP contract requires was never "
                 "materialized by the partitioner (replicas would silently "
                 "diverge)")
    if metric_scalars > metric_reductions:
        emit("DP301",
             f"{metric_scalars} scalar all-reduce(s) compiled, "
             f"{metric_reductions} metric reduction(s) declared — an "
             f"undeclared scalar sync per step serializes the schedule")

    # -- DP301, bucketed overlap schedule (train.bucket_mb) --------------
    if bucket_layout is not None:
        if not sharded:
            raise ValueError("bucket_layout applies to sharded-mode programs")
        _check_bucket_schedule(collectives, bucket_layout, emit)

    # -- DP302: host transfers in the hot loop ---------------------------
    for op in ops:
        if op.kind in _HOST_KINDS:
            emit("DP302",
                 f"`{op.kind}` op inside the compiled step — a host "
                 f"transfer in the hot loop stalls every step on the host "
                 f"round-trip")
        elif op.kind == "custom-call" and any(
            marker in op.target.lower() for marker in _HOST_TARGET_MARKERS
        ):
            emit("DP302",
                 f"host-callback custom-call `{op.target}` inside the "
                 f"compiled step — debug prints / pure_callbacks compile "
                 f"into a per-step host round-trip; hoist them out of the "
                 f"jitted body")

    # -- DP303: donation survived as input_output_alias ------------------
    aliased = alias_param_indices(text)
    if donated_leaves:
        missing = [i for i in range(donated_leaves) if i not in aliased]
        if missing:
            why = f" (XLA: {donation_warnings[0]})" if donation_warnings \
                else ""
            emit("DP303",
                 f"{len(missing)} of {donated_leaves} donated buffer(s) "
                 f"missing from input_output_alias (params "
                 f"{missing[:8]}{'...' if len(missing) > 8 else ''}) — XLA "
                 f"dropped the aliasing without error, so those buffers "
                 f"are double-allocated every step{why}")

    # -- DP304: pinned-fingerprint comparison ----------------------------
    digest = schedule_digest(ops)
    if expect_fingerprint is not None and digest != expect_fingerprint:
        emit("DP304",
             f"collective-schedule fingerprint {digest[:12]}… does not "
             f"match the pinned {expect_fingerprint[:12]}… — this binary "
             f"compiles a different collective sequence than the one "
             f"recorded; desynced ranks would deadlock mid-step")

    record = {
        "digest": digest,
        # The fingerprint artifact names the schedule mode explicitly: the
        # digest already separates the two (different op kinds digest
        # differently), but a reviewer diffing the artifact should not have
        # to infer the mode from the op list.
        "update_sharding": update_sharding,
        # Which wire format the program was compiled for ("f32" covers the
        # bf16 cast too — the cast is payload dtype, not schedule shape;
        # "int8" marks the quantized all-to-all schedule, and the blanket
        # no-leak test keys off this field).
        "wire": wire,
        "collectives": [op.to_dict() for op in collectives],
        "counts": count_collectives(text),
        # The bucketed overlap schedule's layout (None for monolithic
        # programs): the per-bucket exchange expectations DP301 verified,
        # so the fingerprint artifact round-trips the bucket plan and a
        # reviewer diffing it sees K and the per-bucket element counts,
        # not just a changed digest.
        "buckets": (list(bucket_layout) if bucket_layout is not None
                    else None),
        # Mode-neutral name: in sharded mode the gradient-reduction ops are
        # the reduce-scatter group, not non-scalar all-reduces.
        "grad_reduce_ops": len(grad_ars),
        "metric_allreduce_ops": metric_scalars,
        "donated_inputs": donated_leaves,
        "aliased_inputs": len(aliased),
    }
    return findings, record


# --------------------------------------------------------------------------
# The shipped step programs, lowered on an abstract data mesh.
# --------------------------------------------------------------------------

def _usable_world(world: int) -> int:
    import jax

    return min(world, len(jax.devices()))


def _step_py_path() -> str:
    from tpu_dp.train import step

    return step.__file__


def _example_batch(batch_size: int, prefix: tuple[int, ...] = ()):
    import jax.numpy as jnp

    return {
        "image": jnp.zeros(prefix + (batch_size, 32, 32, 3), jnp.float32),
        "label": jnp.zeros(prefix + (batch_size,), jnp.int32),
    }


def shipped_programs(
    accum_steps: Sequence[int] = (1, 2),
    world: int = 8,
    model_name: str = "net",
):
    """Yield (name, jitted, args, spec) for every shipped step factory.

    ``spec`` carries donated_leaves / metric_reductions /
    expect_grad_reduce / where for `analyze_module`. Metric reductions per
    update are the two replicated scalars the step returns: mean loss
    (f32[]) and the correct-prediction count (s32[]).
    """
    import jax
    import numpy as np

    from tpu_dp.models import build_model
    from tpu_dp.parallel import dist
    from tpu_dp.train import step as step_mod
    from tpu_dp.train.optim import SGD, shard_optimizer
    from tpu_dp.train.schedule import constant_lr
    from tpu_dp.train.state import create_train_state

    world = _usable_world(world)
    mesh = dist.data_mesh(num_devices=world)
    model = build_model(model_name)
    opt = SGD(momentum=0.9)
    sched = constant_lr(0.1)
    state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32),
        opt,
    )
    sharded_opt = shard_optimizer(SGD(momentum=0.9), world)
    sharded_state = create_train_state(
        model, jax.random.PRNGKey(0), np.zeros((1, 32, 32, 3), np.float32),
        sharded_opt,
    )
    # The quantized-wire state: error-feedback residuals ride along,
    # flat-sharded like the opt state (tpu_dp/parallel/quant.py).
    from tpu_dp.parallel import quant as quant_mod

    int8_state = sharded_state.replace(
        residuals=quant_mod.init_residuals(sharded_state.params, world)
    )
    n_state = len(jax.tree_util.tree_leaves(state))
    n_int8_state = len(jax.tree_util.tree_leaves(int8_state))
    batch = 2 * world
    path = _step_py_path()

    def spec(factory, donated, metrics, grad, mode="replicated",
             wire="f32", bucket_layout=None):
        return {
            "donated_leaves": donated,
            "metric_reductions": metrics,
            "expect_grad_reduce": grad,
            "where": (path, factory.__code__.co_firstlineno),
            "world": world,
            "update_sharding": mode,
            "wire": wire,
            "bucket_layout": bucket_layout,
        }

    for accum in accum_steps:
        prefix = () if accum == 1 else (accum,)
        yield (
            f"train_step[gspmd]@accum{accum}",
            step_mod.make_train_step(model, opt, mesh, sched,
                                     accum_steps=accum),
            (state, _example_batch(batch, prefix)),
            spec(step_mod.make_train_step, n_state, 2, True),
        )
    yield (
        "train_step[shard_map]@accum1",
        step_mod.make_train_step(model, opt, mesh, sched, explicit=True),
        (state, _example_batch(batch)),
        spec(step_mod.make_train_step, n_state, 2, True),
    )
    # The sharded weight update's second legal schedule: one combinable
    # reduce-scatter group + one all-gather group (DP301 sharded mode).
    for accum in accum_steps:
        prefix = () if accum == 1 else (accum,)
        yield (
            f"train_step[shard_map,sharded]@accum{accum}",
            step_mod.make_train_step(
                model, sharded_opt, mesh, sched, accum_steps=accum,
                update_sharding="sharded",
            ),
            (sharded_state, _example_batch(batch, prefix)),
            spec(step_mod.make_train_step, n_state, 2, True,
                 mode="sharded"),
        )
    # The quantized-wire variants (train.collective_dtype=int8): the THIRD
    # legal schedule — int8 payload + f32 scale all-to-alls for the
    # quantizable leaves, plain reduce-scatters for the small-leaf
    # fallback, the params all-gather, and FOUR declared metric scalars
    # (loss, correct, and the codec's overflow/clip counts).
    for accum in accum_steps:
        prefix = () if accum == 1 else (accum,)
        yield (
            f"train_step[shard_map,sharded,int8]@accum{accum}",
            step_mod.make_train_step(
                model, sharded_opt, mesh, sched, accum_steps=accum,
                update_sharding="sharded", collective_dtype="int8",
            ),
            (int8_state, _example_batch(batch, prefix)),
            spec(step_mod.make_train_step, n_int8_state, 4, True,
                 mode="sharded", wire="int8"),
        )
    # The bucketed overlap schedule (train.bucket_mb, docs/PERF.md
    # "Overlapped collectives"): the FOURTH legal world — the sharded
    # exchange issued as K size-targeted bucket reductions in reverse
    # production order. The spec carries the bucket layout (derived from
    # the SAME `bucketing.plan_buckets` plan the step factory compiles),
    # so DP301 holds the module to "K bucketed reductions, exactly-once
    # over the union of gradient leaves" per wire dtype, and the DP304
    # artifact round-trips the layout. 0.05 MB targets K=2 on Net — small
    # enough that a dropped/duplicated bucket is a real two-sided check.
    from tpu_dp.parallel import bucketing

    bucket_mb = 0.05
    bucket_bytes = bucketing.parse_bucket_mb(bucket_mb)
    block = quant_mod.DEFAULT_BLOCK_SIZE
    plan_f32 = bucketing.plan_for_tree(state.params, world, bucket_bytes)
    plan_int8 = bucketing.plan_for_tree(state.params, world, bucket_bytes,
                                        block_size=block, int8=True)
    bucket_int8_state = sharded_state.replace(
        residuals=quant_mod.init_residuals(
            sharded_state.params, world, block, bucket_bytes=bucket_bytes)
    )
    n_bucket_state = len(jax.tree_util.tree_leaves(bucket_int8_state))
    yield (
        "train_step[shard_map,sharded,bucketed]@accum1",
        step_mod.make_train_step(
            model, sharded_opt, mesh, sched, update_sharding="sharded",
            bucket_mb=bucket_mb,
        ),
        (sharded_state, _example_batch(batch)),
        spec(step_mod.make_train_step, n_state, 2, True,
             mode="sharded",
             bucket_layout=bucket_expectations(plan_f32, world, block)),
    )
    yield (
        "train_step[shard_map,sharded,int8,bucketed]@accum1",
        step_mod.make_train_step(
            model, sharded_opt, mesh, sched, update_sharding="sharded",
            collective_dtype="int8", bucket_mb=bucket_mb,
        ),
        (bucket_int8_state, _example_batch(batch)),
        spec(step_mod.make_train_step, n_bucket_state, 4, True,
             mode="sharded", wire="int8",
             bucket_layout=bucket_expectations(plan_int8, world, block)),
    )
    yield (
        "multi_step[sharded,bucketed]@w2",
        step_mod.make_train_step(model, sharded_opt, mesh, sched,
                                 feed="window", num_steps=2,
                                 update_sharding="sharded",
                                 bucket_mb=bucket_mb),
        (sharded_state, _example_batch(batch, (2,))),
        spec(step_mod.make_train_step, n_state, 2, True, mode="sharded",
             bucket_layout=bucket_expectations(plan_f32, world, block)),
    )
    yield (
        "multi_step@w2",
        step_mod.make_train_step(model, opt, mesh, sched, feed="window",
                                 num_steps=2),
        (state, _example_batch(batch, (2,))),
        spec(step_mod.make_train_step, n_state, 2, True),
    )
    yield (
        "multi_step[sharded]@w2",
        step_mod.make_train_step(model, sharded_opt, mesh, sched,
                                 feed="window", num_steps=2,
                                 update_sharding="sharded"),
        (sharded_state, _example_batch(batch, (2,))),
        spec(step_mod.make_train_step, n_state, 2, True, mode="sharded"),
    )
    yield (
        "multi_step[sharded,int8]@w2",
        step_mod.make_train_step(model, sharded_opt, mesh, sched,
                                 feed="window", num_steps=2,
                                 update_sharding="sharded",
                                 collective_dtype="int8"),
        (int8_state, _example_batch(batch, (2,))),
        spec(step_mod.make_train_step, n_int8_state, 4, True,
             mode="sharded", wire="int8"),
    )
    yield (
        "eval_step",
        step_mod.make_eval_step(model, mesh),
        (state, _example_batch(batch)),
        spec(step_mod.make_eval_step, 0, 2, False),
    )
    # The guardrail sentinel variants (guard.enabled, docs/RESILIENCE.md
    # "Guardrails"): the same programs with the on-device health summary +
    # guarded update compiled in and the replicated guard_in input. The
    # replicated/GSPMD schedules are unchanged (the health summary is
    # computed from already-reduced gradients — same 2 metric scalars);
    # the sharded path adds exactly ONE scalar psum (the cross-shard
    # grad-norm sum — the only collective the sentinel ever adds), hence
    # metric_reductions=3 there. Registering them keeps DP301–DP304 the
    # safety net for guard-enabled runs; with the sentinel off the
    # non-sentinel programs above must stay digest-identical across PRs.
    gi = step_mod.default_guard_in()
    yield (
        "train_step[gspmd,sentinel]@accum1",
        step_mod.make_train_step(model, opt, mesh, sched, sentinel=True),
        (state, _example_batch(batch), gi),
        spec(step_mod.make_train_step, n_state, 2, True),
    )
    yield (
        "train_step[shard_map,sentinel]@accum1",
        step_mod.make_train_step(model, opt, mesh, sched, sentinel=True,
                                 explicit=True),
        (state, _example_batch(batch), gi),
        spec(step_mod.make_train_step, n_state, 2, True),
    )
    yield (
        "train_step[shard_map,sharded,sentinel]@accum1",
        step_mod.make_train_step(
            model, sharded_opt, mesh, sched, update_sharding="sharded",
            sentinel=True,
        ),
        (sharded_state, _example_batch(batch), gi),
        spec(step_mod.make_train_step, n_state, 3, True,
             mode="sharded"),
    )
    # Guard + quantized wire together (the interaction the guard suite
    # proves: sentinel health reads the DEQUANTIZED post-reduce gradients,
    # and a skipped batch's residuals revert with the rest of the state):
    # 5 declared scalars — loss, correct, cross-shard grad-norm psum,
    # overflow, clip.
    yield (
        "train_step[shard_map,sharded,int8,sentinel]@accum1",
        step_mod.make_train_step(
            model, sharded_opt, mesh, sched, update_sharding="sharded",
            collective_dtype="int8", sentinel=True,
        ),
        (int8_state, _example_batch(batch), gi),
        spec(step_mod.make_train_step, n_int8_state, 5, True,
             mode="sharded", wire="int8"),
    )
    yield (
        "multi_step[sentinel]@w2",
        step_mod.make_train_step(model, opt, mesh, sched, sentinel=True,
                                 feed="window", num_steps=2),
        (state, _example_batch(batch, (2,)), gi),
        spec(step_mod.make_train_step, n_state, 2, True),
    )
    # The serving forwards (`tpu_dp.serve`, docs/SERVING.md): one program
    # per batch bucket, donating the ServeStats pytree (2 leaves — DP303
    # must prove the aliasing for serving too). A bucket divisible by the
    # world shards the batch over ``data`` and reduces only the two stats
    # values (one scalar, one [C] vector — the non-scalar one plays the
    # "gradient" role in DP301's replicated classification); a smaller
    # bucket runs replicated and must compile to ZERO collectives.
    import jax.numpy as jnp

    serve_state = state.replace(opt_state={})  # params-only, like serving
    serve_buckets = [(2 * world, 1, True)]   # sharded fan-out bucket
    if world > 1:
        # sub-world bucket: replicated, no comms (on a 1-device "mesh"
        # it would collide with the bucket above).
        serve_buckets.append((2, 0, False))
    for bucket, metric_count, expect_reduce in serve_buckets:
        yield (
            f"serve_step@b{bucket}",
            step_mod.make_serve_step(model, mesh, bucket),
            (
                step_mod.init_serve_stats(10),
                serve_state,
                {
                    "image": jnp.zeros((bucket, 32, 32, 3), jnp.float32),
                    "weight": jnp.ones((bucket,), jnp.float32),
                },
            ),
            spec(step_mod.make_serve_step, 2, metric_count,
                 expect_reduce and world > 1),
        )


def verify_repo_hlo(
    accum_steps: Sequence[int] = (1, 2),
    world: int = 8,
) -> tuple[list[Finding], dict]:
    """Compile every shipped step on the abstract mesh; verify DP301–DP304.

    Returns (findings, artifact) where the artifact is the
    collective-fingerprint record `write_fingerprint_artifact` persists.
    """
    import jax

    findings: list[Finding] = []
    programs: dict[str, dict] = {}
    usable = _usable_world(world)
    for name, jitted, args, spec in shipped_programs(accum_steps, world):
        text, stats, donation_warns = lower_and_compile(jitted, args)
        got, record = analyze_module(
            text, label=name, where=spec["where"], world=spec["world"],
            donated_leaves=spec["donated_leaves"],
            metric_reductions=spec["metric_reductions"],
            expect_grad_reduce=spec["expect_grad_reduce"],
            donation_warnings=donation_warns,
            update_sharding=spec.get("update_sharding", "replicated"),
            wire=spec.get("wire", "f32"),
            bucket_layout=spec.get("bucket_layout"),
        )
        findings.extend(got)
        record.update(stats)
        programs[name] = record
    overall = hashlib.sha256(json.dumps(
        {k: v["digest"] for k, v in sorted(programs.items())},
        sort_keys=True,
    ).encode()).hexdigest()
    artifact = {
        "version": 1,
        "world": usable,
        "backend": jax.default_backend(),
        "digest": overall,
        "programs": programs,
    }
    return findings, artifact


def write_fingerprint_artifact(path: str, artifact: dict) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")


def program_fingerprint(jitted: Callable, args: Sequence[Any]) -> str:
    """Collective-schedule digest of one jitted program (startup hook).

    What `Trainer` feeds `tpu_dp.parallel.dist.verify_collective_fingerprint`
    when ``train.verify_fingerprint`` is enabled: every rank digests the
    program it is about to run and rank 0's digest is the reference.
    """
    text, _, _ = lower_and_compile(jitted, args)
    return schedule_digest(collect_ops(text))


# --------------------------------------------------------------------------
# Standalone-file hook: how the adversarial fixtures ride the same pipeline.
# --------------------------------------------------------------------------

HLO_HOOK = "DPLINT_HLO_PROGRAM"


def _hook_line(fn: Any, path: str) -> int:
    """Line to attribute a hook program's findings to.

    Walks the ``__wrapped__`` chain (jit → shard_map wrapper → user fn)
    preferring the first code object defined in the hook file itself — a
    program wrapped in transformation layers must not attribute its
    findings to a line number inside jax internals.
    """
    best = None
    seen: set[int] = set()
    node = fn
    while node is not None and id(node) not in seen:
        seen.add(id(node))
        code = getattr(node, "__code__", None)
        if code is not None:
            if os.path.abspath(code.co_filename) == os.path.abspath(path):
                return code.co_firstlineno
            if best is None:
                best = code.co_firstlineno
        node = getattr(node, "__wrapped__", None)
    return best if best is not None else 1


def verify_hlo_hook(path: str, module: Any, world: int) -> list[Finding]:
    """Compile and verify a file's ``DPLINT_HLO_PROGRAM`` declaration."""
    import jax

    hook = getattr(module, HLO_HOOK)
    decl = hook() if callable(hook) else hook
    fn = decl["fn"]
    args = decl["args"]
    jit_kwargs = dict(decl.get("jit_kwargs", {}))
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn, **jit_kwargs)

    donated_leaves = 0
    donate = jit_kwargs.get("donate_argnums", ())
    if isinstance(donate, int):
        donate = (donate,)
    # jit flattens positional args in order, so donated parameter indices
    # are exactly the flattened-leaf ranges of the donated argnums — and the
    # shipped steps donate argnum 0, making the range a prefix.
    offset = 0
    donated_idx: set[int] = set()
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate:
            donated_idx.update(range(offset, offset + n))
        offset += n
    if donated_idx:
        if donated_idx != set(range(len(donated_idx))):
            raise ValueError(
                f"{HLO_HOOK} in {path}: donated argnums must form a leading "
                f"prefix of the flattened arguments (got {sorted(donated_idx)})"
            )
        donated_leaves = len(donated_idx)

    line = _hook_line(fn, path)
    text, _, donation_warns = lower_and_compile(jitted, args)
    findings, _ = analyze_module(
        text,
        label=f"{HLO_HOOK} in {os.path.basename(path)}",
        where=(path, line),
        world=_usable_world(world),
        donated_leaves=donated_leaves,
        metric_reductions=int(decl.get("metric_reductions", 0)),
        expect_grad_reduce=bool(decl.get("expect_grad_reduce", False)),
        expect_fingerprint=decl.get("expect_fingerprint"),
        donation_warnings=donation_warns,
        update_sharding=str(decl.get("update_sharding", "replicated")),
        wire=str(decl.get("wire", "f32")),
        bucket_layout=decl.get("bucket_layout"),
    )
    return findings
