"""Live efficiency accounting: per-program FLOP costs, MFU, goodput.

The pjit/TPUv4 scaling paper (arXiv:2204.06514) treats hardware
utilization — MFU, model FLOPs per second over the chip's peak — as the
first-class fleet health signal, yet until this module the repo's MFU
math lived only in `bench.py` and was computed once, offline, per bench
run. This module is the single source of truth both consumers share:

- `bench.py` imports `peak_flops` / `resolve_flops_per_step` /
  `FLOPS_CHECK_RTOL` from here (the analytic-FLOPs sanity check that
  caught the round-2 scan-cost bug lives on unchanged);
- the `Trainer` registers each compiled program's per-step cost in the
  process-wide `registry` (keyed by the same program tags the DP304
  collective fingerprint uses) and publishes rolling ``obs.mfu`` /
  ``obs.goodput`` / ``obs.step_time_ms`` gauges per dispatched window;
- `serve/engine.py` registers per-bucket forward costs and publishes
  per-bucket device utilization from the very same registry.

Definitions (docs/OBSERVABILITY.md "Efficiency accounting"):

- **MFU** = flops_per_step_per_chip x steps / wall_s / peak_flops(chip).
  Wall time is the host window boundary-to-boundary time — at
  ``train.obs=full`` the window ends on a device fence so this is
  honest device time; at ``basic`` it is a dispatch rate that tracks
  the device rate only under sustained backpressure (documented, not
  hidden).
- **goodput** = 1 − data_wait / window_wall: the fraction of wall time
  NOT spent blocked on the input pipeline. A healthy overlapped feed
  shows ~1.0; a starving feed shows the loss directly.

Import-light on purpose (no jax at module load): the registry is
consulted by post-hoc tooling (`obsctl diff`) in processes with no
accelerator attached.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque

from tpu_dp.obs import chips as _chips

#: bf16 peak matmul FLOP/s per chip, by device_kind substring (first match
#: wins; ordered so "v5 lite" is tested before "v5"). Derived from the
#: unified `tpu_dp.obs.chips` registry (which adds HBM/ICI peaks for the
#: comm-attribution layer); kept as a tuple here because bench.py
#: re-exports it. MFU is None on unknown kinds rather than wrong.
PEAK_FLOPS_BY_KIND = tuple(
    (sub, spec.peak_flops) for sub, spec in _chips.CHIP_SPECS
)

#: Analytic conv+dot FLOPs for one *trained* image, by model name (the
#: derivation lives with the numbers' first user, bench.py's module
#: docstring: per-layer MAC counts x ~3 for the backward pass, matching
#: XLA's compiled count within FLOPS_CHECK_RTOL). Models not listed have
#: no analytic yardstick — their MFU needs a measured cost
#: (`Trainer` with ``obs.measure_flops=true``, or bench's cost analysis).
#: The unit is a batch row, which for every model listed is an image; a
#: model whose row holds many items (a token model) has no entry, and its
#: cost is measured from the abstract batch its data set describes.
MODEL_TRAIN_FLOPS_PER_IMAGE = {
    "resnet18": 3.0e9,
    "resnet50": 7.0e9,
}

#: +-35%: covers bwd-pass accounting slop, not 30x (see
#: `resolve_flops_per_step` — the check that keeps a wrong MFU from ever
#: looking routine again).
FLOPS_CHECK_RTOL = 1.35


def peak_flops(device_kind: str) -> float | None:
    """Peak bf16 FLOP/s for a device kind, or None when unknown
    (delegates to the `tpu_dp.obs.chips` registry)."""
    return _chips.peak_flops(device_kind)


def train_flops_per_image(model_name: str) -> float | None:
    """Analytic trained-image FLOPs for a known model name, else None."""
    return MODEL_TRAIN_FLOPS_PER_IMAGE.get(str(model_name).lower())


def serve_flops_per_image(model_name: str) -> float | None:
    """Analytic forward-only FLOPs per image (~training/3: the backward
    pass costs ~2 forwards; serving runs only the forward)."""
    trained = train_flops_per_image(model_name)
    return None if trained is None else trained / 3.0


def resolve_flops_per_step(program_flops, step_flops, window, per_chip_batch,
                           flops_per_image):
    """Per-optimizer-step per-chip FLOPs for MFU; robust to scan cost semantics.

    All inputs and the result are PER-DEVICE: `compiled.cost_analysis()`
    reports the SPMD per-device module's FLOPs, MFU divides by one chip's
    peak, and the analytic yardstick is therefore built from the per-chip
    batch (using the global batch would mis-resolve on any multi-chip mesh).

    Round 2 published mfu=0.0165 instead of the true ~0.49 because
    `compiled.cost_analysis()["flops"]` on a `lax.scan` program reports the
    loop *body's* FLOPs once on this jaxlib/TPU, and the old code divided by
    the trip count again (the round-2 review, weak point 1). Resolution
    order:

    1. `step_flops` — cost analysis of the w1-compiled production step
       (`make_train_step`), which has no loop and therefore no ambiguity.
       The scanned w30 point reuses this number, so w1 and w30 publish the
       same flops_per_step by construction.
    2. `program_flops` — the scanned program's cost. Whether it is body-only
       or body x trip-count is version-dependent, so pick the reading
       (as-is vs /window) closest in log-space to the analytic count.
    3. The analytic count itself.

    ``flops_per_image`` may be None (a model with no analytic yardstick):
    the ambiguity-free `step_flops` reading then resolves with check
    "unchecked", the scan reading falls back to the body-only
    interpretation (also "unchecked"), and with neither there is nothing
    to return — (None, "unavailable", "unavailable").

    Returns (flops_per_step, source, check) where check is "ok" when the
    resolved value agrees with the analytic count within FLOPS_CHECK_RTOL,
    else "mismatch:analytic_ratio=R" — published in the record so a wrong
    MFU can never again look routine.
    """
    analytic = (
        None if flops_per_image is None
        else float(flops_per_image) * per_chip_batch
    )
    if step_flops:
        resolved, source = float(step_flops), "w1_step_cost_analysis"
    elif program_flops:
        body = float(program_flops)          # body-reported-once reading
        divided = float(program_flops) / max(int(window), 1)
        if analytic is None:
            # No yardstick to disambiguate the scan semantics with; the
            # body-only reading is this jaxlib's observed behavior.
            return body, "scan_cost_analysis_body", "unchecked"
        resolved = min((body, divided),
                       key=lambda f: abs(math.log(f / analytic)))
        source = ("scan_cost_analysis_body" if resolved == body
                  else "scan_cost_analysis_divided")
    elif analytic is not None:
        # Comparing the analytic estimate against itself would be vacuous:
        # mark it so consumers can't mistake an estimate for a validation.
        return analytic, "analytic", "unverified"
    else:
        return None, "unavailable", "unavailable"
    if analytic is None:
        return resolved, source, "unchecked"
    ratio = resolved / analytic
    check = ("ok" if 1 / FLOPS_CHECK_RTOL <= ratio <= FLOPS_CHECK_RTOL
             else f"mismatch:analytic_ratio={ratio:.3g}")
    return resolved, source, check


def cost_analysis_flops(compiled) -> float | None:
    """The compiled executable's per-device FLOP count, or None.

    One tolerant wrapper for the two `cost_analysis()` return shapes
    (dict vs [dict]) and for backends that report nothing — shared by
    bench's `compile_with_flops` and the trainer's ``obs.measure_flops``
    path so both read XLA's count identically.
    """
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:
        return None


def goodput(data_wait_ms: float, window_ms: float) -> float:
    """1 − data_wait/window: the non-input-starved fraction of wall time."""
    if window_ms <= 0:
        return 0.0
    return max(0.0, min(1.0, 1.0 - float(data_wait_ms) / float(window_ms)))


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """One compiled program's per-optimizer-step per-chip FLOP cost."""

    tag: str            # DP304-style program tag, e.g. "train_step"
    flops_per_step_per_chip: float
    source: str         # w1_step_cost_analysis | scan_* | analytic
    check: str          # ok | unverified | unchecked | mismatch:...

    @property
    def measured(self) -> bool:
        return self.source != "analytic"


class CostRegistry:
    """Per-compiled-program cost registry, keyed by DP304 program tags.

    Measured entries (XLA cost analysis) outrank analytic estimates: an
    analytic `register` never overwrites a measured one, so bench / the
    trainer's ``obs.measure_flops`` path can upgrade the number the live
    gauges are computed from without a config dance.
    """

    def __init__(self):
        self._by_tag: dict[str, ProgramCost] = {}

    def register(self, tag: str, flops_per_step_per_chip: float | None,
                 source: str = "analytic",
                 check: str = "unverified") -> ProgramCost | None:
        """Record a program's cost; returns the registry's current entry
        (which may be a pre-existing measured one that outranks this)."""
        if not flops_per_step_per_chip:
            return self._by_tag.get(tag)
        cost = ProgramCost(str(tag), float(flops_per_step_per_chip),
                           str(source), str(check))
        cur = self._by_tag.get(tag)
        if cur is not None and cur.measured and not cost.measured:
            return cur
        self._by_tag[tag] = cost
        return cost

    def register_analytic(self, tag: str, model_name: str,
                          per_chip_batch: float) -> ProgramCost | None:
        """Analytic per-step cost for a known model, or None (unknown)."""
        per_image = train_flops_per_image(model_name)
        if per_image is None:
            return self._by_tag.get(tag)
        return self.register(tag, per_image * float(per_chip_batch),
                             source="analytic", check="unverified")

    def alias(self, tag: str, source_tag: str) -> ProgramCost | None:
        """Register ``tag`` with ``source_tag``'s cost (one optimizer step
        costs the same whether dispatched per-step, windowed, or
        resident — only the program wrapping differs)."""
        src = self._by_tag.get(source_tag)
        if src is None:
            return None
        cost = dataclasses.replace(src, tag=str(tag))
        self._by_tag[tag] = cost
        return cost

    def get(self, tag: str) -> ProgramCost | None:
        return self._by_tag.get(tag)

    def tags(self) -> list[str]:
        return sorted(self._by_tag)

    def mfu(self, tag: str, n_steps: float, elapsed_s: float,
            peak: float | None) -> float | None:
        """Model FLOPs utilization of ``n_steps`` of ``tag`` over
        ``elapsed_s`` against ``peak``; None when anything is unknown."""
        cost = self._by_tag.get(tag)
        if cost is None or not peak or elapsed_s <= 0:
            return None
        return cost.flops_per_step_per_chip * float(n_steps) / float(
            elapsed_s
        ) / float(peak)

    # serving publishes the same ratio per batch; the alias keeps call
    # sites honest about what they measure (a bucket dispatch, not a step).
    utilization = mfu

    def reset(self) -> None:
        """Drop everything — test isolation only."""
        self._by_tag.clear()


#: The process-wide registry the trainer, serve engine and bench share.
registry = CostRegistry()


class EfficiencyMeter:
    """Rolling window-level MFU / goodput / step-time accounting.

    The trainer calls `observe` once per dispatched window with the
    window's boundary-to-boundary wall time and its measured data_wait;
    the returned dict is what lands in the ``obs.*`` gauges and the
    schema-3 per-step metrics records. `rollup` summarizes the ring for
    epoch records, `train.py`'s summary block, and `obsctl diff`.
    """

    def __init__(self, registry_: CostRegistry | None = None,
                 peak: float | None = None, capacity: int = 4096):
        self.registry = registry if registry_ is None else registry_
        self.peak = peak
        self._win: deque[dict] = deque(maxlen=max(1, int(capacity)))

    def observe(self, tag: str, n_steps: int, window_wall_ms: float,
                data_wait_ms: float) -> dict:
        """Account one dispatched window; returns the window's gauges."""
        n = max(1, int(n_steps))
        wall_ms = max(1e-6, float(window_wall_ms))
        out = {
            "step_time_ms": round(wall_ms / n, 3),
            "goodput": round(goodput(data_wait_ms, wall_ms), 4),
        }
        mfu = self.registry.mfu(tag, n, wall_ms / 1e3, self.peak)
        if mfu is not None:
            out["mfu"] = round(mfu, 4)
        cost = self.registry.get(tag)
        if cost is not None:
            out["flops_per_step_per_chip"] = cost.flops_per_step_per_chip
        self._win.append({"n": n, **out})
        return out

    def rollup(self) -> dict | None:
        """Percentile/mean summary over the ring (None before any window)."""
        from tpu_dp.obs.spans import percentile

        if not self._win:
            return None
        step_ms = sorted(w["step_time_ms"] for w in self._win)
        total_steps = sum(w["n"] for w in self._win)
        wsum = lambda k: sum(  # noqa: E731  (step-weighted means)
            w[k] * w["n"] for w in self._win if k in w
        )
        wn = lambda k: sum(w["n"] for w in self._win if k in w)  # noqa: E731
        out = {
            "windows": len(self._win),
            "steps": total_steps,
            "goodput": round(wsum("goodput") / max(1, wn("goodput")), 4),
            "step_time_ms": {
                "p50": round(percentile(step_ms, 50), 3),
                "p95": round(percentile(step_ms, 95), 3),
                "p99": round(percentile(step_ms, 99), 3),
                "mean": round(sum(step_ms) / len(step_ms), 3),
                "max": round(step_ms[-1], 3),
            },
        }
        n_mfu = wn("mfu")
        if n_mfu:
            out["mfu"] = round(wsum("mfu") / n_mfu, 4)
        costs = {w.get("flops_per_step_per_chip") for w in self._win
                 if "flops_per_step_per_chip" in w}
        if costs:
            out["flops_per_step_per_chip"] = max(costs)
        return out

    def reset(self) -> None:
        self._win.clear()
