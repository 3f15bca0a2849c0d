"""One chip-spec registry: peak FLOP/s, HBM and ICI bandwidth per kind.

`tpu_dp.obs.costs.PEAK_FLOPS_BY_KIND` (the MFU denominator) is derived
from this table, and the per-collective wire-bandwidth health metric of
`tpu_dp.obs.commprof` (arXiv:2204.06514 treats it as first-class) reads
the chip's ICI bandwidth from it: one copy of each peak, pinned by a
cross-import test.

Values are public spec-sheet numbers (Cloud TPU system-architecture
docs): ``peak_flops`` is the bf16 matmul peak per chip, ``hbm_gbs`` the
HBM bandwidth per chip, ``ici_gbs`` the aggregate inter-chip-interconnect
bandwidth per chip (links summed, one direction). A kind we cannot match
returns None, and a field we do not confidently know is None — every
consumer publishes *absence* rather than a wrong utilization
(the `costs.peak_flops` discipline, extended to bandwidth).

Import-light on purpose (no jax): consulted by post-hoc tooling in
processes with no accelerator attached.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One chip generation's public peaks (None = unknown, never 0)."""

    name: str                 # canonical short name, e.g. "v5e"
    peak_flops: float         # bf16 matmul FLOP/s per chip
    hbm_gbs: float | None     # HBM bandwidth, GB/s per chip
    ici_gbs: float | None     # aggregate ICI bandwidth, GB/s per chip


#: (device_kind substring, spec) — first match wins, ordered so
#: "v5 lite" is tested before "v5" (the same matching discipline the old
#: costs table used; `tests/test_commprof.py` pins the derived
#: PEAK_FLOPS_BY_KIND tuple against this registry).
_V5E = ChipSpec("v5e", 197e12, 819.0, 200.0)
_V6E = ChipSpec("v6e", 918e12, 1640.0, 448.0)
_V5P = ChipSpec("v5p", 459e12, 2765.0, 600.0)
_V4 = ChipSpec("v4", 275e12, 1228.0, 300.0)
_V3 = ChipSpec("v3", 123e12, 900.0, None)
_V2 = ChipSpec("v2", 45e12, 700.0, None)

CHIP_SPECS: tuple[tuple[str, ChipSpec], ...] = (
    ("v5 lite", _V5E),
    ("v5litepod", _V5E),
    ("v5e", _V5E),
    ("v6 lite", _V6E),
    ("v6e", _V6E),
    ("v5p", _V5P),
    ("v5", _V5P),
    ("v4", _V4),
    ("v3", _V3),
    ("v2", _V2),
)


def chip_spec(device_kind: str) -> ChipSpec | None:
    """The spec for a ``device_kind`` string, or None when unknown."""
    kind = str(device_kind).lower()
    for sub, spec in CHIP_SPECS:
        if sub in kind:
            return spec
    return None


def peak_flops(device_kind: str) -> float | None:
    """Peak bf16 FLOP/s per chip (the MFU denominator), or None."""
    spec = chip_spec(device_kind)
    return None if spec is None else spec.peak_flops


def hbm_gbs(device_kind: str) -> float | None:
    """HBM bandwidth GB/s per chip, or None when unknown."""
    spec = chip_spec(device_kind)
    return None if spec is None else spec.hbm_gbs


def ici_gbs(device_kind: str) -> float | None:
    """Aggregate ICI bandwidth GB/s per chip, or None when unknown."""
    spec = chip_spec(device_kind)
    return None if spec is None else spec.ici_gbs
