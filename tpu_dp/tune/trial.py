"""The fenced trial runner: real `bench.py` measurements, one subprocess
per trial.

A tune trial IS a bench point — same subprocess isolation (a wedged
trial costs a timeout, never the search), same fenced timing, same
record schema — with comm profiling forced on so every score carries
the `exposed_comm_ms` tie-breaker. The runner maps the executable knobs
(`tpu_dp.tune.space.EXECUTABLE_KNOBS`) onto bench's measurement config;
pinned profile knobs (`serve.*`, `train.obs`, accum) do not reach the
trial — the space grammar already refuses to sweep them.

Every completed trial is archived to `benchmarks/results.jsonl` tagged
``tune_trial: true`` (and, like every archived row since this PR,
stamped with ``schema`` + ``config_hash``), so trials, BENCH emissions
and `obsctl diff` baselines join on one key. The tag tells trial rows —
deliberately tiny, short-fence measurements — from headline rows.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Any, Mapping

from tpu_dp.tune.profile import config_hash


def repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


_BENCH = None


def load_bench():
    """Import the repo-root `bench.py` as a module (cached).

    bench.py is an entry script, not a package member — the tuner loads
    it by path so `run_point`/`archive` stay the single implementation
    of subprocess measurement and archiving."""
    global _BENCH
    if _BENCH is None:
        path = repo_root() / "bench.py"
        spec = importlib.util.spec_from_file_location("_tpu_dp_bench", path)
        module = importlib.util.module_from_spec(spec)
        # Registered before exec: bench.py's measure-subprocess re-import
        # idiom is not in play here, but a partial module on a second
        # import attempt would be.
        sys.modules["_tpu_dp_bench"] = module
        spec.loader.exec_module(module)
        _BENCH = module
    return _BENCH


def trial_cfg(knobs: Mapping[str, Any], rung: Mapping[str, int], *,
              model: str, per_chip_batch: int,
              platform: str | None) -> dict:
    """One bench `--_measure` config from (grid point, rung budget)."""
    return {
        "model": model,
        "per_chip_batch": int(per_chip_batch),
        "steps_per_call": 1,
        "measure_steps": int(rung["measure_steps"]),
        "latency_steps": int(rung["latency_steps"]),
        "pallas_xent": False,
        "platform": platform,
        # The knobs under test. update_sharding defaults to sharded: the
        # tuned knobs live on the explicit-collectives path.
        "update_sharding": str(
            knobs.get("train.update_sharding", "sharded")),
        "collective_dtype": str(knobs.get("train.collective_dtype", "")),
        "quant_block_size": int(knobs.get("train.quant_block_size", 256)),
        "bucket_mb": float(knobs.get("train.bucket_mb", 0.0) or 0.0),
        # Forced on: a score without comm attribution cannot tie-break,
        # and the prior cannot size from it.
        "comm_profile": True,
    }


class TrialRunner:
    """Callable the search driver invokes for every (knobs, rung) it
    cannot serve from the ledger. Returns the BENCH record dict."""

    def __init__(self, *, model: str = "resnet18", per_chip_batch: int = 2,
                 platform: str | None = None, point_timeout_s: float = 420.0,
                 archive: bool = True):
        self.model = model
        self.per_chip_batch = per_chip_batch
        self.platform = platform
        self.point_timeout_s = point_timeout_s
        self.archive = archive

    def __call__(self, knobs: Mapping[str, Any],
                 rung: Mapping[str, int]) -> dict:
        bench = load_bench()
        cfg = trial_cfg(knobs, rung, model=self.model,
                        per_chip_batch=self.per_chip_batch,
                        platform=self.platform)
        rec = bench.run_point(cfg, self.point_timeout_s)
        rec["tune_trial"] = True
        rec["tune_knobs"] = dict(sorted(knobs.items()))
        rec["tune_config_hash"] = config_hash(knobs)
        if self.archive and rec.get("value") is not None:
            import time

            rec.setdefault(
                "ts", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
            bench.archive(rec)
        return rec
