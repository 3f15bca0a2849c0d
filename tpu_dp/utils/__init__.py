"""Utilities: rank-0 logging, throughput metering, profiling, determinism."""

from tpu_dp.utils.compile_cache import place_compile_cache
from tpu_dp.utils.determinism import (
    check_cross_process_consistency,
    check_replica_consistency,
    local_digest,
)
from tpu_dp.utils.logging import get_logger, log0, print0
from tpu_dp.utils.meter import ThroughputMeter
from tpu_dp.utils.profiling import (
    StepProfiler,
    parse_profile_steps,
    profile_trace,
)

__all__ = [
    "StepProfiler",
    "ThroughputMeter",
    "check_cross_process_consistency",
    "check_replica_consistency",
    "get_logger",
    "local_digest",
    "log0",
    "parse_profile_steps",
    "place_compile_cache",
    "print0",
    "profile_trace",
]
