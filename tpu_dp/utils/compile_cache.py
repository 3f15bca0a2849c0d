"""Where the persistent XLA compile cache lives.

One rule for every entry point that compiles (`train.py`,
`python -m tpu_dp.serve`, `bench.py`'s measuring child, `chip_smoke.py`):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here touches the config; where it is not, the cache goes to
``<checkout>/.jax_cache``. The path is part of a cache entry's key, so it is
computed from this file's own location and is the same on every run.

Called by the entry point before its first compile — never at import, and
never by the tests (a compile for a described TPU is written to the cache
but cannot be read back without the chip).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX at the compile cache; returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
