"""`tpu_dp.utils.place_compile_cache`: where the XLA compile cache lives."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from tpu_dp.utils import compile_cache, place_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def cache_config():
    """Whatever a test does to JAX's cache directory is undone."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_set_leaves_the_config_alone(monkeypatch, cache_config,
                                             tmp_path):
    """JAX reads the variable itself; no other directory is set in code."""
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "elsewhere"))
    assert place_compile_cache() == str(tmp_path / "elsewhere")
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_points_at_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert place_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")


def test_two_calls_give_the_same_path(monkeypatch, cache_config):
    """The path is part of a cache entry's key: no pid, time or temp name."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert place_compile_cache() == place_compile_cache()
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
