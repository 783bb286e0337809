"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache:
the directory ``JAX_COMPILATION_CACHE_DIR`` names, read by JAX itself, or
else the checkout's fixed ``.jax_cache``."""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_include_full_tracebacks_in_locations",
         "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture
def cache_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    jax.config.update("jax_enable_compilation_cache", True)
    # cache even this test's tiny executables
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _compile_something(tag: float):
    jax.jit(lambda x: x * tag + 1.0)(jnp.arange(7.0)).block_until_ready()


def test_default_dir_is_fixed_in_the_checkout():
    checkout = Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == checkout / ".jax_cache"


def test_without_env_cache_lands_in_default_dir(monkeypatch, tmp_path,
                                                cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "cache")
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
    _compile_something(3.0)
    assert any((tmp_path / "cache").iterdir())


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # what JAX reads from the variable when it starts
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    _compile_something(5.0)
    assert any(tmp_path.iterdir())
