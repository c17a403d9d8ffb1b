"""Where the entry points put JAX's persistent compilation cache."""

import jax
import pytest

from repro.launch import cache


@pytest.fixture()
def restore_config():
    was = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


def test_checkout_cache_when_unset(monkeypatch, restore_config):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.use_compile_cache()
    assert path == str(cache.CHECKOUT_CACHE)
    assert cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (cache.CHECKOUT_CACHE.parent / "chip_smoke.py").exists()  # the repo root
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert cache.use_compile_cache() == path  # fixed: the same path every call


def test_env_dir_wins_and_no_other_is_set(monkeypatch, tmp_path, restore_config):
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir == before
