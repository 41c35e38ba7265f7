"""Where the persistent compilation cache lives."""
import os

import pytest

import jax

from repro.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

_OPTIONS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_cache_config():
    saved = {k: getattr(jax.config, k) for k in _OPTIONS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_from_environment(monkeypatch, tmp_path,
                                    restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads it


def test_cache_dir_defaults_to_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == CHECKOUT_CACHE_DIR == jax.config.jax_compilation_cache_dir
    root = os.path.dirname(path)
    assert os.path.basename(path) == ".jax_cache"
    assert os.path.exists(os.path.join(root, "chip_smoke.py"))
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
