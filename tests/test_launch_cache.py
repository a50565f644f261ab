"""The entry points' compile-cache rule (``repro.launch.cache``):
``JAX_COMPILATION_CACHE_DIR`` wins where it is set; otherwise the cache
sits at the fixed ``<repo>/.jax_cache``. The config update is captured,
not applied — tests never turn the cache on."""
import os

import jax

from repro.launch import cache


def _captured(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch):
    calls = _captured(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert cache.enable_compile_cache() == "/somewhere/cache"
    assert calls == []


def test_default_dir_is_fixed_in_the_repo(monkeypatch):
    calls = _captured(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    # same path on every call: the directory is part of the cache key
    assert cache.enable_compile_cache() == path
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
