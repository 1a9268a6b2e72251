"""Where the entry points keep JAX's persistent compilation cache."""

import os

import jax

from repro.launch import compile_cache


def test_env_dir_wins_and_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setattr(jax.config, "update", _refuse)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first.endswith(".jax_cache")
    root = os.path.dirname(first)
    assert os.path.isfile(os.path.join(root, "src", "repro", "__init__.py"))
    assert seen == [("jax_compilation_cache_dir", first)] * 2


def _refuse(*_):
    raise AssertionError("JAX_COMPILATION_CACHE_DIR is JAX's to read")
