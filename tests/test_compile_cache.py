"""Placement of the persistent compile cache (repro.launch.compile_cache).

Each test restores JAX's cache setting, so the rest of the suite runs with
the cache off; nothing is compiled while it is on.
"""
import os

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_setting():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_is_fixed_path_in_checkout(monkeypatch, cache_setting):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_environment_variable_wins(monkeypatch, cache_setting, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_outside_a_checkout_raises(monkeypatch, cache_setting, tmp_path):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    with pytest.raises(RuntimeError, match=compile_cache.ENV):
        compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == before
