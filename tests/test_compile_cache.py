"""Where the entry points put JAX's persistent compilation cache."""

import importlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import compile_cache


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield was
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_environment_variable_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                                      cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_dir_restored


def test_default_is_a_fixed_directory_in_the_checkout(monkeypatch,
                                                      cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()


@pytest.mark.parametrize("module", ["repro.launch.compile_cache",
                                    "repro.launch.serve"])
def test_importing_sets_no_cache_path(module, monkeypatch,
                                      cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    importlib.reload(importlib.import_module(module))
    assert jax.config.jax_compilation_cache_dir == cache_dir_restored
