"""The entry points' compile-cache placement (``repro.launch.compile_cache``)."""
import os

import jax

from repro.launch import compile_cache as cc

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.compile_cache_dir() == str(tmp_path)
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV, raising=False)
    first, second = cc.compile_cache_dir(), cc.compile_cache_dir()
    assert first == second == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
