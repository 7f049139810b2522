"""The entry points' persistent compile cache: where it lives."""
from pathlib import Path

import jax

from repro.launch import compile_cache as cc


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "cache"))
    assert cc.compile_cache_dir() == str(tmp_path / "cache")


def test_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    path = Path(cc.compile_cache_dir())
    root = Path(__file__).resolve().parents[1]
    assert path == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_enable_sets_only_the_default(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        assert cc.enable_compile_cache() == str(cc.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)
        # with the variable set, JAX reads it itself: nothing is overridden
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
