"""The compile-cache helper: placed from outside, never by the tests."""
from pathlib import Path

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_wins_and_config_is_untouched(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_repo_cache_dir_is_fixed_and_gitignored():
    assert REPO_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
