"""JAX's persistent compilation cache, placed from outside the program.

Launchers call :func:`use_compile_cache` once, before their first
compile — never at import time, so tests and library users keep JAX's
own default (no cache). When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads the variable itself and nothing is changed here. Otherwise the
cache lives at a fixed path inside the checkout, ``<repo>/.jax_cache``
(gitignored): a directory that moved from run to run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
