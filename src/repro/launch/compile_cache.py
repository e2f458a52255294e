"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` at the start of ``main()``, never
at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here sets another.  Otherwise the cache lives at one fixed path
in the checkout, ``.jax_cache/`` at the repository root (git-ignored): a
cache directory named after a temporary name, a process id or the time
would be new, and empty, on every run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
