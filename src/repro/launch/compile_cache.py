"""Where the persistent JAX compilation cache lives.

A cache entry is found again only under the same directory, so the path is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and nothing here overrides it), otherwise
``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns the path.

    Call once per process, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
