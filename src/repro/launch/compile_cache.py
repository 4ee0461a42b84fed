"""JAX's persistent compilation cache for runs on the chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is set here. Otherwise the cache goes to ``.jax_cache``
at the root of the checkout: a fixed path, so the next run finds what
this one compiled (a directory named from a tempdir, a pid or the time
would start empty every run). Entry points that drive the chip call
this; tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
