"""Persistent XLA compilation cache for the repo's entry points.

`enable_compile_cache()` is called by `chip_smoke.py` and
`benchmarks/run.py` before their first compile (importing this module
configures nothing).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and no other directory is set in code; otherwise the
cache lives at the fixed path ``<repo>/.jax_cache`` (git-ignored): the
path is part of what makes a later run find the entries again.  Most
programs here compile in well under a second and are small, so the
minimum compile time and entry size are lowered to keep every one.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
