"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` before their first compile;
importing this module changes nothing. ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and no directory is set here. Otherwise the
cache lives at ``<repo root>/.jax_cache``, a fixed path, since the path is
part of what a later run must match to find its entries.

The cache key includes the program's op metadata (named scopes, source
locations). JAX leaves it out by default, and then an executable compiled
from an earlier version of the code is loaded wherever only metadata
changed: its ops keep that version's op_names, and a device trace charges
them to the wrong ``jax.named_scope`` (core/splitfed.py).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, key it
    on op metadata too, and return that directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
