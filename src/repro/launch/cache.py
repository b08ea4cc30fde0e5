"""Persistent XLA compilation cache at a path that never moves.

A cache entry is only found again from the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself, and nothing here overrides it),
otherwise ``<checkout>/.jax_cache``.

The key holds each op's metadata, its ``jax.named_scope`` path among
it.  A profile names a device op's layer by that path in the
executable's HLO (``bench/scopes.py``); without it in the key, a module
that differs only in its scopes would load an entry compiled from
another, and its ops would carry that module's names.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
