"""Persistent compilation cache for the entry points that run on a chip.

A sort at real sizes compiles for minutes, so every script a user runs on
the chip (``chip_smoke.py``, ``repro.launch.sort_serve``, ``benchmarks/``)
calls :func:`use_compile_cache` once at start-up.  Library imports and the
tests never do.

``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, and nothing is set
here.  Otherwise the cache lives at a fixed path, ``<checkout>/.jax_cache``
(git-ignored), so that a later run finds what an earlier one stored.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/runtime/ → root


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set.  Returns the directory in
    use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
