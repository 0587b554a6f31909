"""Persistent XLA compile cache for the entry points that run on a chip.

A full-width UNet program takes minutes to compile, so every entry point
that runs on the accelerator (``chip_smoke.py``, ``repro.launch.serve``)
calls :func:`enable_compile_cache` before its first compile.  Library
modules never call it: importing them leaves JAX's configuration alone.

The cache key includes the directory, so the directory must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself, and nothing here overrides it) or the fixed
``.jax_cache`` directory at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — git-ignored; the same path on every run.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
