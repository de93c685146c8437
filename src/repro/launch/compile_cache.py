"""Persistent XLA compilation cache shared by every entry point.

At full width a cold run compiles the decode scan and every prefill bucket,
which on a chip takes minutes; a warm cache turns that into a read. Each
entry point (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``,
``benchmarks/*``) calls :func:`enable_compile_cache` before it touches JAX
devices.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself and
no other directory is set in code. Otherwise the cache lives at a fixed
path, ``.jax_cache/`` at the checkout root (git-ignored) — fixed because
the path is part of the cache key, so a moving directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
