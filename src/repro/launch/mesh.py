"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any jax import; smoke tests
see 1 CPU device).

Single pod: (16, 16) = 256 chips, axes (data, model) — a TPU v5e pod.
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model); the "pod" axis
carries cross-pod data parallelism (gradient all-reduce over DCN/ICI).
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): 1D data mesh."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
