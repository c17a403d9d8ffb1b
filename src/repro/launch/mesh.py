"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run entry point must set XLA_FLAGS first).
"""

from __future__ import annotations

import jax

from repro.compat import set_mesh  # noqa: F401  (re-export)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis. "pod" composes with "data" for all data-parallel math (the DCN-side
    axis); "model" stays intra-pod (ICI-side) for TP/EP."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Degenerate mesh for CPU smoke tests (same axis names)."""
    return jax.make_mesh((data, model), ("data", "model"))
