"""One import path for the JAX mesh and collective names the repo calls.

``set_mesh`` (the ambient-mesh context manager), ``axis_size`` (a concrete
int inside ``shard_map``) and a differentiable ``optimization_barrier`` are
native in the installed JAX; call sites import them from here so a future
API move is a one-line change.
"""

from __future__ import annotations

import jax

set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size
optimization_barrier = jax.lax.optimization_barrier
