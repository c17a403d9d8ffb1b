"""Where the entry points keep JAX's persistent compilation cache.

Called from ``main()`` of the launchers and from ``chip_smoke.py`` — never
at import, so tests and library callers keep JAX's own defaults. A set
``JAX_COMPILATION_CACHE_DIR`` wins, and JAX reads it itself; otherwise the
cache lives at one fixed path inside the checkout, so a second run of the
same programs finds what the first compiled (the path is part of the key:
a directory named from a pid, the time or a temporary name never hits).
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    path = os.environ.get(ENV) or str(CHECKOUT_CACHE)
    if ENV not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the Pallas kernels compile in about a second,
    # under the default one-second floor, and a chip call starts cold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
