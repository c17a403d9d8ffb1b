"""Plain reference of the dense scorer: the inner product of each query
with each stored float32 vector, at float32 precision
(``jax.default_matmul_precision("highest")``: a v5e otherwise multiplies
float32 in one bfloat16 pass), on the device in blocks of documents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16384  # documents per step


@functools.partial(jax.jit, static_argnames=("block", "depth", "dtype"))
def _ranked(vectors, queries, *, block, depth, dtype):
    n_q = queries.shape[0]
    n_blocks = vectors.shape[0] // block
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def body(i, carry):
        best_s, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(vectors, i * block, block)
        s = jnp.dot(
            queries.astype(dtype), rows.astype(dtype).T, precision=precision,
            preferred_element_type=jnp.float32,
        )
        ids = (i * block + jnp.arange(block, dtype=jnp.int32))[None, :]
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        top_s, pos = jax.lax.top_k(cat_s, depth)
        return top_s, jnp.take_along_axis(cat_i, pos, axis=1)

    init = (
        jnp.full((n_q, depth), -jnp.inf, jnp.float32),
        jnp.full((n_q, depth), -1, jnp.int32),
    )
    return jax.lax.fori_loop(0, n_blocks, body, init)


@jax.jit
def _scores_of(vectors, queries, ids):
    rows = vectors[jnp.clip(ids, 0, vectors.shape[0] - 1)]  # [n_q, k, dim]
    return jnp.einsum(
        "qd,qkd->qk", queries, rows, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


class DenseReference:
    """The reference for one query set over ``vectors_dev [n_docs, dim]``."""

    def __init__(self, vectors_dev, queries):
        self.vectors = vectors_dev
        self.queries = jnp.asarray(np.asarray(queries), jnp.float32)
        n_docs = vectors_dev.shape[0]
        self.block = BLOCK if n_docs % BLOCK == 0 else n_docs

    def ranked(self, depth: int, dtype=jnp.float32):
        """Each query's best ``depth`` ``(ids, scores)`` by a ``dtype`` product."""
        s, i = _ranked(self.vectors, self.queries, block=self.block, depth=depth, dtype=dtype)
        return np.asarray(i), np.asarray(s)

    def best(self, k: int) -> np.ndarray:
        """``[n_q, k]`` each query's ``k`` best scores, descending."""
        _, s = self.ranked(k)
        return s.astype(np.float64)

    def scores_of(self, ids: np.ndarray) -> np.ndarray:
        return np.asarray(_scores_of(self.vectors, self.queries, jnp.asarray(ids))).astype(
            np.float64
        )
