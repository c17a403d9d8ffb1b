"""Inputs made from ``--seed``: corpora and vectors on the device in one
jitted call each, queries, schedules and qrels on the host.

The same seed gives the same inputs. Every seed gives the same set of sizes
(query lengths, request counts) in another order, so the work of a run does
not move with the seed; only which tokens and which arrival times change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = -1  # the padding token of the system's corpus format


def key_of(seed: int, stream: int = 0) -> jax.Array:
    """A JAX key from any whole number (more than 32 bits hold)."""
    s = int(seed) % (1 << 64)
    key = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, s >> 32), stream)


def rng_of(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@functools.partial(
    jax.jit, static_argnames=("n_docs", "pad", "min_len", "vocab", "alpha")
)
def _corpus(key, *, n_docs, pad, min_len, vocab, alpha):
    k_len, k_tok = jax.random.split(key)
    lengths = jax.random.randint(k_len, (n_docs,), min_len, pad + 1, dtype=jnp.int32)
    u = jax.random.uniform(k_tok, (n_docs, pad), jnp.float32)
    # inverse CDF of a continuous power law on [1, vocab + 1): Zipf-like ids
    a = 1.0 - alpha
    top = (vocab + 1.0) ** a
    rank = (1.0 + u * (top - 1.0)) ** (1.0 / a)
    tokens = jnp.clip(jnp.floor(rank).astype(jnp.int32) - 1, 0, vocab - 1)
    tokens = jnp.where(jnp.arange(pad)[None, :] < lengths[:, None], tokens, PAD)
    return tokens, lengths


def corpus(seed: int, *, n_docs: int, pad: int, min_len: int, vocab: int, alpha: float):
    """``(tokens [n_docs, pad] int32, lengths [n_docs] int32)`` on the device:
    lengths uniform in ``[min_len, pad]``, token ids Zipf-like over ``vocab``,
    ``PAD`` past each length."""
    return _corpus(
        key_of(seed, 1), n_docs=n_docs, pad=pad, min_len=min_len, vocab=vocab,
        alpha=float(alpha),
    )


@functools.partial(jax.jit, static_argnames=("n", "dim"))
def _vectors(key, *, n, dim):
    return jax.random.normal(key, (n, dim), jnp.float32) * jnp.float32(dim**-0.5)


def vectors(seed: int, *, n: int, dim: int, stream: int = 2) -> jax.Array:
    """``[n, dim]`` float32 Gaussian vectors of about unit norm, on the device,
    at full float32 resolution (no grid)."""
    return _vectors(key_of(seed, stream), n=n, dim=dim)


def term_counts(n: int, lo: int, hi: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` query lengths: ``lo..hi`` in equal shares (the first ``n mod
    (hi - lo + 1)`` lengths once more), in an order drawn from ``rng``."""
    span = np.arange(lo, hi + 1)
    return rng.permutation(np.resize(span, n))


def lexical_queries(
    tokens: np.ndarray, lengths: np.ndarray, counts: np.ndarray, slots: int,
    rng: np.random.Generator,
) -> np.ndarray:
    """One query per entry of ``counts``: that many terms drawn from the
    positions of one document, ``PAD`` in the remaining ``slots``."""
    n = len(counts)
    docs = rng.integers(0, tokens.shape[0], size=n)
    pos = (rng.random((n, slots)) * lengths[docs][:, None]).astype(np.int64)
    terms = tokens[docs[:, None], pos]
    return np.where(np.arange(slots)[None, :] < counts[:, None], terms, PAD).astype(np.int32)


def dense_queries(n: int, dim: int, rng: np.random.Generator) -> np.ndarray:
    return (rng.standard_normal((n, dim)) * dim**-0.5).astype(np.float32)


def graded_qrels(
    n_queries: int, n_docs: int, per_query: int, rng: np.random.Generator
) -> np.ndarray:
    """``[n_queries, n_docs]`` int8 grades 1..3 on ``per_query`` documents of
    each query, drawn from the seed alone (no output of the system)."""
    qrels = np.zeros((n_queries, n_docs), np.int8)
    for q in range(n_queries):
        docs = rng.choice(n_docs, size=per_query, replace=False)
        qrels[q, docs] = rng.integers(1, 4, size=per_query)
    return qrels
