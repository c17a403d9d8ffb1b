"""Plain reference of the lexical scorers: BM25 and the query-likelihood
language model with a length prior, over raw padded token rows.

Semantics, as the configurations state them (one term per query slot, so a
repeated term counts once per slot; a slot of ``PAD`` or of a term that
occurs nowhere adds nothing; ``|d|`` is the document's length):

* ``bm25(k1, b)``:  sum over slots of ``idf(t) (k1 + 1) tf / (tf + k1 (1 - b)
  + k1 b |d| / avgdl)``, ``idf(t) = ln(1 + (N - df + 0.5) / (df + 0.5))``;
* ``ql_lm(lam, length_prior)``: ``ln |d|`` (with the prior) plus the sum over
  slots of ``ln(1 + lam tf |C| / ((1 - lam) cf |d|))``.

``cf``, ``df``, ``|C|``, ``N`` and ``avgdl`` are counted here from the corpus
itself. Counting (term frequencies, statistics, a first ranking to find
candidates) runs on the device in plain ``jax.numpy``, exact in integers;
every score that is compared is then computed in float64 on the host. The
candidates are each query's best ``candidates`` documents by a float32 score
(or a bfloat16 one for the control), far more than the float32 error can
reorder across rank ``k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = -1
NO_TERM = -2  # a query slot that matches no token
BLOCK = 16384  # documents per step of the device passes


@functools.partial(jax.jit, static_argnames=("block",))
def _term_stats(tokens, terms, *, block):
    """cf and df of each of ``terms`` over the corpus: exact int32."""
    n_blocks = tokens.shape[0] // block

    def body(i, carry):
        cf, df = carry
        rows = jax.lax.dynamic_slice_in_dim(tokens, i * block, block)
        tf = jnp.sum(rows[:, :, None] == terms[None, None, :], axis=1, dtype=jnp.int32)
        return cf + tf.sum(0), df + jnp.sum(tf > 0, axis=0, dtype=jnp.int32)

    zero = jnp.zeros(terms.shape, jnp.int32)
    return jax.lax.fori_loop(0, n_blocks, body, (zero, zero))


@functools.partial(jax.jit, static_argnames=("mode", "block", "depth", "dtype"))
def _ranked(tokens, lengths, q_terms, w, norm, *, mode, block, depth, dtype):
    """Each query's best ``depth`` documents by a score computed in ``dtype``:
    ``(scores [n_q, depth] float32, ids [n_q, depth] int32)``."""
    n_q, slots = q_terms.shape
    flat = q_terms.reshape(-1)
    w = w.astype(dtype)
    n_blocks = tokens.shape[0] // block

    def body(i, carry):
        best_s, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(tokens, i * block, block)
        lens = jax.lax.dynamic_slice_in_dim(lengths, i * block, block)
        tf = jnp.sum(rows[:, :, None] == flat[None, None, :], axis=1, dtype=jnp.int32)
        tf = tf.reshape(block, n_q, slots).astype(dtype)  # [B, n_q, slots]
        d = jnp.maximum(lens, 1).astype(dtype)[:, None, None]
        if mode == "bm25":
            sat = norm[0].astype(dtype) + norm[1].astype(dtype) * d
            s = jnp.sum(w[None] * tf / (tf + sat), axis=-1)
        else:
            s = jnp.sum(jnp.log1p(w[None] * tf / d), axis=-1)
            if mode == "ql_prior":
                s = s + jnp.log(d[:, :, 0])
        s = jnp.where(lens[:, None] > 0, s, -jnp.inf).astype(jnp.float32).T  # [n_q, B]
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


class LexicalReference:
    """The reference for one query set over one corpus.

    ``tokens``/``lengths`` are the host copy (numpy); ``tokens_dev`` /
    ``lengths_dev`` the same arrays on the device. ``models`` maps a model
    name to ``{"base": "bm25" | "ql_lm", **params}``.
    """

    def __init__(self, tokens, lengths, tokens_dev, lengths_dev, queries, models):
        self.tokens = np.asarray(tokens)
        self.lengths = np.asarray(lengths).astype(np.int64)
        self.tokens_dev, self.lengths_dev = tokens_dev, lengths_dev
        self.queries = np.asarray(queries)
        self.models = models
        n_docs = self.tokens.shape[0]
        self.block = BLOCK if n_docs % BLOCK == 0 else n_docs
        terms = np.unique(self.queries[self.queries != PAD])
        cf, df = _term_stats(tokens_dev, jnp.asarray(terms, jnp.int32), block=self.block)
        self.cf = dict(zip(terms.tolist(), np.asarray(cf).astype(np.float64)))
        self.df = dict(zip(terms.tolist(), np.asarray(df).astype(np.float64)))
        self.total = float(self.lengths.sum())
        self.n_docs = float((self.lengths > 0).sum())
        self.avgdl = self.total / max(self.n_docs, 1.0)

    # -- float64 scores ------------------------------------------------------

    def weights(self, model: str) -> np.ndarray:
        """Per-slot term weights ``[n_q, slots]`` in float64 (0 for slots
        that add nothing)."""
        p = self.models[model]
        w = np.zeros(self.queries.shape, np.float64)
        for (qi, j), t in np.ndenumerate(self.queries):
            if t == PAD or self.cf.get(t, 0.0) <= 0:
                continue
            if p["base"] == "bm25":
                df = self.df[t]
                idf = np.log1p((self.n_docs - df + 0.5) / (df + 0.5))
                w[qi, j] = idf * (p["k1"] + 1.0)
            else:
                w[qi, j] = p["lam"] * self.total / ((1.0 - p["lam"]) * self.cf[t])
        return w

    def _norm(self, model: str) -> tuple[float, float]:
        p = self.models[model]
        if p["base"] == "bm25":
            return p["k1"] * (1.0 - p["b"]), p["k1"] * p["b"] / self.avgdl
        return 0.0, 0.0

    def _mode(self, model: str) -> str:
        p = self.models[model]
        if p["base"] == "bm25":
            return "bm25"
        return "ql_prior" if p["length_prior"] else "ql"

    def score(self, model: str, qi: int, ids: np.ndarray, w: np.ndarray) -> np.ndarray:
        """Float64 scores of documents ``ids`` for query ``qi``."""
        p = self.models[model]
        rows = self.tokens[ids]
        d = np.maximum(self.lengths[ids], 1).astype(np.float64)
        s = np.zeros(len(ids), np.float64)
        for j, t in enumerate(self.queries[qi]):
            if w[qi, j] == 0.0:
                continue
            tf = (rows == t).sum(axis=1).astype(np.float64)
            if p["base"] == "bm25":
                a, b = self._norm(model)
                s += w[qi, j] * tf / (tf + a + b * d)
            else:
                s += np.log1p(w[qi, j] * tf / d)
        if p["base"] != "bm25" and p["length_prior"]:
            s += np.log(d)
        return np.where(self.lengths[ids] > 0, s, -np.inf)

    # -- rankings --------------------------------------------------------------

    def ranked(self, model: str, depth: int, dtype=jnp.float32):
        """Each query's best ``depth`` ids by a ``dtype`` score on the device,
        with those scores: ``(ids, scores)`` as numpy."""
        q = np.where(self.queries == PAD, NO_TERM, self.queries)
        w = self.weights(model)
        s, i = _ranked(
            self.tokens_dev, self.lengths_dev, jnp.asarray(q, jnp.int32),
            jnp.asarray(w, jnp.float32), jnp.asarray(self._norm(model), jnp.float32),
            mode=self._mode(model), block=self.block, depth=depth, dtype=dtype,
        )
        return np.asarray(i), np.asarray(s)

    def best(self, model: str, k: int, candidates: int) -> np.ndarray:
        """``[n_q, k]`` float64: each query's ``k`` best scores, descending."""
        ids, _ = self.ranked(model, max(candidates, k))
        w = self.weights(model)
        out = np.empty((len(self.queries), k), np.float64)
        for qi in range(len(self.queries)):
            s = self.score(model, qi, ids[qi][ids[qi] >= 0], w)
            out[qi] = np.sort(s)[::-1][:k]
        return out

    def scores_of(self, model: str, ids: np.ndarray) -> np.ndarray:
        """``[n_q, k]`` float64 scores of the documents ``ids [n_q, k]``."""
        w = self.weights(model)
        return np.stack([self.score(model, qi, ids[qi], w) for qi in range(len(ids))])
