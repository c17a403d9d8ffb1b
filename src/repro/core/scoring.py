"""Pluggable scoring functions — the paper's ``experimental_score``.

MIREX's whole point is that a *new retrieval approach is a new scoring
function*, not a change to index machinery. The contract here is the TPU
adaptation of that idea: a scorer is a **blocked** function

    score_block(query_block, doc_block) -> scores [n_q, n_d]

so that new approaches stay ~20 lines while the scan engine and kernels keep
the MXU busy. Two families:

* ``lexical`` — raw-token scan, exactly the paper's setting. Documents are
  padded token-id arrays; term frequencies are recomputed on the fly from the
  raw text every scan (no index!), which is the "radical new approaches can use
  anything in the document" property the paper argues for. Every lexical
  scorer further decomposes into the shared tf reduction plus a declarative
  **epilogue** (`EpilogueMode` + `LexicalEpilogue`, applied by
  `apply_epilogue`) — the contract the fused Pallas lexical-scan kernel
  consumes, and what lets one kernel pass score a whole model grid.
* ``dense``   — learned-representation scan (two-tower recsys, neural IR); the
  block score is a plain matmul and the hot path of the Pallas kernel.

The default lexical scorer is the paper's own: Hiemstra's query-likelihood
language model with a document-length prior, eq. of [Hiemstra 2001]:

    score(q, d) = log |d| + sum_{t in q} log(1 + lam * tf(t,d) * |C|
                                                / ((1-lam) * cf(t) * |d|))
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

PAD_TOKEN = -1


class CollectionStats(NamedTuple):
    """Corpus-wide statistics (output of the stats MapReduce job)."""

    cf: jax.Array  # [vocab] collection term frequency
    df: jax.Array  # [vocab] document frequency
    total_terms: jax.Array  # scalar: |C|
    n_docs: jax.Array  # scalar
    avg_doc_len: jax.Array  # scalar


def term_frequencies(
    q_tokens: jax.Array, d_tokens: jax.Array, *, tile_d: int = 16
) -> jax.Array:
    """tf[t, q, d] of each query term in each doc, from raw token ids.

    ``q_tokens [n_q, L_q]``, ``d_tokens [n_d, L_d]`` (PAD_TOKEN-padded) ->
    ``tf [n_q, L_q, n_d]`` float32. This *is* the sequential scan: no posting
    list, just an equality reduction over the raw document text.

    The reduction over ``L_d`` is tiled (``tile_d`` positions per step), so
    the live intermediate is ``[n_q, L_q, n_d, tile_d]`` — the full rank-4
    ``[n_q, L_q, n_d, L_d]`` cross-product is never materialized and the
    scan stays memory-bounded (~10x over the dense form on the CPU host;
    see benchmarks/lexical_scan.py). Query pads are remapped to a sentinel
    that matches nothing, which subsumes the doc-side validity mask: real
    tokens are >= 0, so they never equal PAD_TOKEN either.
    """
    n_d, L_d = d_tokens.shape
    q_safe = jnp.where(q_tokens == PAD_TOKEN, jnp.int32(PAD_TOKEN - 1), q_tokens)
    pad = (-L_d) % tile_d
    if pad:
        d_tokens = jnp.pad(d_tokens, ((0, 0), (0, pad)), constant_values=PAD_TOKEN)
    tiles = d_tokens.reshape(n_d, -1, tile_d).transpose(1, 0, 2)  # [n_tiles, n_d, tile_d]

    def fold(acc, tile):
        eq = q_safe[:, :, None, None] == tile[None, None, :, :]
        return acc + jnp.sum(eq, axis=-1, dtype=jnp.int32), None

    acc0 = jnp.zeros((*q_tokens.shape, n_d), jnp.int32)
    tf, _ = jax.lax.scan(fold, acc0, tiles)
    return tf.astype(jnp.float32)


def term_frequencies_dense(q_tokens: jax.Array, d_tokens: jax.Array) -> jax.Array:
    """Seed rank-4 form of :func:`term_frequencies`, kept as the parity
    oracle and the benchmark baseline — materializes the full
    ``[n_q, L_q, n_d, L_d]`` equality cross-product."""
    eq = q_tokens[:, :, None, None] == d_tokens[None, None, :, :]
    valid_d = (d_tokens != PAD_TOKEN)[None, None, :, :]
    return jnp.sum(eq & valid_d, axis=-1).astype(jnp.float32)


# --------------------------------------------------------------- epilogues
#
# Every lexical scorer decomposes into the *shared* term-frequency reduction
# (the dominant chunk cost) plus a cheap per-term **epilogue**: a declarative
# spec small enough to evaluate on the VPU inside the fused Pallas kernel
# (`repro.kernels.lexical_scan`) and on the pure-JAX fallback path with the
# *same code* (`epilogue_scores`), so both paths run the same operations in
# the same order on the same exact tf. The static half (`EpilogueMode`) selects the
# per-term transform and the doc-length treatment; the traced half
# (`LexicalEpilogue`) is a per-term weight table plus two doc-length
# normalization scalars.


@dataclasses.dataclass(frozen=True)
class EpilogueMode:
    """Static (hashable) half of a lexical scorer's epilogue spec.

    ``mode`` picks the per-term transform of ``(weights w, tf, doc len)``:

    * ``"ql"``    — ``log1p(w * tf / |d|)``  (Hiemstra's log-odds)
    * ``"bm25"``  — ``w * tf / (tf + alpha + beta * |d|)``  (BM25 saturation)
    * ``"tfidf"`` — ``w * log1p(tf)``

    ``length_prior`` adds ``log |d|`` (QL LM document prior);
    ``length_norm="rsqrt"`` divides the summed score by ``sqrt(|d|)``.
    """

    mode: str  # "ql" | "bm25" | "tfidf"
    length_prior: bool = False
    length_norm: str = "none"  # "none" | "rsqrt"


class LexicalEpilogue(NamedTuple):
    """Traced half of the epilogue spec (per model in a grid).

    ``weights [n_q, L_q]`` fold the collection statistics and the query
    validity mask into one per-term table (zero for PAD / zero-frequency
    terms, so masked terms contribute exactly 0); ``alpha``/``beta`` are the
    BM25 doc-length normalization ``tf + alpha + beta*|d|`` (zero scalars
    for the other modes).
    """

    weights: jax.Array  # [n_q, L_q] float32
    alpha: jax.Array  # scalar float32
    beta: jax.Array  # scalar float32


def apply_epilogue(
    mode: EpilogueMode, ep: LexicalEpilogue, tf: jax.Array, d_len: jax.Array
) -> jax.Array:
    """Score a block from its term frequencies: ``[n_q, L_q, n_d] -> [n_q, n_d]``.

    The host-layout entry to :func:`epilogue_scores`, the code the Pallas
    kernel epilogue runs too.
    """
    return epilogue_scores(
        mode,
        jnp.swapaxes(ep.weights, 0, 1)[:, :, None],
        ep.alpha,
        ep.beta,
        jnp.swapaxes(tf, 0, 1),
        d_len[None, :],
    )


def epilogue_scores(
    mode: EpilogueMode,
    w: jax.Array,  # [L_q, n_q, 1] float32 per-term weights
    alpha: jax.Array,
    beta: jax.Array,
    tf: jax.Array,  # [L_q, n_q, n_d] float32, term-major
    d_len: jax.Array,  # [1, n_d] int32
) -> jax.Array:
    """Term-major epilogue ``-> [n_q, n_d]``, shared verbatim by the Pallas
    kernel and the pure-JAX fold.

    Every array stays 2-D per query term (docs along lanes in the kernel),
    and the sum over query terms is an explicit left fold, so XLA and Mosaic
    evaluate the same operations in the same order. VPU-only ops: no
    gathers, no matmuls — the collection statistics were already folded into
    the weights when the epilogue was built.
    """
    d_len_f = jnp.maximum(d_len.astype(jnp.float32), 1.0)  # [1, n_d]
    if mode.mode == "ql":
        def per_term(j):
            return jnp.log1p(w[j] * tf[j] / d_len_f)
    elif mode.mode == "bm25":
        norm = alpha + beta * d_len.astype(jnp.float32)

        def per_term(j):
            return w[j] * tf[j] / (tf[j] + norm)
    elif mode.mode == "tfidf":
        def per_term(j):
            return w[j] * jnp.log1p(tf[j])
    else:
        raise ValueError(f"unknown epilogue mode {mode.mode!r}")
    score = per_term(0)
    for j in range(1, tf.shape[0]):
        score = score + per_term(j)
    if mode.length_prior:
        score = score + jnp.log(d_len_f)
    if mode.length_norm == "rsqrt":
        score = score / jnp.sqrt(d_len_f)
    # padded corpus rows (len 0) must never enter the top-k
    return jnp.where(d_len > 0, score, -jnp.inf)


def ql_lm_epilogue(
    q_tokens: jax.Array,
    stats: CollectionStats,
    *,
    lam: float = 0.15,
    length_prior: bool = True,
) -> tuple[EpilogueMode, LexicalEpilogue]:
    """Hiemstra QL LM: ``w = lam * |C| / ((1-lam) * cf)`` per valid term."""
    cf = jnp.asarray(stats.cf)[jnp.clip(q_tokens, 0, None)].astype(jnp.float32)
    q_valid = (q_tokens != PAD_TOKEN) & (cf > 0)
    safe_cf = jnp.where(cf > 0, cf, 1.0)
    total = jnp.asarray(stats.total_terms).astype(jnp.float32)
    w = jnp.where(q_valid, lam * total / ((1.0 - lam) * safe_cf), 0.0)
    zero = jnp.float32(0.0)
    return EpilogueMode("ql", length_prior=length_prior), LexicalEpilogue(w, zero, zero)


def bm25_epilogue(
    q_tokens: jax.Array,
    stats: CollectionStats,
    *,
    k1: float = 1.2,
    b: float = 0.75,
) -> tuple[EpilogueMode, LexicalEpilogue]:
    """Okapi BM25: ``w = idf * (k1+1)``, saturation ``tf + k1(1-b) + (k1 b/avgdl)|d|``."""
    df = jnp.asarray(stats.df)[jnp.clip(q_tokens, 0, None)].astype(jnp.float32)
    n = jnp.asarray(stats.n_docs).astype(jnp.float32)
    idf = jnp.log1p((n - df + 0.5) / (df + 0.5))
    q_valid = (q_tokens != PAD_TOKEN) & (df > 0)
    w = jnp.where(q_valid, idf * (k1 + 1.0), 0.0)
    avgdl = jnp.asarray(stats.avg_doc_len).astype(jnp.float32)
    return EpilogueMode("bm25"), LexicalEpilogue(
        w, jnp.float32(k1 * (1.0 - b)), jnp.float32(k1 * b) / avgdl
    )


def tfidf_epilogue(
    q_tokens: jax.Array, stats: CollectionStats
) -> tuple[EpilogueMode, LexicalEpilogue]:
    """ltc tf-idf: ``w = idf``, score scaled by ``1/sqrt(|d|)``."""
    df = jnp.asarray(stats.df)[jnp.clip(q_tokens, 0, None)].astype(jnp.float32)
    n = jnp.asarray(stats.n_docs).astype(jnp.float32)
    idf = jnp.log((n + 1.0) / (df + 1.0))
    q_valid = (q_tokens != PAD_TOKEN) & (df > 0)
    w = jnp.where(q_valid, idf, 0.0)
    zero = jnp.float32(0.0)
    return EpilogueMode("tfidf", length_norm="rsqrt"), LexicalEpilogue(w, zero, zero)


def hiemstra_lm(
    q_tokens: jax.Array,
    d_tokens: jax.Array,
    d_len: jax.Array,
    stats: CollectionStats,
    *,
    lam: float = 0.15,
    length_prior: bool = True,
    tf: jax.Array | None = None,
) -> jax.Array:
    """The paper's scorer: query-likelihood LM with length prior.

    ``tf`` lets a multi-scorer scan share one :func:`term_frequencies`
    reduction per corpus chunk across a whole grid of variants.
    """
    if tf is None:
        tf = term_frequencies(q_tokens, d_tokens)  # [n_q, L_q, n_d]
    mode, ep = ql_lm_epilogue(q_tokens, stats, lam=lam, length_prior=length_prior)
    return apply_epilogue(mode, ep, tf, d_len)


def bm25(
    q_tokens: jax.Array,
    d_tokens: jax.Array,
    d_len: jax.Array,
    stats: CollectionStats,
    *,
    k1: float = 1.2,
    b: float = 0.75,
    tf: jax.Array | None = None,
) -> jax.Array:
    """Okapi BM25 over the raw-token scan (a "new approach" in 5 lines)."""
    if tf is None:
        tf = term_frequencies(q_tokens, d_tokens)
    mode, ep = bm25_epilogue(q_tokens, stats, k1=k1, b=b)
    return apply_epilogue(mode, ep, tf, d_len)


def tfidf(
    q_tokens: jax.Array,
    d_tokens: jax.Array,
    d_len: jax.Array,
    stats: CollectionStats,
    *,
    tf: jax.Array | None = None,
) -> jax.Array:
    """Plain ltc-style tf-idf, length-normalized."""
    if tf is None:
        tf = term_frequencies(q_tokens, d_tokens)
    mode, ep = tfidf_epilogue(q_tokens, stats)
    return apply_epilogue(mode, ep, tf, d_len)


def dense_dot(q_vecs: jax.Array, d_vecs: jax.Array) -> jax.Array:
    """Dense inner-product block score — the MXU/Pallas hot path."""
    return jax.lax.dot_general(
        q_vecs,
        d_vecs,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def dense_cosine(q_vecs: jax.Array, d_vecs: jax.Array, eps: float = 1e-6) -> jax.Array:
    qn = q_vecs / (jnp.linalg.norm(q_vecs, axis=-1, keepdims=True) + eps)
    dn = d_vecs / (jnp.linalg.norm(d_vecs, axis=-1, keepdims=True) + eps)
    return dense_dot(qn, dn)


@dataclasses.dataclass(frozen=True)
class Scorer:
    """A retrieval approach = kind + block function (+ params).

    ``params`` records keyword overrides bound onto ``fn`` (a grid point in
    an experiment); ``base`` names the unparameterized scorer it came from.
    ``epilogue`` is the lexical decomposition contract
    ``(q_tokens, stats) -> (EpilogueMode, LexicalEpilogue)`` — the scorer
    restated as shared-tf + declarative epilogue, which is what the fused
    Pallas lexical kernel consumes (None for dense scorers).
    """

    name: str
    kind: str  # "lexical" | "dense"
    fn: Callable
    base: str | None = None
    params: tuple[tuple[str, object], ...] = ()
    epilogue: Callable | None = None

    def score_block(
        self,
        queries,
        doc_block,
        stats: CollectionStats | None = None,
        *,
        tf: jax.Array | None = None,
    ):
        if self.kind == "lexical":
            d_tokens, d_len = doc_block
            if tf is not None:
                return self.fn(queries, d_tokens, d_len, stats, tf=tf)
            return self.fn(queries, d_tokens, d_len, stats)
        return self.fn(queries, doc_block)


SCORERS: dict[str, Scorer] = {
    "ql_lm": Scorer("ql_lm", "lexical", hiemstra_lm, epilogue=ql_lm_epilogue),
    "bm25": Scorer("bm25", "lexical", bm25, epilogue=bm25_epilogue),
    "tfidf": Scorer("tfidf", "lexical", tfidf, epilogue=tfidf_epilogue),
    "dense_dot": Scorer("dense_dot", "dense", dense_dot),
    "dense_cosine": Scorer("dense_cosine", "dense", dense_cosine),
}


def get_scorer(name: str) -> Scorer:
    try:
        return SCORERS[name]
    except KeyError:
        raise KeyError(f"unknown scorer {name!r}; available: {sorted(SCORERS)}") from None


def make_variant(base: str, name: str | None = None, **params) -> Scorer:
    """A grid point: ``base`` scorer with keyword parameters bound.

    ``make_variant("bm25", k1=0.9, b=0.4)`` is a *new retrieval approach* in
    the paper's sense — same block contract, new model — which is what lets
    one corpus pass score a whole parameter grid (`scan.search_local_multi`).
    """
    b = get_scorer(base)
    fn = functools.partial(b.fn, **params) if params else b.fn
    ep = b.epilogue
    if ep is not None and params:
        ep = functools.partial(ep, **params)  # fn and epilogue share param names
    if name is None:
        name = base if not params else (
            base + "(" + ",".join(f"{k}={v}" for k, v in sorted(params.items())) + ")"
        )
    return Scorer(
        name, b.kind, fn, base=base, params=tuple(sorted(params.items())), epilogue=ep
    )


def lexical_epilogues(
    scorers: tuple[Scorer, ...] | list[Scorer],
    q_tokens: jax.Array,
    stats: CollectionStats,
) -> tuple[tuple[EpilogueMode, ...], jax.Array, jax.Array]:
    """Assemble a grid's epilogue specs for the fused lexical kernel.

    Returns ``(modes, weights [n_models, n_q, L_q], ab [n_models, 2])`` —
    the static mode tuple is hashable (a jit static arg), the weight tables
    and (alpha, beta) scalars ride along as traced arrays.
    """
    modes, weights, ab = [], [], []
    for s in scorers:
        if s.kind != "lexical" or s.epilogue is None:
            raise ValueError(f"scorer {s.name!r} has no lexical epilogue")
        mode, ep = s.epilogue(q_tokens, stats)
        modes.append(mode)
        weights.append(ep.weights)
        ab.append(jnp.stack([ep.alpha, ep.beta]))
    return tuple(modes), jnp.stack(weights), jnp.stack(ab)
