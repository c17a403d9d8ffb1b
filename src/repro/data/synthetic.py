"""Deterministic synthetic data: corpora, queries, qrels, links, graphs, logs.

ClueWeb09 does not fit in this container, so every experiment runs on
statistically-shaped stand-ins: Zipf token corpora (web text is Zipfian, which
is what makes both posting lists and scan-time term matching realistic),
power-law link graphs for the anchor job, and the recsys/GNN generators the
assigned architectures need. Everything is keyed by an integer seed and a
chunk index so a restarted job regenerates byte-identical shards
(restart-safe data, see DESIGN §5).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scoring
from repro.core.scoring import PAD_TOKEN


@dataclasses.dataclass(frozen=True)
class Corpus:
    tokens: np.ndarray  # [n_docs, max_len] int32, PAD_TOKEN-padded
    lengths: np.ndarray  # [n_docs] int32


def _zipf_tokens(rng: np.random.Generator, n: int, vocab: int, alpha: float) -> np.ndarray:
    """Zipf-ish token ids in [0, vocab) via inverse-CDF over rank weights."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks**-alpha
    probs /= probs.sum()
    return rng.choice(vocab, size=n, p=probs).astype(np.int32)


def make_corpus(
    *,
    n_docs: int,
    vocab: int,
    max_len: int = 64,
    min_len: int = 8,
    alpha: float = 1.1,
    seed: int = 0,
) -> Corpus:
    rng = np.random.default_rng(seed)
    lengths = rng.integers(min_len, max_len + 1, size=n_docs).astype(np.int32)
    tokens = np.full((n_docs, max_len), PAD_TOKEN, np.int32)
    flat = _zipf_tokens(rng, int(lengths.sum()), vocab, alpha)
    # row-major boolean fill: doc i takes the next lengths[i] tokens of flat
    tokens[np.arange(max_len)[None, :] < lengths[:, None]] = flat
    return Corpus(tokens=tokens, lengths=lengths)


def make_queries(
    corpus: Corpus,
    *,
    n_queries: int,
    max_q_len: int = 4,
    seed: int = 1,
) -> np.ndarray:
    """Queries sampled from corpus text (so they have matches), padded."""
    rng = np.random.default_rng(seed)
    n_docs = corpus.tokens.shape[0]
    q = np.full((n_queries, max_q_len), PAD_TOKEN, np.int32)
    for i in range(n_queries):
        qlen = int(rng.integers(1, max_q_len + 1))
        doc = int(rng.integers(0, n_docs))
        dlen = int(corpus.lengths[doc])
        picks = rng.integers(0, dlen, size=qlen)
        q[i, :qlen] = corpus.tokens[doc, picks]
    return q


_COUNT_CHUNK = 16384  # docs per device call of the query-term count


def _query_term_counts(queries: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """``[n_q, n_docs]`` occurrences of each query's terms (with their
    multiplicity in the query) in each doc: the scan's own tf reduction,
    summed over query slots, run chunk by chunk on the default device.
    Exact integers, so the ranking below does not depend on where it ran."""
    n_docs, max_len = tokens.shape
    pad = -n_docs % _COUNT_CHUNK if n_docs > _COUNT_CHUNK else 0
    if pad:
        tokens = np.concatenate([tokens, np.full((pad, max_len), PAD_TOKEN, np.int32)])
    step = min(n_docs, _COUNT_CHUNK)
    q = jnp.asarray(queries)
    parts = [
        np.asarray(_count_chunk(q, jnp.asarray(tokens[a : a + step])))
        for a in range(0, tokens.shape[0], step)
    ]
    return np.concatenate(parts, axis=1)[:, :n_docs]


@jax.jit
def _count_chunk(queries, tokens):
    return scoring.term_frequencies(queries, tokens).sum(axis=1).astype(jnp.int32)


def _density_ranked(
    corpus: Corpus, queries: np.ndarray, per_query: int, seed: int
) -> list[np.ndarray]:
    """Per query: the ``per_query`` densest matching docs, best first.

    The single source of the synthetic gold standard — binary and graded
    qrels both consume this ranking, which is what keeps
    ``make_graded_qrels(...) > 0 == make_qrels(...)`` true by construction."""
    rng = np.random.default_rng(seed)
    lengths = np.maximum(corpus.lengths, 1)
    counts = _query_term_counts(queries, corpus.tokens)
    ranked = []
    for qi in range(queries.shape[0]):
        density = counts[qi] / lengths
        density += rng.normal(0, 1e-9, density.shape)  # tie-break
        top = np.argsort(-density)[:per_query]
        ranked.append(top[density[top] > 0])
    return ranked


def make_qrels(
    corpus: Corpus,
    queries: np.ndarray,
    *,
    per_query: int = 20,
    seed: int = 2,
) -> np.ndarray:
    """Synthetic relevance: for each query the docs with the highest raw
    query-term density are 'relevant' (a golden standard generated from the
    scoring-model family, per DESIGN C4 — sanity, not SOTA)."""
    qrels = np.zeros((queries.shape[0], corpus.tokens.shape[0]), bool)
    for qi, top in enumerate(_density_ranked(corpus, queries, per_query, seed)):
        qrels[qi, top] = True
    return qrels


def make_graded_qrels(
    corpus: Corpus,
    queries: np.ndarray,
    *,
    per_query: int = 20,
    max_grade: int = 3,
    seed: int = 2,
) -> np.ndarray:
    """Graded relevance (0..max_grade) for NDCG: same density ranking as
    :func:`make_qrels`, with grades assigned by rank band (denser ⇒ higher)."""
    qrels = np.zeros((queries.shape[0], corpus.tokens.shape[0]), np.int8)
    for qi, top in enumerate(_density_ranked(corpus, queries, per_query, seed)):
        for rank, doc in enumerate(top):
            band = rank * max_grade // max(len(top), 1)  # 0 = densest band
            qrels[qi, doc] = max_grade - band
    return qrels


def make_links(
    *,
    n_docs: int,
    n_links: int,
    vocab: int,
    max_anchor_len: int = 6,
    seed: int = 3,
) -> tuple[np.ndarray, np.ndarray]:
    """Power-law link graph + anchor token strings for the anchor job."""
    rng = np.random.default_rng(seed)
    # preferential-attachment-ish dst distribution
    w = (np.arange(1, n_docs + 1, dtype=np.float64)) ** -0.9
    w /= w.sum()
    dst = rng.choice(n_docs, size=n_links, p=w).astype(np.int32)
    tokens = np.full((n_links, max_anchor_len), PAD_TOKEN, np.int32)
    lens = rng.integers(1, max_anchor_len + 1, size=n_links)
    flat = _zipf_tokens(rng, int(lens.sum()), vocab, 1.05)
    pos = 0
    for i, l in enumerate(lens):
        tokens[i, :l] = flat[pos : pos + l]
        pos += l
    return dst, tokens


def make_dense_corpus(*, n_docs: int, dim: int, seed: int = 4) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((n_docs, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=-1, keepdims=True)


def make_lm_batch(
    *, batch: int, seq_len: int, vocab: int, seed: int = 0, chunk: int = 0
) -> dict[str, np.ndarray]:
    """Deterministic LM training batch keyed by (seed, chunk) for restarts.

    Tokens are Zipf-distributed (like the corpora above): uniform tokens have
    no learnable structure at all — loss starts at ln|V| and can only walk in
    place — whereas a skewed unigram distribution gives training runs real
    signal (the convergence tests in test_system assert on it)."""
    rng = np.random.default_rng((seed, chunk))
    tokens = _zipf_tokens(rng, batch * (seq_len + 1), vocab, 1.2).reshape(
        batch, seq_len + 1
    )
    return {
        "tokens": tokens[:, :-1].astype(np.int32),
        "labels": tokens[:, 1:].astype(np.int32),
    }


def make_graph(
    *, n_nodes: int, n_edges: int, d_feat: int, n_classes: int = 16, seed: int = 5
) -> dict[str, np.ndarray]:
    """Random power-law graph (COO edge list, sorted by dst for segment ops)."""
    rng = np.random.default_rng(seed)
    w = (np.arange(1, n_nodes + 1, dtype=np.float64)) ** -0.8
    w /= w.sum()
    src = rng.choice(n_nodes, size=n_edges, p=w).astype(np.int32)
    dst = rng.integers(0, n_nodes, size=n_edges, dtype=np.int32)
    order = np.argsort(dst, kind="stable")
    return {
        "src": src[order],
        "dst": dst[order],
        "x": rng.standard_normal((n_nodes, d_feat)).astype(np.float32),
        "y": rng.integers(0, n_classes, size=n_nodes, dtype=np.int32),
    }


def make_recsys_batch(
    *,
    batch: int,
    n_dense: int,
    n_sparse: int,
    vocab_per_field: int,
    seed: int = 0,
    chunk: int = 0,
) -> dict[str, np.ndarray]:
    rng = np.random.default_rng((seed, chunk))
    dense = (
        rng.standard_normal((batch, n_dense)).astype(np.float32)
        if n_dense
        else np.zeros((batch, 0), np.float32)
    )
    sparse_ids = rng.integers(0, vocab_per_field, size=(batch, n_sparse), dtype=np.int32)
    # learnable labels from a fixed linear teacher over the dense features
    # (plus a small per-field id-parity term): coin-flip labels would pin the
    # achievable loss at ln 2 and make convergence tests meaningless
    logit = dense @ np.linspace(-1.0, 1.0, n_dense) if n_dense else np.zeros(batch)
    if n_sparse:
        logit = logit + 0.5 * ((sparse_ids[:, 0] % 2) * 2 - 1)
    return {
        "dense": dense,
        "sparse_ids": sparse_ids,
        "labels": (logit > 0).astype(np.float32),
    }


def make_item_sequences(
    *, batch: int, seq_len: int, n_items: int, seed: int = 0, chunk: int = 0
) -> dict[str, np.ndarray]:
    rng = np.random.default_rng((seed, chunk))
    seq = rng.integers(1, n_items, size=(batch, seq_len + 1), dtype=np.int32)
    return {"history": seq[:, :-1], "target": seq[:, 1:]}
