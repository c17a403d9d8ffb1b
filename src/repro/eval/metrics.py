"""TREC-style rank metrics over (run, qrels) pairs — host-side numpy.

Evaluation is deliberately *not* a JAX dataflow: TREC semantics are full of
ragged, data-dependent bookkeeping (per-query relevant counts, graded gains,
rank cutoffs) that belong on the host. Everything takes

    run_ids [n_q, depth] int   — ranked doc ids, best first; ``-1`` = empty slot
    qrels   [n_q, n_docs] int/bool — relevance grades (binary qrels are grade 1)

and returns **per-query** vectors; the scalar aggregate (MAP, MRR, mean P@k …)
is just ``.mean()``. Keeping per-query values first-class is what makes the
paired randomization significance test (`repro.eval.significance`) a one-liner
downstream instead of a re-evaluation.

Runs are small (``n_q × k`` after the combiner bound), but the qrels matrix
is not: it spans the whole collection while each query judges a few hundred
documents. So no measure converts or sorts the matrix. :func:`judgments`
makes one pass over it and returns a :class:`Judgments` view, shared by
every measure and every run evaluated against the same qrels: the matrix
itself (uncopied, in its own dtype, for gathering the grades at ranked
positions; only the gathered ``[n_q, depth]`` grades become float64), each
query's count of relevant documents, and each query's ideal grade ranking
down to the deepest cutoff, built from the judged entries alone. Every
measure accepts either the matrix or the view; given the matrix it builds
the view itself.

Conventions follow trec_eval: AP divides by the number of relevant documents
(not the cutoff), queries with no relevant documents score 0 everywhere, and
NDCG uses exponential gains ``2^grade - 1`` with ``log2(rank+1)`` discounts.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs


@dataclasses.dataclass(frozen=True)
class Judgments:
    """What the measures read of a qrels matrix, from one pass over it.

    ``qrels`` is the matrix as given (no copy). ``n_rel [n_q]`` counts each
    row's grades > 0. ``ideal [n_q, min(max_k, n_docs)]`` float64 holds each
    row's grades sorted descending and truncated to ``max_k``: positive
    grades, then zeros, then negative grades (TREC Web's -2 for spam).
    ``n_judged`` is the number of nonzero entries.
    """

    qrels: np.ndarray
    n_rel: np.ndarray
    ideal: np.ndarray
    n_judged: int


def _nonzero_flat(flat: np.ndarray) -> np.ndarray:
    """Flat indices of the nonzero entries of a 1-D array, ascending.

    Scans 8 bytes at a time where the dtype packs into words, then looks
    inside the nonzero words only; qrels are almost all zeros."""
    per = 8 // flat.itemsize if 8 % flat.itemsize == 0 else 0
    if not per or not flat.flags.c_contiguous:
        return np.flatnonzero(flat)
    n_body = flat.size - flat.size % per
    body = flat[:n_body]
    words = np.flatnonzero(body.view(np.uint64))
    word_i, lane = np.nonzero(body.reshape(-1, per)[words])
    tail = np.flatnonzero(flat[n_body:]) + n_body
    return np.concatenate([words[word_i] * per + lane, tail])


def judgments(qrels: np.ndarray, max_k: int) -> Judgments:
    """The :class:`Judgments` view of ``qrels`` with ideal rankings down to
    ``max_k``, from one pass over the matrix."""
    qrels = np.asarray(qrels)
    if qrels.ndim != 2:
        raise ValueError(f"qrels must be [n_q, n_docs], got {qrels.shape}")
    n_q, n_docs = qrels.shape
    flat = qrels.reshape(-1)
    idx = _nonzero_flat(flat)
    rows = idx // max(n_docs, 1)
    grades = flat[idx].astype(np.float64)
    n_rel = np.bincount(rows[grades > 0], minlength=n_q)
    n_nz = np.bincount(rows, minlength=n_q)
    # each row's judged grades, descending
    by_grade = np.lexsort((-grades, rows))
    rows, grades = rows[by_grade], grades[by_grade]
    rank = np.arange(rows.size) - (np.cumsum(n_nz) - n_nz)[rows]
    # in the row's full descending sort the positives lead and the negatives
    # close it, after the row's n_docs - n_nz zeros
    pos = np.where(grades > 0, rank, n_docs - n_nz[rows] + rank)
    width = min(max_k, n_docs)
    keep = pos < width
    # laid out as the matrix is, so each idcg sums its terms in the order a
    # sort of the whole matrix would give them
    layout = "F" if abs(qrels.strides[0]) < abs(qrels.strides[1]) else "C"
    ideal = np.zeros((n_q, width), np.float64, order=layout)
    ideal[rows[keep], pos[keep]] = grades[keep]
    return Judgments(qrels=qrels, n_rel=n_rel, ideal=ideal, n_judged=int(idx.size))


def _view(qrels: np.ndarray | Judgments, max_k: int) -> Judgments:
    return qrels if isinstance(qrels, Judgments) else judgments(qrels, max_k)


def _matrix(qrels: np.ndarray | Judgments) -> np.ndarray:
    return qrels.qrels if isinstance(qrels, Judgments) else qrels


def _grades_at_ranks(run_ids: np.ndarray, qrels: np.ndarray) -> np.ndarray:
    """Relevance grade of each ranked position, 0 for empty (-1) slots.

    Gathers from the matrix in its own dtype; only the ``[n_q, depth]``
    result becomes float64 (exact for integer grades)."""
    run_ids = np.asarray(run_ids)
    qrels = np.asarray(qrels)
    if run_ids.ndim != 2 or qrels.ndim != 2 or run_ids.shape[0] != qrels.shape[0]:
        raise ValueError(f"shape mismatch: run {run_ids.shape} vs qrels {qrels.shape}")
    safe = np.clip(run_ids, 0, qrels.shape[1] - 1)
    g = np.take_along_axis(qrels, safe, axis=1).astype(np.float64)
    return np.where(run_ids >= 0, g, 0.0)


def precision_at_k(
    run_ids: np.ndarray, qrels: np.ndarray | Judgments, k: int
) -> np.ndarray:
    """P@k per query (graded qrels are binarized as grade > 0)."""
    rel = _grades_at_ranks(run_ids[:, :k], _matrix(qrels)) > 0
    return rel.sum(axis=1) / float(k)


def recall_at_k(
    run_ids: np.ndarray, qrels: np.ndarray | Judgments, k: int
) -> np.ndarray:
    """Fraction of each query's relevant docs retrieved in the top k."""
    view = _view(qrels, 0)
    rel = _grades_at_ranks(run_ids[:, :k], view.qrels) > 0
    n_rel = view.n_rel
    return np.where(n_rel > 0, rel.sum(axis=1) / np.maximum(n_rel, 1), 0.0)


def average_precision(run_ids: np.ndarray, qrels: np.ndarray | Judgments) -> np.ndarray:
    """AP per query over the full run depth; MAP = ``average_precision().mean()``."""
    view = _view(qrels, 0)
    rel = _grades_at_ranks(run_ids, view.qrels) > 0
    ranks = np.arange(1, rel.shape[1] + 1, dtype=np.float64)
    prec_at_rank = np.cumsum(rel, axis=1) / ranks  # P@rank at every position
    n_rel = view.n_rel
    ap_sum = (prec_at_rank * rel).sum(axis=1)
    return np.where(n_rel > 0, ap_sum / np.maximum(n_rel, 1), 0.0)


def reciprocal_rank(run_ids: np.ndarray, qrels: np.ndarray | Judgments) -> np.ndarray:
    """1/rank of the first relevant doc per query (0 if none retrieved)."""
    rel = _grades_at_ranks(run_ids, _matrix(qrels)) > 0
    first = np.argmax(rel, axis=1)  # 0 when no hit — disambiguate via any()
    return np.where(rel.any(axis=1), 1.0 / (first + 1.0), 0.0)


def ndcg_at_k(run_ids: np.ndarray, qrels: np.ndarray | Judgments, k: int) -> np.ndarray:
    """NDCG@k per query with exponential gains (graded or binary qrels).

    A run shallower than ``k`` simply contributes no gain at the missing
    ranks (ideal DCG still uses the full ``k``), matching trec_eval."""
    view = _view(qrels, k)
    width = min(k, view.qrels.shape[1])
    if view.ideal.shape[1] < width:
        raise ValueError(f"judgments hold ideal ranks to {view.ideal.shape[1]}, not {k}")
    gains = 2.0 ** _grades_at_ranks(run_ids[:, :k], view.qrels) - 1.0
    discounts = 1.0 / np.log2(np.arange(2, k + 2, dtype=np.float64))
    dcg = (gains * discounts[: gains.shape[1]]).sum(axis=1)
    # ideal ranking: each query's grades sorted descending, truncated to k
    ideal = view.ideal[:, :width]
    idcg = ((2.0**ideal - 1.0) * discounts[: ideal.shape[1]]).sum(axis=1)
    return np.where(idcg > 0, dcg / np.maximum(idcg, 1e-12), 0.0)


PER_QUERY_METRICS = {
    "ap": average_precision,
    "rr": reciprocal_rank,
}
AT_K_METRICS = {
    "p": precision_at_k,
    "recall": recall_at_k,
    "ndcg": ndcg_at_k,
}


def evaluate_run(
    run_ids: np.ndarray,
    qrels: np.ndarray | Judgments,
    *,
    ks: tuple[int, ...] = (5, 10, 20),
) -> dict:
    """The full report card for one run.

    Returns ``{"aggregate": {...}, "per_query": {...}}`` where aggregates are
    floats (``map``, ``mrr``, ``p@k`` / ``recall@k`` / ``ndcg@k`` per cutoff)
    and per-query vectors back the significance test. ``qrels`` is the
    matrix or its :func:`judgments` view; given the matrix, the view is
    built once here for every measure.
    """
    depth = np.asarray(run_ids).shape[1]
    for k in ks:
        if k > depth:
            raise ValueError(f"cutoff {k} exceeds run depth {depth}")
    qrels = _view(qrels, max(ks, default=0))
    # one span per measure (its name says which), so a trace shows where
    # evaluation spends its time
    tr = obs.tracer()
    per_query: dict[str, np.ndarray] = {}
    for short, fn in PER_QUERY_METRICS.items():
        with tr.span(f"eval.{short}", "eval"):
            per_query[short] = fn(run_ids, qrels)
    for k in ks:
        for short, fn in AT_K_METRICS.items():
            with tr.span(f"eval.{short}", "eval", k=k):
                per_query[f"{short}@{k}"] = fn(run_ids, qrels, k)
    aggregate = {
        "map" if name == "ap" else "mrr" if name == "rr" else name: float(v.mean())
        for name, v in per_query.items()
    }
    return {"aggregate": aggregate, "per_query": per_query}
