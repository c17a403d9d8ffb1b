"""Plain reference for `repro.eval.metrics`: the dense implementation the
compact judgments view replaced, kept verbatim. Every measure converts the
whole qrels matrix to float64, and NDCG sorts each full row at every cutoff;
the tests hold the view-based measures to these results bit for bit."""

from __future__ import annotations

import numpy as np


def _grades_at_ranks(run_ids: np.ndarray, qrels: np.ndarray) -> np.ndarray:
    """Relevance grade of each ranked position, 0 for empty (-1) slots."""
    run_ids = np.asarray(run_ids)
    qrels = np.asarray(qrels)
    if run_ids.ndim != 2 or qrels.ndim != 2 or run_ids.shape[0] != qrels.shape[0]:
        raise ValueError(f"shape mismatch: run {run_ids.shape} vs qrels {qrels.shape}")
    safe = np.clip(run_ids, 0, qrels.shape[1] - 1)
    g = np.take_along_axis(qrels.astype(np.float64), safe, axis=1)
    return np.where(run_ids >= 0, g, 0.0)


def precision_at_k(run_ids: np.ndarray, qrels: np.ndarray, k: int) -> np.ndarray:
    """P@k per query (graded qrels are binarized as grade > 0)."""
    rel = _grades_at_ranks(run_ids[:, :k], qrels) > 0
    return rel.sum(axis=1) / float(k)


def recall_at_k(run_ids: np.ndarray, qrels: np.ndarray, k: int) -> np.ndarray:
    """Fraction of each query's relevant docs retrieved in the top k."""
    rel = _grades_at_ranks(run_ids[:, :k], qrels) > 0
    n_rel = (np.asarray(qrels) > 0).sum(axis=1)
    return np.where(n_rel > 0, rel.sum(axis=1) / np.maximum(n_rel, 1), 0.0)


def average_precision(run_ids: np.ndarray, qrels: np.ndarray) -> np.ndarray:
    """AP per query over the full run depth; MAP = ``average_precision().mean()``."""
    rel = _grades_at_ranks(run_ids, qrels) > 0
    ranks = np.arange(1, rel.shape[1] + 1, dtype=np.float64)
    prec_at_rank = np.cumsum(rel, axis=1) / ranks  # P@rank at every position
    n_rel = (np.asarray(qrels) > 0).sum(axis=1)
    ap_sum = (prec_at_rank * rel).sum(axis=1)
    return np.where(n_rel > 0, ap_sum / np.maximum(n_rel, 1), 0.0)


def reciprocal_rank(run_ids: np.ndarray, qrels: np.ndarray) -> np.ndarray:
    """1/rank of the first relevant doc per query (0 if none retrieved)."""
    rel = _grades_at_ranks(run_ids, qrels) > 0
    first = np.argmax(rel, axis=1)  # 0 when no hit — disambiguate via any()
    return np.where(rel.any(axis=1), 1.0 / (first + 1.0), 0.0)


def ndcg_at_k(run_ids: np.ndarray, qrels: np.ndarray, k: int) -> np.ndarray:
    """NDCG@k per query with exponential gains (graded or binary qrels).

    A run shallower than ``k`` simply contributes no gain at the missing
    ranks (ideal DCG still uses the full ``k``), matching trec_eval."""
    gains = 2.0 ** _grades_at_ranks(run_ids[:, :k], qrels) - 1.0
    discounts = 1.0 / np.log2(np.arange(2, k + 2, dtype=np.float64))
    dcg = (gains * discounts[: gains.shape[1]]).sum(axis=1)
    # ideal ranking: each query's grades sorted descending, truncated to k
    ideal = np.sort(np.asarray(qrels).astype(np.float64), axis=1)[:, ::-1][:, :k]
    idcg = ((2.0**ideal - 1.0) * discounts[: ideal.shape[1]]).sum(axis=1)
    return np.where(idcg > 0, dcg / np.maximum(idcg, 1e-12), 0.0)


def evaluate(run_ids: np.ndarray, qrels: np.ndarray, ks: tuple[int, ...]) -> dict:
    """``evaluate_run``'s report card from the measures above (no spans)."""
    per_query = {
        "ap": average_precision(run_ids, qrels),
        "rr": reciprocal_rank(run_ids, qrels),
    }
    for k in ks:
        per_query[f"p@{k}"] = precision_at_k(run_ids, qrels, k)
        per_query[f"recall@{k}"] = recall_at_k(run_ids, qrels, k)
        per_query[f"ndcg@{k}"] = ndcg_at_k(run_ids, qrels, k)
    aggregate = {
        "map" if name == "ap" else "mrr" if name == "rr" else name: float(v.mean())
        for name, v in per_query.items()
    }
    return {"aggregate": aggregate, "per_query": per_query}
