"""The comparison that decides ``correct`` for ranked answers.

For each query the system returns ``k`` document ids with scores, best
first. Against the reference (float64 for the lexical scorers, float32 at
full precision for the dense one) three numbers are read:

* ``bad_ids``   -- ids out of range, repeated within a query, or missing:
  an exact count, limit 0;
* ``score_gap`` -- the widest gap between a returned score and the
  reference's score of the same document;
* ``rank_gap``  -- the widest gap by which the reference's score of the
  document at rank ``i`` lies below the reference's ``i``-th best score:
  0 for the exact ranking, rounding for a ranking of near-ties, and large
  where a document is missing or out of place.

Both gaps are divided by the larger of 1 and the query's best reference
score, so that one limit holds across queries.
"""

from __future__ import annotations

import numpy as np


def bad_ids(ids: np.ndarray, n_docs: int) -> int:
    ids = np.asarray(ids)
    bad = int(((ids < 0) | (ids >= n_docs)).sum())
    for row in ids:
        valid = row[(row >= 0) & (row < n_docs)]
        bad += len(valid) - len(np.unique(valid))
    return bad


def gaps(ids, scores, ref_of_ids, ref_best) -> tuple[float, float]:
    """``(score_gap, rank_gap)`` of one model's answers.

    ``ids``, ``scores``: ``[n_q, k]`` as returned; ``ref_of_ids``: the
    reference's scores of those ids; ``ref_best``: the reference's ``k``
    best scores per query, descending.
    """
    scores = np.asarray(scores, np.float64)
    ref_of_ids = np.asarray(ref_of_ids, np.float64)
    ref_best = np.asarray(ref_best, np.float64)
    scale = np.maximum(np.abs(ref_best[:, :1]), 1.0)
    with np.errstate(invalid="ignore"):
        score_gap = np.abs(scores - ref_of_ids) / scale
        rank_gap = (ref_best - ref_of_ids) / scale
    # a non-finite gap (an -inf score, an id that scores nothing) is a fault
    score_gap = np.where(np.isfinite(score_gap), score_gap, np.inf)
    rank_gap = np.where(np.isnan(rank_gap), np.inf, rank_gap)
    return float(score_gap.max()), float(rank_gap.max())
