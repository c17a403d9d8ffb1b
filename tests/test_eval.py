"""repro.eval: metrics against hand-computed values, TREC I/O, significance."""

import _eval_reference as ref
import numpy as np
import pytest

from repro.eval import metrics, significance, trec

# Hand-worked example, 2 queries × 8 docs, run depth 4.
#   q0 ranking [0, 1, 2, 3]; relevant docs {0, 2, 5} (grades 3, 1, 2)
#   q1 ranking [3, 4, 5, 0]; relevant docs {4}    (grade 1)
RUN = np.array([[0, 1, 2, 3], [3, 4, 5, 0]])
QRELS = np.zeros((2, 8), np.int8)
QRELS[0, 0], QRELS[0, 2], QRELS[0, 5] = 3, 1, 2
QRELS[1, 4] = 1
BINARY = (QRELS > 0).astype(np.int8)


def test_precision_at_k_hand_computed():
    # q0: top-2 = [rel, not] -> 1/2; q1: top-2 = [not, rel] -> 1/2
    np.testing.assert_allclose(metrics.precision_at_k(RUN, QRELS, 2), [0.5, 0.5])
    # q0: [rel, not, rel, not] -> 2/4; q1: [not, rel, not, not] -> 1/4
    np.testing.assert_allclose(metrics.precision_at_k(RUN, QRELS, 4), [0.5, 0.25])


def test_recall_at_k_hand_computed():
    # q0 has 3 relevant, 2 retrieved in top-4; q1 has 1, retrieved
    np.testing.assert_allclose(metrics.recall_at_k(RUN, QRELS, 4), [2 / 3, 1.0])


def test_average_precision_hand_computed():
    # q0: hits at ranks 1, 3 -> (1/1 + 2/3) / 3 relevant = 5/9
    # q1: hit at rank 2 -> (1/2) / 1 = 1/2
    np.testing.assert_allclose(
        metrics.average_precision(RUN, QRELS), [5 / 9, 1 / 2]
    )


def test_reciprocal_rank_hand_computed():
    np.testing.assert_allclose(metrics.reciprocal_rank(RUN, QRELS), [1.0, 0.5])


def test_ndcg_hand_computed():
    # q0 gains at ranks 1..4: 2^3-1, 0, 2^1-1, 0 -> DCG = 7/log2(2) + 1/log2(4)
    # ideal grades [3, 2, 1] -> IDCG = 7/log2(2) + 3/log2(3) + 1/log2(4)
    dcg0 = 7.0 + 1.0 / 2.0
    idcg0 = 7.0 + 3.0 / np.log2(3.0) + 1.0 / 2.0
    # q1: gain 1 at rank 2 -> DCG = 1/log2(3); ideal -> 1/log2(2)
    dcg1 = 1.0 / np.log2(3.0)
    np.testing.assert_allclose(
        metrics.ndcg_at_k(RUN, QRELS, 4), [dcg0 / idcg0, dcg1], rtol=1e-12
    )


def test_ndcg_run_shallower_than_k():
    # depth-3 run, k=5: missing ranks contribute no gain, ideal still uses k
    run = np.array([[0, 1, 2], [3, 4, 5]])
    got = metrics.ndcg_at_k(run, QRELS, 5)
    full = metrics.ndcg_at_k(RUN, QRELS, 4)
    assert got.shape == (2,)
    assert 0.0 < got[0] <= full[0]  # q0 loses nothing (its 4th rank had no gain)


def test_perfect_ranking_is_one():
    run = np.array([[0, 5, 2, 1]])  # q0's docs in descending-grade order
    assert metrics.ndcg_at_k(run, QRELS[:1], 4)[0] == pytest.approx(1.0)
    run_bin = np.array([[0, 2, 5, 7]])
    assert metrics.average_precision(run_bin, BINARY[:1])[0] == pytest.approx(1.0)


def test_empty_slots_and_unjudged_queries():
    run = np.array([[0, -1, -1, -1], [-1, -1, -1, -1]])
    p = metrics.precision_at_k(run, QRELS, 4)
    np.testing.assert_allclose(p, [0.25, 0.0])  # -1 slots never count as hits
    no_rel = np.zeros((2, 8), np.int8)
    assert metrics.average_precision(RUN, no_rel).tolist() == [0.0, 0.0]
    assert metrics.reciprocal_rank(RUN, no_rel).tolist() == [0.0, 0.0]
    assert metrics.ndcg_at_k(RUN, no_rel, 4).tolist() == [0.0, 0.0]


def test_evaluate_run_aggregates():
    rep = metrics.evaluate_run(RUN, QRELS, ks=(2, 4))
    assert rep["aggregate"]["map"] == pytest.approx((5 / 9 + 1 / 2) / 2)
    assert rep["aggregate"]["mrr"] == pytest.approx(0.75)
    assert rep["aggregate"]["p@2"] == pytest.approx(0.5)
    assert set(rep["per_query"]) == {
        "ap", "rr", "p@2", "recall@2", "ndcg@2", "p@4", "recall@4", "ndcg@4",
    }
    with pytest.raises(ValueError, match="exceeds run depth"):
        metrics.evaluate_run(RUN, QRELS, ks=(5,))


def _graded(rng, n_q, n_docs, per_query, grades):
    qrels = np.zeros((n_q, n_docs), np.int8)
    for q in range(n_q):
        docs = rng.choice(n_docs, size=per_query, replace=False)
        qrels[q, docs] = rng.choice(grades, size=per_query)
    return qrels


def _case(name):
    """(run [n_q, depth], qrels, cutoffs) for one equivalence case; cutoffs
    may run deeper than the run or the collection."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n_q, n_docs, depth, ks = 6, 256, 30, (5, 10, 20)
    per_query, grades = 12, (1, 2, 3)
    if name == "negative_grades":  # rows nearly all judged: negatives reach top 20
        n_docs, per_query, grades = 24, 20, (-2, -1, 1, 2, 3)
    elif name == "n_docs_below_k":
        n_docs, depth, per_query, grades = 12, 24, 8, (-2, 1, 2, 3)
    elif name == "judged_beyond_k":
        per_query = 60
    elif name == "ragged_row_width":
        n_q, n_docs = 5, 203  # 1,015 bytes: words and a 7-byte tail
    elif name == "shallow_run":
        depth = 8
    qrels = _graded(rng, n_q, n_docs, per_query, grades)
    if name == "bool":
        qrels = qrels > 0
    if name == "unjudged_query":
        qrels[2] = 0
    if name == "ragged_row_width":
        qrels[-1, -1] = 2  # a judgment in the tail
    if name == "column_major":  # idcg sums in the layout's order, as the sort did
        qrels = np.asfortranarray(qrels)
    # a run that retrieves some judged documents among unjudged ones
    run = np.full((n_q, depth), -1)
    for q in range(n_q):
        run[q, : min(n_docs, depth)] = rng.permutation(n_docs)[:depth]
    judged = np.flatnonzero(qrels[0])
    run[0, : min(len(judged), depth)] = judged[:depth]
    if name == "empty_slots":
        run[:, depth // 2 :] = -1
        run[1] = -1
    return run, qrels, ks


EQUIVALENCE_CASES = [
    "sparse_grades", "bool", "negative_grades", "unjudged_query", "empty_slots",
    "shallow_run", "n_docs_below_k", "judged_beyond_k", "ragged_row_width",
    "column_major",
]


@pytest.mark.parametrize("name", EQUIVALENCE_CASES)
def test_measures_bitwise_equal_to_dense_reference(name):
    run, qrels, ks = _case(name)
    view = metrics.judgments(qrels, max(ks))
    full = np.sort(qrels.astype(np.float64), axis=1)[:, ::-1][:, : max(ks)]
    assert np.array_equal(view.ideal, full)
    assert view.qrels is qrels and view.n_judged == np.count_nonzero(qrels)
    assert np.array_equal(view.n_rel, (qrels > 0).sum(axis=1))
    for q in (qrels, view):
        for fn in ("average_precision", "reciprocal_rank"):
            want = getattr(ref, fn)(run, qrels)
            assert np.array_equal(getattr(metrics, fn)(run, q), want), fn
        for k in (*ks, run.shape[1] + 3):
            for fn in ("precision_at_k", "recall_at_k", "ndcg_at_k"):
                if q is view and fn == "ndcg_at_k" and k > max(ks):
                    continue  # deeper than the view's ideal ranks
                want = getattr(ref, fn)(run, qrels, k)
                assert np.array_equal(getattr(metrics, fn)(run, q, k), want), (fn, k)
    within = tuple(k for k in ks if k <= run.shape[1])
    want = ref.evaluate(run, qrels, within)
    got = metrics.evaluate_run(run, qrels, ks=within)
    assert got["aggregate"] == want["aggregate"]
    assert got["per_query"].keys() == want["per_query"].keys()
    for key, v in want["per_query"].items():
        assert np.array_equal(got["per_query"][key], v), key


def test_evaluate_run_from_judgments_equals_from_matrix():
    run, qrels, ks = _case("negative_grades")
    from_view = metrics.evaluate_run(run, metrics.judgments(qrels, max(ks)), ks=ks)
    from_matrix = metrics.evaluate_run(run, qrels, ks=ks)
    assert from_view["aggregate"] == from_matrix["aggregate"]
    for key, v in from_matrix["per_query"].items():
        assert np.array_equal(from_view["per_query"][key], v), key


def test_ndcg_refuses_judgments_shallower_than_k():
    run, qrels, _ = _case("sparse_grades")
    with pytest.raises(ValueError, match="ideal ranks to 5"):
        metrics.ndcg_at_k(run, metrics.judgments(qrels, 5), 10)


def test_trec_run_roundtrip(tmp_path):
    scores = np.array([[4.0, 3.5, 2.25, -1.125], [9.0, 8.5, 0.1, -3.75]])
    path = str(tmp_path / "a.run")
    trec.write_run(path, RUN, scores, run_tag="test/a")
    ids, sc, tag = trec.read_run(path)
    np.testing.assert_array_equal(ids, RUN)
    np.testing.assert_array_equal(sc, scores)
    assert tag == "test/a"


def test_trec_run_valid_mask_roundtrip(tmp_path):
    scores = np.array([[4.0, 3.5, 2.0, 1.0], [9.0, 8.5, 7.0, 6.0]])
    valid = np.array([[True, True, False, False], [True, True, True, True]])
    path = str(tmp_path / "b.run")
    trec.write_run(path, RUN, scores, run_tag="t", valid=valid)
    ids, sc, _ = trec.read_run(path)
    assert ids[0].tolist() == [0, 1, -1, -1]  # masked slots -> empty sentinels
    assert sc[0][2] == -np.inf
    np.testing.assert_array_equal(ids[1], RUN[1])


def test_trec_write_deterministic(tmp_path):
    scores = np.array([[1 / 3, 0.1, 0.07, 1e-17], [2.0, 1.0, 0.5, 0.25]])
    a, b = str(tmp_path / "a.run"), str(tmp_path / "b.run")
    trec.write_run(a, RUN, scores, run_tag="t")
    trec.write_run(b, RUN, scores.copy(), run_tag="t")
    assert open(a, "rb").read() == open(b, "rb").read()


def test_qrels_roundtrip(tmp_path):
    path = str(tmp_path / "qrels.txt")
    trec.write_qrels(path, QRELS)
    back = trec.read_qrels(path, n_queries=2, n_docs=8)
    np.testing.assert_array_equal(back, QRELS)


def test_significance_identical_runs():
    a = np.array([0.2, 0.4, 0.6, 0.8])
    res = significance.paired_randomization_test(a, a.copy(), n_permutations=500)
    assert res.diff == 0.0
    assert res.p_value == pytest.approx(1.0)


def test_significance_detects_dominant_system():
    rng = np.random.default_rng(0)
    b = rng.uniform(0.2, 0.4, size=50)
    a = b + 0.2  # uniformly better
    res = significance.paired_randomization_test(a, b, n_permutations=2000, seed=1)
    assert res.diff == pytest.approx(0.2)
    assert res.p_value < 0.01
    # symmetric: swapping systems flips the sign, not the p-value
    rev = significance.paired_randomization_test(b, a, n_permutations=2000, seed=1)
    assert rev.diff == pytest.approx(-0.2)
    assert rev.p_value == res.p_value


def test_significance_validates_input():
    with pytest.raises(ValueError):
        significance.paired_randomization_test(np.zeros(3), np.zeros(4))
    with pytest.raises(ValueError):
        significance.paired_randomization_test(np.zeros(0), np.zeros(0))
