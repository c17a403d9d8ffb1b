"""Fused lexical-scan kernel: interpret-mode parity vs the host scorers.

The contract under test (ISSUE 3 acceptance): for every lexical scorer ×
parameter variant, with PAD_TOKEN-padded queries/docs and zero-length corpus
rows, the kernel's rankings match the pure-JAX chunked fold **id-exactly**
under the shared tie-break (score desc, then smaller doc id — what
``lax.top_k``'s positional stability means on a scan whose candidate ids
increase monotonically) and score-wise to fp32 tolerance. Plus: a whole
model grid scanned in one kernel pass equals `scan.search_local_multi`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import anchors, packing, scan, scoring, topk
from repro.data import synthetic
from repro.kernels import lexical_scan

VOCAB = 300
CHUNK = 64
N_PAD_ROWS = 64  # zero-length corpus rows appended to the synthetic corpus
N_REAL = 256

VARIANTS = [
    scoring.get_scorer("ql_lm"),
    scoring.make_variant("ql_lm", lam=0.5, length_prior=False),
    scoring.get_scorer("bm25"),
    scoring.make_variant("bm25", k1=0.9, b=0.4),
    scoring.get_scorer("tfidf"),
]


@pytest.fixture(scope="module")
def collection():
    corpus = synthetic.make_corpus(n_docs=N_REAL, vocab=VOCAB, max_len=24, seed=3)
    toks = np.concatenate(
        [corpus.tokens, np.full((N_PAD_ROWS, 24), scoring.PAD_TOKEN, np.int32)]
    )
    lens = np.concatenate([corpus.lengths, np.zeros(N_PAD_ROWS, np.int32)])
    stats = anchors.collection_stats(
        jnp.asarray(toks), jnp.asarray(lens), vocab=VOCAB, chunk_size=CHUNK
    )
    queries = synthetic.make_queries(corpus, n_queries=12, seed=4)
    assert (queries == scoring.PAD_TOKEN).any()  # padded query rows in play
    return (jnp.asarray(toks), jnp.asarray(lens)), stats, jnp.asarray(queries)


def _assert_state_parity(host: topk.TopKState, kern: topk.TopKState):
    np.testing.assert_array_equal(np.asarray(kern.ids), np.asarray(host.ids))
    np.testing.assert_allclose(
        np.asarray(kern.scores), np.asarray(host.scores), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("scorer", VARIANTS, ids=lambda s: s.name)
def test_kernel_matches_host_fold(collection, scorer):
    docs, stats, queries = collection
    host = scan.search_local(queries, docs, scorer, k=16, chunk_size=CHUNK, stats=stats)
    kern = scan.search_local(
        queries, docs, scorer, k=16, chunk_size=CHUNK, stats=stats, use_kernel=True
    )
    _assert_state_parity(host, kern)


def test_kernel_padded_rows_never_surface(collection):
    docs, stats, queries = collection
    kern = scan.search_local(
        queries, docs, scoring.get_scorer("ql_lm"), k=16, chunk_size=CHUNK,
        stats=stats, use_kernel=True,
    )
    assert int(jnp.max(kern.ids)) < N_REAL  # no zero-length row in the top-k


def test_grid_in_one_kernel_pass_matches_multi(collection):
    """[n_models, n_q, k] grid state from one kernel pass == host multi-scan."""
    docs, stats, queries = collection
    host = scan.search_local_multi(
        queries, docs, VARIANTS, k=16, chunk_size=CHUNK, stats=stats
    )
    kern = scan.search_local_multi(
        queries, docs, VARIANTS, k=16, chunk_size=CHUNK, stats=stats, use_kernel=True
    )
    assert kern.scores.shape == (len(VARIANTS), queries.shape[0], 16)
    _assert_state_parity(host, kern)


def test_kernel_k_exceeds_corpus(collection):
    """k > n_docs: empty slots carry the host's (-inf, -1) sentinels."""
    docs, stats, queries = collection
    tiny = (docs[0][:CHUNK], docs[1][:CHUNK])
    host = scan.search_local(
        queries, tiny, scoring.get_scorer("bm25"), k=100, chunk_size=CHUNK, stats=stats
    )
    kern = scan.search_local(
        queries, tiny, scoring.get_scorer("bm25"), k=100, chunk_size=CHUNK,
        stats=stats, use_kernel=True,
    )
    _assert_state_parity(host, kern)
    assert not bool(topk.valid_mask(kern)[:, CHUNK:].any())


def test_kernel_resume_from_init_state(collection):
    """Segmented kernel passes (the scan-job path) == one unsegmented scan."""
    docs, stats, queries = collection
    grid = VARIANTS[:3]
    full = scan.search_local_multi(
        queries, docs, grid, k=16, chunk_size=CHUNK, stats=stats, use_kernel=True
    )
    half = 3 * CHUNK  # chunk-aligned segment boundary
    seg_a = scan.search_local_multi(
        queries, (docs[0][:half], docs[1][:half]), grid,
        k=16, chunk_size=CHUNK, stats=stats, use_kernel=True,
    )
    seg_b = scan.search_local_multi(
        queries, (docs[0][half:], docs[1][half:]), grid,
        k=16, chunk_size=CHUNK, stats=stats,
        doc_id_offset=half, init_state=seg_a, use_kernel=True,
    )
    _assert_state_parity(full, seg_b)


def test_kernel_respects_doc_id_offset(collection):
    docs, stats, queries = collection
    off = scan.search_local(
        queries, docs, scoring.get_scorer("ql_lm"), k=8, chunk_size=CHUNK,
        stats=stats, doc_id_offset=1000, use_kernel=True,
    )
    base = scan.search_local(
        queries, docs, scoring.get_scorer("ql_lm"), k=8, chunk_size=CHUNK,
        stats=stats, use_kernel=True,
    )
    valid = np.asarray(topk.valid_mask(base))
    np.testing.assert_array_equal(
        np.asarray(off.ids)[valid], np.asarray(base.ids)[valid] + 1000
    )
    assert (np.asarray(off.ids)[~valid] == -1).all()  # sentinels never shifted


def test_tiled_tf_matches_dense_reference(collection):
    """The memory-bounded fallback is bit-equal to the seed rank-4 reduction."""
    docs, _, queries = collection
    tiled = scoring.term_frequencies(queries, docs[0])
    dense = scoring.term_frequencies_dense(queries, docs[0])
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(dense))
    # odd tile width exercises the L_d padding path
    tiled7 = scoring.term_frequencies(queries, docs[0], tile_d=7)
    np.testing.assert_array_equal(np.asarray(tiled7), np.asarray(dense))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_non_multiple_chunk_raises(collection, use_kernel):
    docs, stats, queries = collection
    with pytest.raises(ValueError, match="not a multiple of chunk_size"):
        scan.search_local(
            queries, docs, scoring.get_scorer("ql_lm"), k=8, chunk_size=50,
            stats=stats, use_kernel=use_kernel,
        )


# (L_q, block_d, query rows, token packing, models): geometries where the
# register-blocked tf loop is cut differently — one slot group or several
# (L_q 32), one document sub-block or several — and where the fold takes
# one row tile, all eight, seven (50 rows) or two groups of six (96 rows)
BLOCKING = [
    (1, 128, 3, "none", 1),
    (1, 512, 64, "16", 2),
    (5, 128, 64, "none", 1),
    (5, 512, 64, "16", 2),
    (8, 128, 3, "16", 2),
    (8, 512, 64, "none", 1),
    (32, 128, 64, "16", 1),
    (32, 512, 3, "none", 2),
    (8, 512, 50, "none", 2),
    (5, 128, 96, "16", 1),
]


@pytest.fixture(scope="module")
def blocking_corpus():
    n_docs, vocab, length = 1024, 300, 24
    corpus = synthetic.make_corpus(n_docs=n_docs, vocab=vocab, max_len=length, seed=11)
    toks, lens = jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths)
    stats = anchors.collection_stats(toks, lens, vocab=vocab, chunk_size=128)
    return corpus, stats


@pytest.mark.parametrize(
    "l_q, block_d, rows, pack, n_models", BLOCKING,
    ids=lambda v: str(v),
)
def test_blocked_tf_bitwise_matches_host_fold(blocking_corpus, l_q, block_d, rows, pack, n_models):
    """Scores and ids of the kernel are bitwise the host fold's, whatever
    slot groups and document sub-blocks the tf loop is cut into and however
    many row tiles each fold takes."""
    corpus, stats = blocking_corpus
    rng = np.random.default_rng(l_q * 1000 + block_d + rows)
    # queries drawn from documents' own tokens, with pads in every row but
    # the first (so some rows hold all L_q terms, some fewer)
    docs_of = rng.integers(0, corpus.tokens.shape[0], rows)
    q = np.full((rows, l_q), scoring.PAD_TOKEN, np.int32)
    for i, d in enumerate(docs_of):
        n = l_q if i == 0 else int(rng.integers(1, l_q + 1))
        q[i, :n] = corpus.tokens[d, rng.integers(0, corpus.lengths[d], n)]
    queries = jnp.asarray(q)
    docs = jax.tree.map(
        jnp.asarray,
        packing.pack_corpus(corpus.tokens, corpus.lengths, vocab=300, mode=pack),
    )
    assert isinstance(docs, packing.PackedCorpus) == (pack != "none")
    scorers = [scoring.get_scorer("bm25"), scoring.get_scorer("ql_lm")][:n_models]
    host = scan.search_local_multi(
        queries, docs, scorers, k=16, chunk_size=block_d, stats=stats
    )
    kern = scan.search_local_multi(
        queries, docs, scorers, k=16, chunk_size=block_d, stats=stats, use_kernel=True
    )
    np.testing.assert_array_equal(np.asarray(kern.ids), np.asarray(host.ids))
    np.testing.assert_array_equal(np.asarray(kern.scores), np.asarray(host.scores))
    assert int((np.asarray(kern.ids) >= 0).sum()) > 0


# every (L_q, block_d) the cells and the tuning space use: query slots 8
# (`mirex`, `clueweb09b-web09`, `msmarco-passage`), the blocks `lex_block`
# gives (chunks halved to 512) and `lex_block_d` values the tuning tests try
GEOMETRY_SHAPES = [
    (l_q, block_d)
    for l_q in (1, 2, 4, 5, 8, 12, 16, 17, 32, 64)
    for block_d in (32, 48, 64, 128, 256, 384, 512, 1024, 2048)
]


def test_tf_geometry_stays_within_the_vreg_budget():
    """The live query column and accumulator of one slot group and
    sub-block fit `TF_VREG_BUDGET`, the groups cover whole terms, and the
    sub-blocks tile the block."""
    for l_q, block_d in GEOMETRY_SHAPES:
        terms, lanes = lexical_scan.tf_geometry(l_q, block_d)
        vregs = 2 * terms * -(-lanes // 128)  # [terms * 8, lanes] int32, twice
        assert vregs <= lexical_scan.TF_VREG_BUDGET, (l_q, block_d)
        assert 1 <= terms <= l_q and block_d % lanes == 0, (l_q, block_d)
        if block_d % 128 == 0:
            assert lanes % 128 == 0, (l_q, block_d)
        assert terms == l_q or 2 * (terms + 1) > lexical_scan.TF_VREG_BUDGET
    # the serve and batch cells' shape, and the slot split
    assert lexical_scan.tf_geometry(8, 512) == (8, 256)
    assert lexical_scan.tf_geometry(32, 512) == (16, 128)


def test_fold_tiles_divide_the_block():
    """The fold takes whole groups of row tiles, at most `FOLD_TILES`."""
    for tiles in range(1, 200):
        g = lexical_scan.fold_tiles(tiles)
        assert tiles % g == 0 and 1 <= g <= lexical_scan.FOLD_TILES
        assert all(tiles % h for h in range(g + 1, lexical_scan.FOLD_TILES + 1))
    # serve buckets of 8..64 rows, 50 queries of the batch cell, 96 rows
    assert [lexical_scan.fold_tiles(t) for t in (1, 2, 4, 8, 7, 12)] == [1, 2, 4, 8, 7, 6]
