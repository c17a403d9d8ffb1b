"""Serve subsystem: microbatch triggers + padding, dispatch parity against
the scan engine oracles, and the k-bounded bitonic kernel merge."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import anchors, scan, scoring, topk
from repro.data import synthetic
from repro.kernels import ops
from repro.kernels.score_topk import bitonic_merge_desc, fold_block, state_width
from repro.serve import DenseSession, LexicalSession, Microbatcher, RetrievalService
from repro.serve.microbatch import bucket_size, pad_rows, unpad_results


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------- microbatch


@pytest.mark.parametrize("n,expect", [(1, 8), (7, 8), (8, 8), (9, 16), (65, 128)])
def test_bucket_size(n, expect):
    assert bucket_size(n, min_bucket=8) == expect


@pytest.mark.parametrize("n", [1, 5, 8, 13])
def test_pad_unpad_roundtrip(rng, n):
    q = rng.standard_normal((n, 16)).astype(np.float32)
    padded = pad_rows(q, bucket_size(n), 0.0)
    assert padded.shape[0] == bucket_size(n)
    assert padded.shape[0] % 8 == 0
    np.testing.assert_array_equal(unpad_results(padded, n), q)
    assert (padded[n:] == 0.0).all()


def test_size_trigger_fires_at_max_batch():
    mb = Microbatcher(max_batch=4, max_delay=10.0, pad_value=-1)
    for rid in range(3):
        mb.submit(rid, np.zeros(4, np.int32), now=0.0)
    assert not mb.ready(0.0)  # under size, before deadline
    mb.submit(3, np.zeros(4, np.int32), now=0.0)
    block = mb.pop_block(0.0)
    assert block is not None and block.trigger == "size"
    assert block.rids == (0, 1, 2, 3) and block.n_real == 4
    assert len(mb) == 0


def test_deadline_trigger_fires_on_oldest_request():
    mb = Microbatcher(max_batch=100, max_delay=0.5, min_bucket=8, pad_value=-1)
    mb.submit(0, np.zeros(4, np.int32), now=0.0)
    mb.submit(1, np.zeros(4, np.int32), now=0.3)
    assert mb.pop_block(0.49) is None  # oldest has waited 0.49 < 0.5
    assert mb.next_deadline() == pytest.approx(0.5)
    block = mb.pop_block(0.5)
    assert block is not None and block.trigger == "deadline"
    assert block.n_real == 2 and block.n_padded == 8  # padded to min bucket
    assert (block.queries[2:] == -1).all()


def test_oversize_queue_splits_into_max_batch_blocks():
    mb = Microbatcher(max_batch=4, max_delay=10.0, pad_value=-1)
    for rid in range(10):
        mb.submit(rid, np.zeros(2, np.int32), now=0.0)
    blocks = mb.drain(0.0)
    assert [b.n_real for b in blocks] == [4, 4, 2]
    assert [r for b in blocks for r in b.rids] == list(range(10))


# ------------------------------------------------------------------ dispatch


def _lexical_fixture(n_docs=512, vocab=256, chunk=64, k=10):
    corpus = synthetic.make_corpus(n_docs=n_docs, vocab=vocab, max_len=24, seed=0)
    stats = anchors.collection_stats(
        jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths), vocab=vocab, chunk_size=chunk
    )
    session = LexicalSession(
        corpus.tokens, corpus.lengths, "ql_lm", k=k, chunk_size=chunk, stats=stats
    )
    return corpus, stats, session


def test_lexical_dispatch_matches_direct_scan():
    corpus, stats, session = _lexical_fixture()
    queries = synthetic.make_queries(corpus, n_queries=13, seed=3)
    clock = FakeClock()
    service = RetrievalService({"lexical": session}, max_batch=64, max_delay=0.01, clock=clock)
    rids = [service.submit(q, "lexical") for q in queries]
    assert service.poll() == {}  # no trigger yet
    clock.advance(0.02)
    results = service.poll()
    assert sorted(results) == sorted(rids)
    ref = scan.search_local(
        jnp.asarray(queries),
        (jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths)),
        scoring.get_scorer("ql_lm"),
        k=session.k, chunk_size=session.chunk_size, stats=stats,
    )
    for row, rid in enumerate(rids):
        np.testing.assert_allclose(results[rid].scores, np.asarray(ref.scores[row]), rtol=1e-6)
        np.testing.assert_array_equal(results[rid].ids, np.asarray(ref.ids[row]))
    rec = service.metrics[-1]
    assert rec.trigger == "deadline" and rec.n_real == 13 and rec.n_padded == 16


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dense_dispatch_matches_host_oracle(rng, use_kernel):
    """Service dense path (incl. Pallas kernel dispatch) == unblocked oracle."""
    vecs = rng.standard_normal((512, 64)).astype(np.float32)
    queries = rng.standard_normal((11, 64)).astype(np.float32)
    session = DenseSession(vecs, "dense_dot", k=9, chunk_size=128, use_kernel=use_kernel)
    service = RetrievalService({"dense": session}, max_batch=11, max_delay=10.0)
    rids = [service.submit(q, "dense") for q in queries]
    results = service.poll()  # size trigger: 11 == max_batch
    assert sorted(results) == sorted(rids)
    ref = scan.search_dense_host(jnp.asarray(queries), jnp.asarray(vecs), k=9)
    for row, rid in enumerate(rids):
        np.testing.assert_allclose(results[rid].scores, np.asarray(ref.scores[row]), rtol=1e-5)
        np.testing.assert_array_equal(results[rid].ids, np.asarray(ref.ids[row]))


def test_every_query_answered_exactly_once_across_waves(rng):
    vecs = rng.standard_normal((256, 32)).astype(np.float32)
    session = DenseSession(vecs, "dense_dot", k=5, chunk_size=64, use_kernel=False)
    clock = FakeClock()
    service = RetrievalService({"dense": session}, max_batch=8, max_delay=0.1, clock=clock)
    answered = {}
    submitted = []
    for wave in range(3):
        for _ in range(11):  # 11 per wave: one size-triggered block + remainder
            submitted.append(service.submit(rng.standard_normal(32).astype(np.float32)))
        answered.update(service.poll())
        clock.advance(0.2)
    answered.update(service.poll())
    answered.update(service.drain())
    assert sorted(answered) == sorted(submitted)
    assert all(len(r.scores) == 5 for r in answered.values())


@pytest.mark.parametrize("kind", ["lexical", "dense"])
def test_session_corpus_is_an_argument_not_a_constant(rng, kind):
    """The resident corpus enters the jitted scan as an argument. Closed
    over, it would be compiled into the executable as a constant: a second
    device copy, and at full width minutes of compile and a program too big
    for the persistent compile cache."""
    if kind == "lexical":
        corpus, _, session = _lexical_fixture()
        queries = synthetic.make_queries(corpus, n_queries=8, seed=3)
        args = (jnp.asarray(queries), session._docs, session._stats)
    else:
        vecs = rng.standard_normal((512, 64)).astype(np.float32)
        session = DenseSession(vecs, k=9, chunk_size=128, use_kernel=False)
        args = (jnp.zeros((8, 64), jnp.float32), session._vectors)
    consts = session._scan.trace(*args).jaxpr.consts
    assert sum(np.size(c) for c in consts) < 1024


# -------------------------------------------------------- k-bounded merge


def test_bitonic_merge_desc_matches_numpy(rng):
    for m in (1, 2, 8, 32):
        a_s = -np.sort(-rng.standard_normal((3, m)).astype(np.float32), axis=-1)
        b_s = -np.sort(-rng.standard_normal((3, m)).astype(np.float32), axis=-1)
        a_i = rng.integers(0, 1000, (3, m)).astype(np.int32)
        b_i = rng.integers(1000, 2000, (3, m)).astype(np.int32)
        s, i = bitonic_merge_desc(
            jnp.asarray(a_s), jnp.asarray(a_i), jnp.asarray(b_s), jnp.asarray(b_i)
        )
        cat_s = np.concatenate([a_s, b_s], axis=-1)
        cat_i = np.concatenate([a_i, b_i], axis=-1)
        order = np.argsort(-cat_s, kind="stable")[:, :m]
        np.testing.assert_allclose(
            np.asarray(s), np.take_along_axis(cat_s, order, axis=-1)
        )
        np.testing.assert_array_equal(
            np.asarray(i), np.take_along_axis(cat_i, order, axis=-1)
        )


@pytest.mark.parametrize("k", [5, 16, 100, 300])
def test_kernel_bitonic_merge_matches_host_oracle(rng, k):
    """Acceptance: exact ids on distinct scores, scores within 1e-5 (k=300
    exceeds the 128-doc block)."""
    q = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((1024, 128)), jnp.float32)
    s, i = ops.score_topk(q, d, k=k, block_d=128)
    ref = scan.search_dense_host(q, d, k=k)
    np.testing.assert_allclose(np.asarray(s), np.asarray(ref.scores), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref.ids))



# exactly representable scores: every draw is riddled with ties, so the id
# tie-break decides most of the ranking
TIED_SCORES = np.array([-2.0, -0.5, 0.0, 0.25, 0.5, 1.0, 1.5, 2.0], np.float32)


def _fold_blocks_kernel(s_ref, out_s_ref, out_i_ref, *, block_d):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_s_ref[...] = jnp.full(out_s_ref.shape, -jnp.inf, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    s = s_ref[...]
    ids = step * block_d + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    out_s_ref[...], out_i_ref[...] = fold_block(out_s_ref[...], out_i_ref[...], s, ids)


def _lex_top(scores, ids, k):
    """Per row: top k by (score desc, id asc), padded with (-inf, -1)."""
    rows = scores.shape[0]
    out_s = np.full((rows, k), -np.inf, np.float32)
    out_i = np.full((rows, k), -1, np.int32)
    for r in range(rows):
        order = np.lexsort((ids[r], -scores[r]))[:k]
        out_s[r, : order.size] = scores[r, order]
        out_i[r, : order.size] = ids[r, order]
    return topk.TopKState(scores=jnp.asarray(out_s), ids=jnp.asarray(out_i))


@pytest.mark.parametrize("k,block_d", [(5, 64), (12, 128), (100, 64), (300, 128)])
def test_kernel_fold_network_matches_merge_lex(rng, k, block_d):
    """The in-kernel merge network (the roll-based bitonic sort and
    half-cleaner, interpret mode) folds tie-riddled blocks to the same bytes
    as `topk.merge_lex` over each block's own top-k: k not a power of two,
    and k larger than the block."""
    rows, n_blocks = 8, 5
    scores = rng.choice(TIED_SCORES, size=(rows, n_blocks * block_d))
    width = state_width(k)
    got_s, got_i = pl.pallas_call(
        functools.partial(_fold_blocks_kernel, block_d=block_d),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((rows, block_d), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((rows, width), lambda i: (0, 0))] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.float32),
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
        ],
        interpret=True,
    )(jnp.asarray(scores))

    ids = np.broadcast_to(np.arange(n_blocks * block_d, dtype=np.int32), scores.shape)
    want = None
    for b in range(n_blocks):
        cols = slice(b * block_d, (b + 1) * block_d)
        block = _lex_top(scores[:, cols], ids[:, cols], k)
        want = block if want is None else topk.merge_lex(want, block)
    np.testing.assert_array_equal(np.asarray(got_i)[:, :k], np.asarray(want.ids))
    assert np.asarray(got_s)[:, :k].tobytes() == np.asarray(want.scores).tobytes()
