"""The pipelined scan executor: shared compiled fold, segment prefetch,
async checkpoint commits, concurrent shards.

The contract under test is that every overlap the executor introduces is
*invisible in the artifacts*: pipelined jobs — including killed-and-resumed
ones, and concurrent-shard ones — produce states, checkpoints, progress
manifests, and TREC run files byte-identical to the synchronous sequential
executor's, while compiling the segment fold exactly once per
configuration.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro import cluster
from repro.core import anchors, pipeline, scoring, topk
from repro.data import synthetic
from repro.experiments import runner

VOCAB = 2048
N_DOCS = 512
CHUNK = 64
K = 10


@pytest.fixture(scope="module")
def collection():
    corpus = synthetic.make_corpus(n_docs=N_DOCS, vocab=VOCAB, max_len=32, seed=7)
    stats = anchors.collection_stats(
        jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths), vocab=VOCAB,
        chunk_size=CHUNK,
    )
    queries = jnp.asarray(synthetic.make_queries(corpus, n_queries=8, seed=8))
    docs = (jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths))
    return stats, queries, docs


def assert_states_identical(got, want, *, err=""):
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids), err_msg=err)
    assert np.asarray(got.scores).tobytes() == np.asarray(want.scores).tobytes(), err


# -- shared fold cache --------------------------------------------------------


def test_four_shard_job_compiles_fold_exactly_once(collection):
    """The per-shard retrace fix: equal-shaped shards (the plan invariant)
    plus the config-keyed fold cache mean a 4-shard job — 8 segment folds —
    traces the fold one single time."""
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm", lam=0.777)]  # key unique to this test
    fold = cluster.segment_fold(scorers, k=K, chunk_size=CHUNK, use_kernel=False)
    assert cluster.FOLD_TRACE_COUNTS[fold.key] == 0
    job = cluster.run_sharded_scan_job(
        queries, docs, scorers, k=K, chunk_size=CHUNK, segment_chunks=1,
        n_shards=4, stats=stats,
    )
    assert job.segments_run == 8  # 4 shards x 2 segments each actually folded
    assert cluster.FOLD_TRACE_COUNTS[fold.key] == 1
    # segments are chunk-aligned, so a 2-shard job folds the *same* segment
    # shape — zero new traces for a different shard count
    cluster.run_sharded_scan_job(
        queries, docs, scorers, k=K, chunk_size=CHUNK, segment_chunks=1,
        n_shards=2, stats=stats,
    )
    assert cluster.FOLD_TRACE_COUNTS[fold.key] == 1
    # a different segmentation is a different segment shape: exactly one more
    cluster.run_sharded_scan_job(
        queries, docs, scorers, k=K, chunk_size=CHUNK, segment_chunks=2,
        n_shards=4, stats=stats,
    )
    assert cluster.FOLD_TRACE_COUNTS[fold.key] == 2


def test_fold_cache_keys_on_configuration(collection):
    a = cluster.segment_fold(
        [scoring.make_variant("bm25")], k=K, chunk_size=CHUNK
    )
    b = cluster.segment_fold(
        [scoring.make_variant("bm25")], k=K, chunk_size=CHUNK
    )
    assert a is b  # equal config -> the same shared program
    c = cluster.segment_fold(
        [scoring.make_variant("bm25", k1=0.9)], k=K, chunk_size=CHUNK
    )
    assert c is not a  # a different grid point is a different program


# -- segment prefetch ---------------------------------------------------------


def test_prefetch_segments_yields_exact_slices(collection):
    _, _, docs = collection
    segs = pipeline.segments(N_DOCS, CHUNK, 2)
    got = list(pipeline.prefetch_segments(docs, segs, device=jax.devices()[0]))
    assert len(got) == len(segs)
    for (a, b), seg in zip(segs, got):
        for leaf, want in zip(jax.tree.leaves(seg), jax.tree.leaves(docs)):
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want[a:b]))


def test_prefetch_segments_early_close_stops_worker(collection):
    _, _, docs = collection
    segs = pipeline.segments(N_DOCS, CHUNK, 1)  # 8 segments, depth 2
    stream = pipeline.prefetch_segments(docs, segs, depth=2)
    first = next(stream)
    assert jax.tree.leaves(first)[0].shape[0] == CHUNK
    stream.close()  # must not hang on the staged-but-unconsumed segments


def test_prefetch_segments_rejects_bad_depth(collection):
    _, _, docs = collection
    with pytest.raises(ValueError, match="depth"):
        next(pipeline.prefetch_segments(docs, [(0, CHUNK)], depth=0))


# -- pipelined == sequential, byte for byte -----------------------------------


@pytest.mark.parametrize("n_shards", [1, 4])
def test_pipelined_matches_sequential_executor(collection, tmp_path, n_shards):
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm"), scoring.make_variant("bm25")]
    kw = dict(k=K, chunk_size=CHUNK, segment_chunks=2, stats=stats,
              n_shards=n_shards)
    seq = cluster.run_sharded_scan_job(
        queries, docs, scorers, ckpt_dir=str(tmp_path / "seq"),
        pipelined=False, **kw
    )
    pipe = cluster.run_sharded_scan_job(
        queries, docs, scorers, ckpt_dir=str(tmp_path / "pipe"),
        pipelined=True, **kw
    )
    assert_states_identical(pipe.state, seq.state, err=f"{n_shards} shards")
    pa = runner.write_run_files(str(tmp_path / "ra"), scorers, seq.state, tag_prefix="t")
    pb = runner.write_run_files(str(tmp_path / "rb"), scorers, pipe.state, tag_prefix="t")
    for name in pa:
        assert open(pa[name], "rb").read() == open(pb[name], "rb").read(), name
    # the async writer left the same checkpoint layout the sync path leaves
    sub = "" if n_shards == 1 else "shard_0000"
    assert (
        ckpt.all_steps(str(tmp_path / "pipe" / sub))
        == ckpt.all_steps(str(tmp_path / "seq" / sub))
    )


def test_pipelined_kill_resume_byte_identical(collection, tmp_path):
    """Injected lost-ack kill on the pipelined path: the async writer's
    drain-before-kill makes the commit visible, and the resumed pipelined
    job matches the uninterrupted sequential executor byte for byte."""
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm"), scoring.make_variant("bm25")]
    kw = dict(k=K, chunk_size=CHUNK, segment_chunks=2, stats=stats, n_shards=4)
    seq = cluster.run_sharded_scan_job(
        queries, docs, scorers, pipelined=False, **kw
    )
    with pytest.raises(RuntimeError, match="injected failure"):
        cluster.run_sharded_scan_job(
            queries, docs, scorers, ckpt_dir=str(tmp_path / "p"),
            fail_at_segment=0, fail_at_shard=2, pipelined=True, **kw
        )
    # the kill struck *after* the async commit drained: segment 1 is durable
    prog = cluster.read_progress(str(tmp_path / "p" / "shard_0002"))
    assert prog["shards"]["2"]["segments_done"] == 1
    resumed = cluster.run_sharded_scan_job(
        queries, docs, scorers, ckpt_dir=str(tmp_path / "p"), pipelined=True, **kw
    )
    assert resumed.shard_results[2].resumed_from == 1
    assert_states_identical(resumed.state, seq.state)


def test_concurrent_shard_executor_matches_sequential(collection, tmp_path):
    """max_workers > 1 forces the thread-pool path even on one device; the
    plan-ordered reduce keeps the merged bytes identical however shards
    interleave, and a shard failure propagates deterministically."""
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm"), scoring.make_variant("bm25")]
    kw = dict(k=K, chunk_size=CHUNK, segment_chunks=2, stats=stats, n_shards=4)
    seq = cluster.run_sharded_scan_job(queries, docs, scorers, pipelined=False, **kw)
    conc = cluster.run_sharded_scan_job(
        queries, docs, scorers, pipelined=True, max_workers=4, **kw
    )
    assert_states_identical(conc.state, seq.state)

    with pytest.raises(RuntimeError, match="injected failure"):
        cluster.run_sharded_scan_job(
            queries, docs, scorers, ckpt_dir=str(tmp_path / "c"),
            fail_at_segment=0, fail_at_shard=1, pipelined=True, max_workers=4, **kw
        )
    # concurrent peers were already in flight and ran to completion; the
    # resumed job restores them as no-ops and re-runs only the killed shard
    resumed = cluster.run_sharded_scan_job(
        queries, docs, scorers, ckpt_dir=str(tmp_path / "c"),
        pipelined=True, max_workers=4, **kw
    )
    assert resumed.shard_results[1].resumed_from == 1
    assert_states_identical(resumed.state, seq.state)


def test_pipelined_kernel_path_matches_host(collection):
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm"), scoring.make_variant("bm25")]
    kw = dict(k=K, chunk_size=CHUNK, segment_chunks=2, stats=stats, n_shards=2)
    host = cluster.run_sharded_scan_job(queries, docs, scorers, pipelined=False, **kw)
    kern = cluster.run_sharded_scan_job(
        queries, docs, scorers, pipelined=True, max_workers=2, use_kernel=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(kern.state.ids), np.asarray(host.state.ids))


# -- async checkpointing under the job ---------------------------------------


def test_async_writer_error_fails_the_job(collection, tmp_path, monkeypatch):
    """A checkpoint that cannot commit must fail the job at the next drain
    barrier — never report a scan complete whose progress is not durable."""
    stats, queries, docs = collection
    scorers = [scoring.make_variant("ql_lm")]
    real_save = ckpt.save

    def failing_save(ckpt_dir, step, tree, **kw):
        if step == 2:
            raise OSError("disk full (injected)")
        return real_save(ckpt_dir, step, tree, **kw)

    monkeypatch.setattr(ckpt, "save", failing_save)
    with pytest.raises(OSError, match="disk full"):
        cluster.run_scan_job(
            queries, docs, scorers, k=K, chunk_size=CHUNK, segment_chunks=2,
            stats=stats, ckpt_dir=str(tmp_path / "w"), pipelined=True,
        )
    # fail-stop: nothing after the failed step 2 was committed, and step 1
    # is intact — the job resumes from there
    assert ckpt.all_steps(str(tmp_path / "w")) == [1]
    prog = cluster.read_progress(str(tmp_path / "w"))
    assert prog["shards"]["0"]["segments_done"] == 1


# -- serve: shared mesh-program cache ----------------------------------------


def test_sharded_sessions_share_mesh_program(collection, mesh11):
    from repro.serve.session import ShardedLexicalSession

    stats, queries, docs = collection
    tokens, lengths = np.asarray(docs[0]), np.asarray(docs[1])
    a = ShardedLexicalSession(
        mesh11, tokens, lengths, "ql_lm", k=K, chunk_size=CHUNK, stats=stats
    )
    b = ShardedLexicalSession(
        mesh11, tokens, lengths, "ql_lm", k=K, chunk_size=CHUNK, stats=stats
    )
    assert a._fn is b._fn  # second session reuses the cached mesh program
    q = np.asarray(queries)
    assert_states_identical(b.search(q), a.search(q))


# -- experiment lifecycle flag ------------------------------------------------


def test_experiment_pipelined_flag_round_trips(tmp_path):
    import dataclasses

    from repro.experiments import grid as exp_grid

    spec = dataclasses.replace(
        exp_grid.get_experiment("smoke"), segment_chunks=1, n_queries=8
    )
    coll = runner.prepare_collection(spec)
    r_seq = runner.run_experiment(
        spec, out_dir=str(tmp_path / "seq"), collection=coll, pipelined=False
    )
    r_pipe = runner.run_experiment(
        spec, out_dir=str(tmp_path / "pipe"), collection=coll, pipelined=True
    )
    assert r_seq["job"]["pipelined"] is False
    assert r_pipe["job"]["pipelined"] is True
    for name in r_seq["runs"]:
        assert (
            open(r_seq["runs"][name], "rb").read()
            == open(r_pipe["runs"][name], "rb").read()
        ), name
    assert r_seq["metrics"] == r_pipe["metrics"]


def test_stager_claim_never_races_its_staging_thread():
    """A worker may claim a shard the instant another worker publishes its
    staging thread: `take` must always find a started thread to join."""
    import sys
    import threading

    from repro.cluster.job import _ShardStager

    n_shards = 32
    docs = (np.zeros((n_shards * 8, 4), np.int32), np.zeros(n_shards * 8, np.int32))
    plan = cluster.plan_shards(n_shards * 8, n_shards=n_shards, chunk_size=8)
    device = jax.devices()[0]
    errors = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            stager = _ShardStager(docs, plan, [device], seg_rows=8)

            def stage():
                for _ in range(n_shards):
                    stager.stage_next()

            def claim():
                try:
                    for i in range(n_shards):
                        stager.take(i, device)
                except RuntimeError as e:  # joined before it was started
                    errors.append(e)

            workers = [threading.Thread(target=f) for f in (stage, claim, stage)]
            for t in workers:
                t.start()
            for t in workers:
                t.join(timeout=30)
                assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
