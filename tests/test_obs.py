"""Observability layer: tracer/metrics semantics, exporters, and the two
contracts the subsystem lives or dies by —

* **near-zero cost off, correct under concurrency on**: the disabled
  tracer returns a shared no-op span (no clock read, no allocation);
  enabled instruments never lose cross-thread updates and spans record
  even when their body raises (the timeline survives a mid-segment crash);
* **tracing observes, never decides**: a traced experiment produces
  byte-identical run files to an untraced one, faults and all.

Plus the deprecation-alias contract (satellite): ``fail_at_segment``
warnings must point at the *caller's* line at every entry point —
``run_scan_job``, ``run_sharded_scan_job``, and ``run_experiment``.
"""

import collections
import json
import threading
import warnings
from types import SimpleNamespace

import _eval_reference as eval_ref
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro import cluster, obs
from repro.cluster.faults import FaultSchedule, FaultSpec, WorkerCrash
from repro.core import anchors
from repro.data import synthetic
from repro.eval import paired_randomization_test, trec
from repro.experiments import grid as exp_grid
from repro.experiments import runner
from repro.obs import export
from repro.obs.metrics import Histogram, Metrics
from repro.obs.trace import NULL_SPAN, Tracer
from repro.serve import LexicalSession, RetrievalService

VOCAB = 1024
N_DOCS = 256
CHUNK = 32
K = 8
N_SHARDS = 2


# -- tracer semantics ---------------------------------------------------------


class StepClock:
    """Deterministic tracer clock: each read advances by ``dt``."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def test_disabled_tracer_is_a_shared_noop():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_SPAN
    with tr.span("x", "cat", a=1) as sp:
        sp.set(b=2)
    tr.instant("mark")
    tr.record("win", 0.0, 1.0)
    assert len(tr) == 0


def test_spans_record_name_cat_attrs_thread_and_duration():
    tr = Tracer(clock=StepClock())
    with tr.span("outer", "job", shard=3) as sp:
        sp.set(outcome="ok")
        with tr.span("inner", "job"):
            pass
    ev = tr.events()
    assert [e.name for e in ev] == ["inner", "outer"]  # LIFO close order
    outer = ev[1]
    assert outer.cat == "job" and outer.ph == "X"
    assert outer.attrs == {"shard": 3, "outcome": "ok"}
    assert outer.tid == threading.get_ident()
    # inner's [ts, ts+dur] window nests inside outer's (time containment)
    inner = ev[0]
    assert outer.ts < inner.ts and inner.ts + inner.dur < outer.ts + outer.dur


def test_span_records_on_error_and_reraises():
    """A fold that dies mid-span still leaves its span in the timeline,
    tagged with the exception type, and enclosing spans keep correct
    extents — the crash-forensics contract."""
    tr = Tracer(clock=StepClock())
    with pytest.raises(WorkerCrash, match="boom"):
        with tr.span("shard.run", "job", shard=0):
            with tr.span("segment.fold", "job", segment=1):
                raise WorkerCrash("boom")
    fold, shard = tr.events()
    assert fold.name == "segment.fold"
    assert fold.attrs["error"] == "WorkerCrash"
    assert shard.name == "shard.run"
    assert shard.attrs["error"] == "WorkerCrash"
    assert shard.ts < fold.ts and fold.ts + fold.dur < shard.ts + shard.dur


def test_buffer_bound_drops_oldest():
    tr = Tracer(max_events=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]


def test_instants_and_filtered_readout():
    tr = Tracer()
    tr.instant("fault.crash", "fault", shard=1)
    with tr.span("segment.fold", "job"):
        pass
    assert [e.name for e in tr.instants()] == ["fault.crash"]
    assert [e.name for e in tr.spans(cat="job")] == ["segment.fold"]
    assert tr.spans(name="nope") == []


def test_record_explicit_window():
    tr = Tracer()
    tr.record("serve.request", 10.0, 10.5, "serve", rid=7)
    (e,) = tr.events()
    assert (e.ts, e.dur) == (10.0, 0.5) and e.attrs == {"rid": 7}


def test_session_installs_and_restores():
    base_tr, base_met = obs.tracer(), obs.metrics()
    with obs.session() as (tr, met):
        assert obs.tracer() is tr and obs.metrics() is met
        assert tr.enabled
    assert obs.tracer() is base_tr and obs.metrics() is base_met


# -- metrics ------------------------------------------------------------------


def test_counter_exact_under_concurrent_increments():
    met = Metrics()
    c = met.counter("hits")

    def hammer():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000


def test_histogram_concurrent_observations_all_land():
    h = Histogram("lat")

    def hammer(v):
        for _ in range(5_000):
            h.observe(v)

    threads = [threading.Thread(target=hammer, args=(0.001 * (i + 1),)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 20_000


def test_histogram_quantiles_interpolate_and_clamp():
    h = Histogram("h", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (1.5, 1.5, 1.5, 7.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 1.5 and s["max"] == 7.0
    assert 1.0 <= s["p50"] <= 2.0
    assert s["p99"] <= 7.0  # clamped to the observed max, not the bucket edge
    single = Histogram("one")
    single.observe(0.123)
    assert single.quantile(0.5) == pytest.approx(0.123)


def test_gauge_tracks_last_and_max():
    g = Metrics().gauge("depth")
    for v in (1, 5, 2):
        g.set(v)
    assert g.value == 2 and g.max == 5


def test_registry_get_or_create_and_kind_conflict():
    met = Metrics()
    assert met.counter("a") is met.counter("a")
    with pytest.raises(TypeError, match="Counter"):
        met.gauge("a")
    met.counter("b").inc(3)
    met.histogram("c").observe(0.5)
    s = met.summary()
    assert s["counters"] == {"a": 0, "b": 3}
    assert s["histograms"]["c"]["count"] == 1


# -- exporters ----------------------------------------------------------------


def _sample_tracer():
    tr = Tracer(clock=StepClock(0.5))
    with tr.span("segment.fold", "job", shard=0, segment=0):
        pass
    tr.instant("sched.retry", "sched", shard=1)
    return tr


def test_chrome_trace_structure(tmp_path):
    tr = _sample_tracer()
    met = Metrics()
    met.counter("n").inc()
    path = export.write_chrome_trace(str(tmp_path / "t.json"), tr, metrics=met)
    doc = json.load(open(path))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert spans[0]["name"] == "segment.fold" and spans[0]["dur"] > 0
    assert min(e["ts"] for e in spans + instants) == 0.0  # rebased to t=0
    assert instants[0]["s"] == "t"
    assert metas and metas[0]["name"] == "thread_name"
    assert doc["otherData"]["metrics"]["counters"] == {"n": 1}


def test_jsonl_roundtrip(tmp_path):
    tr = _sample_tracer()
    path = export.write_jsonl(str(tmp_path / "t.jsonl"), tr)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == len(tr)
    assert lines[0]["name"] == "segment.fold"
    assert lines[0]["ts"] == tr.events()[0].ts  # raw clock preserved
    assert lines[1]["attrs"] == {"shard": 1}


def test_summary_tree_groups_by_shard():
    txt = export.summary_tree(_sample_tracer())
    assert "shard 0" in txt and "segment.fold" in txt
    assert "sched.retry×1" in txt
    rollup = export.phase_rollup(_sample_tracer())
    assert rollup["shard 0"]["segment.fold"]["count"] == 1


# -- instrumented layers ------------------------------------------------------


@pytest.fixture(scope="module")
def collection():
    corpus = synthetic.make_corpus(n_docs=N_DOCS, vocab=VOCAB, max_len=24, seed=5)
    stats = anchors.collection_stats(
        jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths), vocab=VOCAB,
        chunk_size=CHUNK,
    )
    queries = jnp.asarray(synthetic.make_queries(corpus, n_queries=4, seed=6))
    docs = (jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths))
    return stats, queries, docs


def _scorers():
    return [__import__("repro.core.scoring", fromlist=["x"]).get_scorer("bm25")]


def _run_job(collection, tmp_path, **kw):
    stats, queries, docs = collection
    return cluster.run_sharded_scan_job(
        queries, docs, _scorers(), k=K, chunk_size=CHUNK, segment_chunks=2,
        n_shards=N_SHARDS, stats=stats, ckpt_dir=str(tmp_path / "ckpt"), **kw,
    )


def test_sharded_job_emits_spans_per_shard(collection, tmp_path):
    with obs.session() as (tr, met):
        _run_job(collection, tmp_path)
    for shard in range(N_SHARDS):
        folds = [s for s in tr.spans("segment.fold") if s.attrs["shard"] == shard]
        assert len(folds) == 2  # 2 segments per shard
        assert [s.attrs["segment"] for s in folds] == [0, 1]
        assert any(s.attrs["shard"] == shard for s in tr.spans("shard.run"))
        assert any(
            s.attrs["shard"] == shard for s in tr.spans("segment.commit_submit")
        )
        assert any(
            s.attrs["shard"] == shard for s in tr.spans("segment.prefetch_wait")
        )
    attempts = tr.spans("shard.attempt")
    assert {s.attrs["outcome"] for s in attempts} == {"ok"}
    # checkpoint commits happen on the writer thread, visible as its spans
    assert all(s.tname == "ckpt-writer" for s in tr.spans("ckpt.save"))
    # one fetch (the first wait on a segment's fold) per committed segment
    fetches = tr.spans("ckpt.fetch")
    assert sorted((s.attrs["shard"], s.attrs["step"]) for s in fetches) == [
        (shard, step) for shard in range(N_SHARDS) for step in (1, 2)
    ]
    assert met.summary()["histograms"]["ckpt.save_s"]["count"] >= 2 * N_SHARDS


def test_crashed_fold_attempt_leaves_error_span_and_fault_marker(
    collection, tmp_path
):
    faults = FaultSchedule(
        [FaultSpec(kind="crash", shard=1, segment=1, phase="pre_commit")]
    )
    with obs.session() as (tr, _):
        _run_job(collection, tmp_path, faults=faults, max_retries=1)
    failed = [s for s in tr.spans("shard.attempt") if s.attrs["outcome"] == "failed"]
    assert len(failed) == 1 and failed[0].attrs["shard"] == 1
    # the doomed attempt's shard.run span carries the crash type
    died = [s for s in tr.spans("shard.run") if "error" in s.attrs]
    assert len(died) == 1 and died[0].attrs["error"] == "WorkerCrash"
    (crash,) = tr.instants("fault.crash")
    assert crash.attrs["shard"] == 1 and crash.attrs["segment"] == 1
    (retry,) = tr.instants("sched.retry")
    assert retry.attrs["shard"] == 1 and retry.attrs["error"] == "WorkerCrash"


def test_scheduler_stats_consistent_under_concurrent_chaos(collection, tmp_path):
    """SchedulerStats counters are mutated from every worker thread; the
    final numbers must reconcile exactly with the injected schedule and
    the trace's own event log."""
    n_shards = 8
    stats, queries, docs = collection
    faults = FaultSchedule(
        [
            FaultSpec(kind="crash", shard=s, segment=0, phase="post_commit")
            for s in range(0, n_shards, 2)
        ]
    )
    with obs.session() as (tr, _):
        job = cluster.run_sharded_scan_job(
            queries, docs, _scorers(), k=K, chunk_size=CHUNK, segment_chunks=1,
            n_shards=n_shards, stats=stats, ckpt_dir=str(tmp_path / "c8"),
            faults=faults, max_retries=1, max_workers=4,
        )
    s = job.scheduler
    assert s.retries == n_shards // 2 == len(tr.instants("sched.retry"))
    assert sum(s.attempts) == n_shards + s.retries + s.speculative_launched
    by_outcome = {}
    for sp in tr.spans("shard.attempt"):
        by_outcome[sp.attrs["outcome"]] = by_outcome.get(sp.attrs["outcome"], 0) + 1
    assert by_outcome.get("failed", 0) == s.retries
    assert by_outcome.get("ok", 0) == n_shards
    assert len(tr.instants("sched.steal")) == s.steals


# -- byte identity ------------------------------------------------------------


def test_traced_run_files_byte_identical_to_untraced(tmp_path):
    spec = exp_grid.ExperimentSpec(
        name="obs-id", grids=(exp_grid.GridSpec("bm25"),),
        n_docs=N_DOCS, n_queries=4, vocab=VOCAB, max_doc_len=24,
        k=K, chunk_size=CHUNK, segment_chunks=2, n_shards=N_SHARDS,
    )
    coll = runner.prepare_collection(spec, seed=3)
    faults = lambda: FaultSchedule(  # noqa: E731 — fresh per run
        [FaultSpec(kind="crash", shard=0, segment=0, phase="post_commit")]
    )
    plain = runner.run_experiment(
        spec, out_dir=str(tmp_path / "plain"), seed=3, collection=coll,
        faults=faults(), max_retries=1,
    )
    trace_path = tmp_path / "obs" / "trace.json"
    traced = runner.run_experiment(
        spec, out_dir=str(tmp_path / "traced"), seed=3, collection=coll,
        faults=faults(), max_retries=1, trace_out=str(trace_path),
    )
    # tracing observed a faulted, retried run...
    ob = traced["job"]["obs"]
    assert ob["n_events"] > 0 and plain["job"]["obs"] is None
    doc = json.load(open(trace_path))
    folds = [e for e in doc["traceEvents"] if e["name"] == "segment.fold"]
    assert {e["args"]["shard"] for e in folds} == set(range(N_SHARDS))
    fetches = [e for e in doc["traceEvents"] if e["name"] == "ckpt.fetch"]
    assert len(fetches) >= 4  # one per committed segment, retries included
    assert {e["args"]["shard"] for e in fetches} == set(range(N_SHARDS))
    assert "shard 0" in ob["phases"]
    assert trace_path.with_suffix(".jsonl").exists()
    # ...and never perturbed the artifacts
    runs = sorted((tmp_path / "plain" / "runs").iterdir())
    assert runs
    for p in runs:
        q = tmp_path / "traced" / "runs" / p.name
        assert p.read_bytes() == q.read_bytes()
    # the lifecycle restored the ambient (disabled) instruments
    assert not obs.tracer().enabled


# -- serve histograms ---------------------------------------------------------


def test_serve_dispatch_populates_histograms_and_request_spans():
    corpus = synthetic.make_corpus(n_docs=128, vocab=256, max_len=24, seed=0)
    session = LexicalSession(
        corpus.tokens, corpus.lengths, "ql_lm", k=5, chunk_size=64, vocab=256
    )
    clock_t = [0.0]

    def clock():
        clock_t[0] += 0.001
        return clock_t[0]

    registry = Metrics()
    service = RetrievalService(
        {"lexical": session}, max_batch=4, max_delay=0.5, clock=clock,
        registry=registry,
    )
    with obs.session() as (tr, _):
        queries = synthetic.make_queries(corpus, n_queries=10, seed=1)
        rids = [service.submit(q, "lexical") for q in queries]
        results = service.poll()
        results.update(service.drain())
    assert sorted(results) == sorted(rids)
    s = registry.summary()
    assert s["counters"]["serve.requests"] == 10
    assert s["counters"]["serve.batches"] == 3  # 4 + 4 + flush(2)
    bs = s["histograms"]["serve.batch_size"]
    assert bs["count"] == 3 and bs["max"] == 4.0 and bs["min"] == 2.0
    for name in ("serve.queue_wait_s", "serve.latency_s"):
        h = s["histograms"][name]
        assert h["count"] == 3
        assert 0 < h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    # one enqueue→reply span per request, plus one dispatch span per block
    reqs = tr.spans("serve.request")
    assert sorted(e.attrs["rid"] for e in reqs) == sorted(rids)
    assert all(e.dur > 0 for e in reqs)
    dispatches = tr.spans("serve.dispatch")
    assert [d.attrs["n_real"] for d in dispatches] == [4, 4, 2]
    assert {d.attrs["trigger"] for d in dispatches} == {"size", "flush"}


class _StubSession:
    """A session with no device work: zero scores for every padded row."""

    pad_value = 0

    def search(self, q):
        n = q.shape[0]
        return SimpleNamespace(
            scores=np.zeros((n, 2), np.float32), ids=np.zeros((n, 2), np.int32)
        )


def test_serve_request_carries_block_and_batcher_wait_and_ends_after_reply():
    clock = StepClock(dt=0.25)  # one clock for the service and the tracer
    tr = Tracer(clock=clock)
    service = RetrievalService(
        {"stub": _StubSession()}, max_batch=2, max_delay=100.0, min_bucket=2,
        clock=clock, registry=Metrics(),
    )
    with obs.session(tr):
        rids = [service.submit(np.ones(3, np.int32), "stub") for _ in range(3)]
        service.poll()  # the size trigger closes block 0 (two requests)
        service.drain()  # the flush closes block 1
    dispatch = {s.attrs["block"]: s for s in tr.spans("serve.dispatch")}
    reply = {s.attrs["block"]: s for s in tr.spans("serve.reply")}
    assert sorted(dispatch) == sorted(reply) == [0, 1]
    reqs = sorted(tr.spans("serve.request"), key=lambda s: s.attrs["rid"])
    assert [r.attrs["rid"] for r in reqs] == rids
    assert [r.attrs["block"] for r in reqs] == [0, 0, 1]
    for r in reqs:
        b = r.attrs["block"]
        closed = r.ts + r.attrs["queued_s"]  # admission + wait = block close
        assert r.attrs["queued_s"] > 0 and closed <= dispatch[b].ts
        assert r.ts + r.dur >= reply[b].ts + reply[b].dur
        assert dispatch[b].ts + dispatch[b].dur <= reply[b].ts
    # the oldest request of a block waited the block's own queue wait
    assert reqs[0].attrs["queued_s"] == pytest.approx(service.metrics[0].queue_wait_s)
    assert reqs[2].attrs["queued_s"] == pytest.approx(service.metrics[1].queue_wait_s)
    assert reqs[0].ts + reqs[0].attrs["queued_s"] == pytest.approx(
        reqs[1].ts + reqs[1].attrs["queued_s"]
    )


def test_disabled_tracer_records_nothing_on_the_instrumented_paths(tmp_path):
    obs.watch_compiles()  # the listener is live, and must stay silent
    tr = Tracer(enabled=False)
    service = RetrievalService(
        {"stub": _StubSession()}, max_batch=2, min_bucket=2, registry=Metrics()
    )
    with obs.session(tr):
        service.submit(np.ones(3, np.int32), "stub")
        service.drain()
        ckpt.save(str(tmp_path), 1, {"x": jnp.arange(5)})
        jax.jit(lambda x: x - 2)(np.arange(3)).block_until_ready()
    assert len(tr) == 0


def test_checkpoint_fetch_and_write_nest_in_save_on_the_writer_thread(
    collection, tmp_path
):
    with obs.session() as (tr, _):
        _run_job(collection, tmp_path)
    saves = {(s.attrs["shard"], s.attrs["step"]): s for s in tr.spans("ckpt.save")}
    committed = [(shard, step) for shard in range(N_SHARDS) for step in (1, 2)]
    assert sorted(saves) == committed
    for name in ("ckpt.fetch", "ckpt.write"):
        inner = tr.spans(name)
        assert sorted((s.attrs["shard"], s.attrs["step"]) for s in inner) == committed
        for s in inner:
            outer = saves[s.attrs["shard"], s.attrs["step"]]
            assert s.tname == "ckpt-writer" and s.tid == outer.tid
            assert outer.ts <= s.ts and s.ts + s.dur <= outer.ts + outer.dur
    fetch = {(s.attrs["shard"], s.attrs["step"]): s for s in tr.spans("ckpt.fetch")}
    for s in tr.spans("ckpt.write"):
        f = fetch[s.attrs["shard"], s.attrs["step"]]
        assert f.ts + f.dur <= s.ts  # the leaves are on the host before the files
        assert isinstance(f.attrs["queued"], int) and f.attrs["queued"] >= 0


EVAL_SPEC = exp_grid.ExperimentSpec(
    name="obs-eval", grids=(exp_grid.GridSpec("bm25"), exp_grid.GridSpec("ql_lm")),
    n_docs=N_DOCS, n_queries=4, vocab=VOCAB, max_doc_len=24,
    k=K, chunk_size=CHUNK, segment_chunks=2,
)


def test_traced_experiment_names_each_eval_measure_inside_experiment_eval(tmp_path):
    spec = EVAL_SPEC
    coll = runner.prepare_collection(spec, seed=3)
    with obs.session() as (tr, _):
        for i in range(2):
            runner.run_experiment(
                spec, out_dir=str(tmp_path / f"e{i}"), seed=3, collection=coll
            )
    evs = tr.spans("experiment.eval")
    assert len(evs) == 2
    parts = [s for s in tr.spans() if s.name.startswith("eval.")]
    ks = [c for c in spec.eval_ks if c <= K] or [K]
    assert collections.Counter(s.name for s in parts) == {
        "eval.judgments": 2, "eval.ap": 4, "eval.rr": 4, "eval.p": 4 * len(ks),
        "eval.recall": 4 * len(ks), "eval.ndcg": 4 * len(ks), "eval.significance": 2,
    }
    assert sorted(s.attrs["k"] for s in parts if s.name == "eval.ndcg") == sorted(ks * 4)
    for ev in evs:  # one pass over the qrels per experiment, before any measure
        inside = [s for s in parts if ev.ts <= s.ts and s.ts + s.dur <= ev.ts + ev.dur]
        assert len(inside) == len(parts) // 2 and all(s.tid == ev.tid for s in inside)
        (jd,) = [s for s in inside if s.name == "eval.judgments"]
        assert jd.attrs == {
            "n_judged": np.count_nonzero(coll.qrels), "n_docs": spec.n_docs
        }
        assert all(jd.ts + jd.dur <= s.ts for s in inside if s is not jd)


def test_report_metrics_and_significance_equal_the_dense_reference(tmp_path):
    spec = EVAL_SPEC
    coll = runner.prepare_collection(spec, seed=5)
    report = runner.run_experiment(
        spec, out_dir=str(tmp_path / "e"), seed=5, collection=coll
    )
    with open(tmp_path / "e" / "report.json") as f:
        written = json.load(f)
    ks = tuple(c for c in spec.eval_ks if c <= K) or (K,)
    per_query_ap = {}
    for name, path in report["runs"].items():
        ids, _, _ = trec.read_run(path, depth=K)
        want = eval_ref.evaluate(ids, coll.qrels, ks)
        assert written["metrics"][name] == want["aggregate"]
        per_query_ap[name] = want["per_query"]["ap"]
    base = written["baseline"]
    want_sig = {}
    for name, ap in per_query_ap.items():
        if name != base:
            res = paired_randomization_test(ap, per_query_ap[base], seed=5)
            want_sig[name] = {
                "vs": base, "metric": "ap", "diff": res.diff, "p_value": res.p_value
            }
    assert written["significance"] == want_sig and want_sig


def test_compile_listener_records_each_new_executable_once():
    obs.watch_compiles()
    obs.watch_compiles()  # idempotent: a second listener would double each span

    def add_seven(x):
        return x + 7

    f = jax.jit(add_seven)
    x = np.arange(11, dtype=np.float32)
    with obs.session() as (tr, _):
        f(x).block_until_ready()
        first = len(tr)
        f(x).block_until_ready()  # cached: nothing compiles
        assert len(tr) == first
    assert len(tr.instants("jit.watch")) == 1
    (comp,) = tr.spans("jit.compile")
    assert "add_seven" in comp.attrs["fun"] and comp.cat == "jit"
    assert len(tr.spans("jit.cache_load")) <= 1  # a persistent-cache hit, if any
    assert tr.spans("jit.trace") and tr.spans("jit.lower")
    assert all(s.dur >= 0 for s in tr.spans())


# -- deprecation alias origin (satellite) -------------------------------------


def _one_shard_kwargs(collection):
    stats, queries, docs = collection
    return dict(
        queries=queries, docs=docs, scorers=_scorers(), k=K, chunk_size=CHUNK,
        segment_chunks=2, stats=stats,
    )


def test_legacy_warning_points_at_caller_run_scan_job(collection):
    kw = _one_shard_kwargs(collection)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with pytest.raises(RuntimeError, match="injected failure"):
            cluster.run_scan_job(
                kw["queries"], kw["docs"], kw["scorers"], k=K, chunk_size=CHUNK,
                segment_chunks=2, stats=kw["stats"], fail_at_segment=0,
            )
    (w,) = [w for w in caught if w.category is DeprecationWarning]
    assert w.filename == __file__  # stacklevel=2: the caller's line, not job.py


def test_legacy_warning_points_at_caller_run_sharded(collection):
    kw = _one_shard_kwargs(collection)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with pytest.raises(RuntimeError, match="injected failure"):
            cluster.run_sharded_scan_job(
                kw["queries"], kw["docs"], kw["scorers"], k=K, chunk_size=CHUNK,
                segment_chunks=2, stats=kw["stats"], n_shards=2,
                fail_at_segment=0, fail_at_shard=1,
            )
    (w,) = [w for w in caught if w.category is DeprecationWarning]
    assert w.filename == __file__


def test_legacy_warning_points_at_caller_run_experiment(tmp_path):
    """run_experiment converts the legacy kwargs itself instead of
    forwarding them, so the warning is attributed to the experiment's
    caller rather than to runner.py's internal job call."""
    spec = exp_grid.ExperimentSpec(
        name="obs-dep", grids=(exp_grid.GridSpec("bm25"),),
        n_docs=N_DOCS, n_queries=4, vocab=VOCAB, max_doc_len=24,
        k=K, chunk_size=CHUNK, segment_chunks=2, n_shards=2,
    )
    coll = runner.prepare_collection(spec, seed=3)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        report = runner.run_experiment(
            spec, out_dir=str(tmp_path / "dep"), seed=3, collection=coll,
            fail_at_segment=0, fail_at_shard=0, max_retries=1,
        )
    deps = [w for w in caught if w.category is DeprecationWarning]
    assert deps and all(w.filename == __file__ for w in deps)
    # the alias reached the job as a real FaultSpec: it fired and was retried
    assert [f["kind"] for f in report["job"]["faults_fired"]] == ["crash"]
    assert report["job"]["scheduler"]["retries"] == 1


# -- windowed (recent-decay) histograms --------------------------------------


class ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_windowed_histogram_forgets_old_samples():
    clock = ManualClock()
    h = Histogram("h", bounds=(1.0, 10.0), window_s=1.0, n_windows=4, clock=clock)
    h.observe(100.0)  # lands in the current sub-window
    assert h.count == 1 and h.quantile(0.99) == 100.0
    clock.t = 0.9  # still inside the ring
    h.observe(0.5)
    assert h.count == 2
    clock.t = 1.3  # first sub-window (0.0-0.25) rotated out -> 100.0 gone
    assert h.count == 1
    assert h.quantile(0.99) == pytest.approx(0.5)
    clock.t = 5.0  # a gap longer than the whole window clears everything
    assert h.count == 0
    assert h.summary()["window_s"] == 1.0


def test_windowed_histogram_rotation_edges():
    clock = ManualClock()
    h = Histogram("h", bounds=(1.0,), window_s=1.0, n_windows=4, clock=clock)
    # one sample per sub-window boundary; each rotation drops exactly one
    for i in range(4):
        clock.t = i * 0.25
        h.observe(float(i))
    assert h.count == 4
    clock.t = 1.0  # rotates out the [0, 0.25) sub-window only
    assert h.count == 3
    clock.t = 1.25
    assert h.count == 2
    # min/max/quantiles come from the merged live sub-windows
    assert h.summary()["max"] == 3.0 and h.summary()["min"] == 2.0


def test_windowed_histogram_tolerates_clock_rewind():
    """Arrival stamping in the open-loop load generator rewinds the service
    clock; a rewound read must not rotate (or crash) — it observes into the
    current sub-window."""
    clock = ManualClock(5.0)
    h = Histogram("h", bounds=(1.0,), window_s=2.0, n_windows=4, clock=clock)
    h.observe(1.0)
    clock.t = 3.0  # rewind
    h.observe(2.0)
    assert h.count == 2
    clock.t = 5.4  # forward again, still same sub-window (0.5s each)
    assert h.count == 2


def test_cumulative_histogram_unchanged_by_default():
    h = Histogram("h", bounds=(1.0, 2.0))
    for v in (0.5, 1.5, 3.0):
        h.observe(v)
    assert h.count == 3 and h.summary().get("window_s") is None


def test_registry_creates_windowed_histogram_once():
    clock = ManualClock()
    m = Metrics()
    h1 = m.histogram("serve.recent", window_s=1.0, n_windows=2, clock=clock)
    h2 = m.histogram("serve.recent")  # get: kwargs only apply at creation
    assert h1 is h2 and h1.window_s == 1.0
    h1.observe(1.0)
    clock.t = 3.0
    assert h2.count == 0  # decayed through the shared instance
