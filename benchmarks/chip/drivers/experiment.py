"""Batch experiments back to back through ``runner.run_experiment``.

Set-up makes the corpus on the device from the seed, runs the system's own
statistics job on it (the prepare stage), copies the corpus to the host,
from where the experiment streams it as the system does, draws the queries
and graded qrels from the seed, and runs one whole experiment to compile
and warm every program. The window then runs whole experiments (scan, run
files, eval), each in a fresh directory with ``resume=False``, and closes at
the first experiment end after ``--seconds``.

``correct``: every experiment's run files equal the first's byte for byte,
and the first's are compared, model by model, with the plain lexical
reference (``references/lexical.py``).
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from chipbench import compare, data
from chipbench.harness import Check, Run, annotate
from repro import obs
from repro.core import anchors
from repro.data.synthetic import Corpus
from repro.experiments import runner
from repro.experiments.grid import ExperimentSpec, GridSpec
from repro.tune import TuningConfig


@dataclasses.dataclass
class State:
    spec: ExperimentSpec
    coll: runner.Collection
    tokens: np.ndarray
    lengths: np.ndarray
    queries: np.ndarray
    reports: list = dataclasses.field(default_factory=list)


def read_run_file(path: str, n_q: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """A TREC run file as ``(ids, scores) [n_q, k]``; absent ranks read
    ``(-1, -inf)``."""
    ids = np.full((n_q, k), -1, np.int64)
    scores = np.full((n_q, k), -np.inf)
    with open(path) as f:
        for line in f:
            qid, _, did, rank, score, _ = line.split()
            ids[int(qid[1:]), int(rank) - 1] = int(did[1:])
            scores[int(qid[1:]), int(rank) - 1] = float(score)
    return ids, scores


def _experiment(run: Run, st: State, out: Path) -> dict:
    return runner.run_experiment(
        st.spec, out_dir=str(out), seed=run.seed, resume=False, collection=st.coll,
        tuning=TuningConfig(),
    )


def setup(run: Run) -> State:
    cfg = run.config
    n_docs, (min_len, pad) = cfg["n_docs"], cfg["doc_len"]
    tok_d, len_d = data.corpus(
        run.seed, n_docs=n_docs, pad=pad, min_len=min_len, vocab=cfg["vocab"],
        alpha=cfg["zipf_alpha"],
    )
    stats = anchors.collection_stats(
        tok_d, len_d, vocab=cfg["vocab"], chunk_size=min(cfg["chunk_size"], n_docs)
    )
    tokens, lengths = np.asarray(tok_d), np.asarray(len_d)
    del tok_d, len_d
    rng = data.rng_of(run.seed, 3)
    counts = data.term_counts(cfg["n_queries"], *cfg["query_terms"], rng)
    queries = data.lexical_queries(tokens, lengths, counts, cfg["query_slots"], rng)
    qrels = data.graded_qrels(cfg["n_queries"], n_docs, cfg["qrels_per_query"], rng)
    spec = ExperimentSpec(
        name=cfg["name"],
        grids=tuple(GridSpec(p["base"]) for p in cfg["models"].values()),
        n_docs=n_docs, n_queries=cfg["n_queries"], vocab=cfg["vocab"],
        max_doc_len=pad, max_q_len=cfg["query_slots"], k=cfg["k"],
        chunk_size=cfg["chunk_size"], segment_chunks=cfg["segment_chunks"],
        n_shards=1, use_kernel=cfg["use_kernel"], baseline=cfg["baseline"],
    )
    coll = runner.Collection(
        corpus=Corpus(tokens=tokens, lengths=lengths), stats=stats, queries=queries,
        qrels=qrels,
    )
    st = State(spec=spec, coll=coll, tokens=tokens, lengths=lengths, queries=queries)
    _experiment(run, st, run.workdir / "warm")
    return st


def window(run: Run, st: State) -> None:
    tracer = obs.Tracer(max_events=1_000_000) if run.trace else None
    prev = obs.install(tracer) if tracer is not None else None
    times = []  # (start, end) of each experiment, monotonic seconds
    try:
        t_start = time.monotonic()
        run.window_start = t_start
        while True:
            if not times:
                run.profiler.start()  # trace runs: the first experiment
            t0 = time.monotonic()
            with annotate(run, "chipbench.experiment"):
                st.reports.append(_experiment(run, st, run.workdir / f"exp{len(times)}"))
            t1 = time.monotonic()
            run.profiler.stop()
            times.append((t0, t1))
            if t1 - t_start >= run.seconds:
                break
    finally:
        if prev is not None:
            obs.install(*prev)
            run.spans = tracer.events()
    run.records["experiments"] = times
    run.records["docs_per_experiment"] = st.spec.n_docs
    run.attempted = len(times)
    run.note(f"experiments in the window: {len(times)} of {st.spec.n_docs} documents")


def finish(run: Run, st: State, control: bool = False) -> list[Check]:
    cfg = run.config
    ref_mod = run.layout.module("references", "lexical")
    k, n_docs, models = cfg["k"], cfg["n_docs"], cfg["models"]
    first = st.reports[0]["runs"]
    differ = 0
    for rep in st.reports[1:]:
        for name, path in rep["runs"].items():
            differ += Path(path).read_bytes() != Path(first[name]).read_bytes()
    checks = [Check("runs_differ", float(differ), 0.0)]
    st.coll = None  # the system's state goes before the reference runs
    tokens_dev, lengths_dev = jnp.asarray(st.tokens), jnp.asarray(st.lengths)
    ref = ref_mod.LexicalReference(
        st.tokens, st.lengths, tokens_dev, lengths_dev, st.queries, models
    )
    cand = cfg["compare"]["candidates"]
    answers = {"": {m: read_run_file(first[m], len(st.queries), k) for m in models}}
    if control:
        answers["control."] = {
            m: ref.ranked(m, k, dtype=jnp.bfloat16) for m in models
        }
    best = {m: ref.best(m, k, cand) for m in models}
    for prefix, per_model in answers.items():
        bad = 0
        for m, (ids, scores) in per_model.items():
            bad += compare.bad_ids(ids, n_docs)
            of = ref.scores_of(m, np.clip(ids, 0, n_docs - 1))
            score_gap, rank_gap = compare.gaps(ids, scores, of, best[m])
            lim = cfg["limits"][m]
            checks.append(Check(f"{prefix}{m}.score_gap", score_gap, lim["score_gap"]))
            checks.append(Check(f"{prefix}{m}.rank_gap", rank_gap, lim["rank_gap"]))
        checks.append(Check(f"{prefix}bad_ids", float(bad), 0.0))
    return checks
