"""Open-loop requests through ``RetrievalService``, on the wall clock.

Set-up makes the corpus (tokens and dense vectors) on the device from the
seed, builds the deployment's resident sessions (the lexical session runs
the system's statistics job over its corpus) behind a ``RetrievalService``
with the default ``TuningConfig`` and no admission controller or adaptive
policy, draws the window's requests and due times from the seed, and warms
every bucket shape of the cell's own kind.

The window is one thread: it submits each request when it falls due
(``try_submit``), dispatches one block per ``poll(limit=1)`` and sleeps to
the next due time or microbatch deadline. A request is timed from when it
was due, not from when it was submitted. After ``--seconds`` nothing more is
offered and the queue drains.

``correct``: a sample of the answered requests drawn from the seed (with a
longest query in it) is compared with the plain reference of its kind.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, data, schedules
from chipbench.harness import Check, Run, annotate
from repro import obs
from repro.serve import RetrievalService
from repro.serve.microbatch import bucket_size
from repro.serve.session import DenseSession, LexicalSession
from repro.tune import TuningConfig


@dataclasses.dataclass
class State:
    service: RetrievalService | None
    kind: str
    tokens: np.ndarray
    lengths: np.ndarray
    vectors: jax.Array | None
    due: np.ndarray
    queries: np.ndarray
    terms: np.ndarray | None
    results: dict = dataclasses.field(default_factory=dict)


def bucket_ladder(tuning: TuningConfig) -> list[int]:
    """Every padded block size the microbatcher can close."""
    cap = tuning.serve_max_batch
    if tuning.serve_max_bucket is not None:
        cap = min(cap, tuning.serve_max_bucket)
    return sorted(
        {
            bucket_size(n, min_bucket=tuning.serve_min_bucket,
                        max_bucket=tuning.serve_max_bucket)
            for n in range(1, cap + 1)
        }
    )


def build(run: Run) -> State:
    """The deployment and the window's requests, without warming."""
    cfg, traffic = run.config, run.traffic
    if "matmul_precision" in cfg:  # the precision the deployment's scores are stated in
        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    n_docs, (min_len, pad) = cfg["n_docs"], cfg["doc_len"]
    tok_d, len_d = data.corpus(
        run.seed, n_docs=n_docs, pad=pad, min_len=min_len, vocab=cfg["vocab"],
        alpha=cfg["zipf_alpha"],
    )
    tokens, lengths = np.asarray(tok_d), np.asarray(len_d)
    del tok_d, len_d
    vectors = data.vectors(run.seed, n=n_docs, dim=cfg["dim"])
    sessions = {
        "lexical": LexicalSession(
            tokens, lengths, cfg["lexical_model"], k=cfg["k"],
            chunk_size=cfg["chunk_size"], vocab=cfg["vocab"], use_kernel=None,
        ),
        "dense": DenseSession(
            vectors, k=cfg["k"], chunk_size=cfg["chunk_size"], use_kernel=True
        ),
    }
    service = RetrievalService(sessions, tuning=TuningConfig())
    rng = data.rng_of(run.seed, 5)
    due = schedules.arrivals(traffic, run.seconds, rng)
    kind = traffic["kind"]
    terms = None
    if kind == "lexical":
        terms = data.term_counts(len(due), *cfg["query_terms"], rng)
        queries = data.lexical_queries(tokens, lengths, terms, cfg["query_slots"], rng)
    else:
        queries = data.dense_queries(len(due), cfg["dim"], rng)
    return State(
        service=service, kind=kind, tokens=tokens, lengths=lengths,
        vectors=vectors, due=due, queries=queries, terms=terms,
    )


def warm(st: State) -> None:
    """Compile and run every bucket shape of the cell's kind, then one
    request through the whole service path."""
    session = st.service.sessions[st.kind]
    for b in bucket_ladder(TuningConfig()):
        block = np.resize(st.queries, (b, *st.queries.shape[1:]))
        session.search(block)
    st.service.submit(st.queries[0], st.kind)
    st.service.drain()
    st.service.metrics.clear()


def setup(run: Run) -> State:
    st = build(run)
    warm(st)
    return st


def serve_window(run: Run, st: State) -> dict:
    """Offer ``st.queries`` at ``st.due`` (seconds after the window opens)
    and drain; returns the per-request and per-block records."""
    svc, due, queries = st.service, st.due, st.queries
    n = len(due)
    submit = np.full(n, np.nan)
    reply = np.full(n, np.nan)
    block_of = np.full(n, -1, np.int64)
    blocks = []  # (reply time, dispatch seconds, real rows, padded rows)
    rid_to_i = {}
    prof_from, prof_len = run.seconds / 3, min(3.0, run.seconds / 3)
    clock = time.monotonic
    t0 = clock()
    run.window_start = t0
    i = 0
    while i < n or svc.pending():
        now = clock() - t0
        if run.trace:
            if not run.profiler.running and run.profiler.mono_t0 is None and now >= prof_from:
                run.profiler.start()
            elif run.profiler.running and now >= prof_from + prof_len:
                run.profiler.stop()
        with annotate(run, "chipbench.submit"):
            while i < n and due[i] <= now:
                out = svc.try_submit(queries[i], st.kind)
                rid_to_i[out.rid] = i
                submit[i] = now
                i += 1
        with annotate(run, "chipbench.poll"):
            got = svc.poll(limit=1)
        if got:
            t = clock() - t0
            rec = svc.metrics[-1]
            blocks.append((t, rec.latency_s, rec.n_real, rec.n_padded))
            for rid, res in got.items():
                j = rid_to_i.pop(rid)
                reply[j] = t
                block_of[j] = len(blocks) - 1
                st.results[j] = res
            continue
        nxt = due[i] if i < n else math.inf
        deadline = svc.next_deadline()
        if deadline is not None:
            nxt = min(nxt, deadline - t0)
        wait = nxt - (clock() - t0)
        if wait > 0:
            with annotate(run, "chipbench.wait"):
                time.sleep(wait)
    run.profiler.stop()
    return {
        "due": due, "submit": submit, "reply": reply, "block_of": block_of,
        "blocks": np.asarray(blocks, np.float64).reshape(-1, 4),
    }


def window(run: Run, st: State) -> None:
    tracer = obs.Tracer(max_events=4_000_000) if run.trace else None
    prev = obs.install(tracer) if tracer is not None else None
    try:
        rec = serve_window(run, st)
    finally:
        if prev is not None:
            obs.install(*prev)
            run.spans = tracer.events()
            run.records["timeline"] = [
                (s.name, s.ts, s.ts + s.dur) for s in run.spans if s.name != "serve.request"
            ]
    run.records.update(rec)
    answered = np.isfinite(rec["reply"])
    run.attempted = len(rec["due"])
    run.failed = int((~answered).sum())
    late = np.nanmax(rec["submit"] - rec["due"]) * 1e3
    slowest = rec["blocks"][:, 1].max() * 1e3
    run.note(
        f"offered {len(rec['due'])} requests over {run.seconds:g} s; generator "
        f"lateness max {late:.3f} ms; blocks {len(rec['blocks'])}, slowest {slowest:.3f} ms"
    )


def sample(run: Run, st: State) -> np.ndarray:
    """The compared requests: drawn from the seed among those answered,
    with one of the longest queries first."""
    answered = np.array(sorted(st.results))
    rng = data.rng_of(run.seed, 7)
    size = min(run.config["compare"]["sample_requests"], len(answered))
    picked = rng.permutation(answered)[:size]
    if st.terms is not None:
        longest = answered[np.argmax(st.terms[answered])]
        if longest not in picked:
            picked[-1] = longest
    return np.sort(picked)


def finish(run: Run, st: State, control: bool = False) -> list[Check]:
    cfg = run.config
    k, n_docs = cfg["k"], cfg["n_docs"]
    picked = sample(run, st)
    ids = np.stack([st.results[j].ids for j in picked]).astype(np.int64)
    scores = np.stack([st.results[j].scores for j in picked])
    st.service = None  # the system's state goes before the reference runs
    q = st.queries[picked]
    if st.kind == "lexical":
        st.vectors = None
        model = cfg["lexical_model"]
        ref = run.layout.module("references", "lexical").LexicalReference(
            st.tokens, st.lengths, jnp.asarray(st.tokens), jnp.asarray(st.lengths), q,
            {model: cfg["models"][model]},
        )
        best = ref.best(model, k, cfg["compare"]["candidates"])

        def ref_of(x):
            return ref.scores_of(model, x)

        def lower():
            return ref.ranked(model, k, dtype=jnp.bfloat16)
    else:
        ref = run.layout.module("references", "dense").DenseReference(st.vectors, q)
        best = ref.best(k)
        ref_of = ref.scores_of

        def lower():
            return ref.ranked(k, dtype=jnp.bfloat16)

    answers = {"": (ids, scores)}
    if control:
        answers["control."] = lower()
    lim = cfg["limits"][st.kind]
    checks = []
    for prefix, (a_ids, a_scores) in answers.items():
        of = ref_of(np.clip(a_ids, 0, n_docs - 1))
        score_gap, rank_gap = compare.gaps(a_ids, a_scores, of, best)
        checks += [
            Check(f"{prefix}bad_ids", float(compare.bad_ids(a_ids, n_docs)), 0.0),
            Check(f"{prefix}score_gap", score_gap, lim["score_gap"]),
            Check(f"{prefix}rank_gap", rank_gap, lim["rank_gap"]),
        ]
    run.note(f"compared {len(picked)} requests of {len(st.results)} answered")
    return checks
