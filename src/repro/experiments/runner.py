"""Experiment orchestration: prepare → scan job → run files → eval report.

One call runs the whole MIREX experiment lifecycle for a declared grid:

  1. **prepare** — deterministic synthetic collection + collection-statistics
     job (the paper's preprocessing MapReduce) + queries + graded qrels;
  2. **scan** — one resumable multi-scorer corpus pass
     (`job.run_scan_job`): every grid point shares the corpus stream;
  3. **report** — per-model TREC run files, the `repro.eval` report card
     (MAP / P@k / NDCG / MRR / recall), and paired-randomization
     significance of every variant against the declared baseline.

Everything is keyed by ``seed``, so a re-run (or a kill/resume, see
`job.py`) regenerates byte-identical artifacts.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, tune
from repro.cluster import FaultSchedule, plan_shards, run_sharded_scan_job
from repro.core import anchors, packing, topk
from repro.data import synthetic
from repro.eval import evaluate_run, judgments, paired_randomization_test, trec
from repro.experiments.grid import ExperimentSpec
from repro.tune import TuningConfig


@dataclasses.dataclass(frozen=True)
class Collection:
    corpus: synthetic.Corpus
    stats: Any  # CollectionStats of jnp arrays
    queries: np.ndarray
    qrels: np.ndarray  # graded [n_q, n_docs] int8


def prepare_collection(spec: ExperimentSpec, *, seed: int = 0) -> Collection:
    """The prepare stage: corpus, stats job, queries, graded qrels."""
    corpus = synthetic.make_corpus(
        n_docs=spec.n_docs, vocab=spec.vocab, max_len=spec.max_doc_len, seed=seed
    )
    stats = anchors.collection_stats(
        jnp.asarray(corpus.tokens),
        jnp.asarray(corpus.lengths),
        vocab=spec.vocab,
        chunk_size=min(spec.chunk_size, spec.n_docs),
    )
    queries = synthetic.make_queries(
        corpus, n_queries=spec.n_queries, max_q_len=spec.max_q_len, seed=seed + 1
    )
    qrels = synthetic.make_graded_qrels(corpus, queries, per_query=25, seed=seed + 2)
    return Collection(corpus=corpus, stats=stats, queries=queries, qrels=qrels)


def _device_of(x) -> str | None:
    """``platform:id`` of the device holding a shard's final state (None
    for a host array: a resumed shard that had nothing left to fold)."""
    if not isinstance(x, jax.Array):
        return None
    (dev,) = x.devices()
    return f"{dev.platform}:{dev.id}"


def run_filename(variant: str) -> str:
    """Filesystem-safe run-file name for a scorer variant."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", variant).strip("_") + ".run"


def write_run_files(
    out_dir: str, scorers, state: topk.TopKState, *, tag_prefix: str
) -> dict[str, str]:
    """One TREC run file per model from the stacked job state."""
    os.makedirs(out_dir, exist_ok=True)
    valid = np.asarray(topk.valid_mask(state))
    ids = np.asarray(state.ids)
    scores = np.asarray(state.scores)
    paths = {}
    for m, s in enumerate(scorers):
        path = os.path.join(out_dir, run_filename(s.name))
        trec.write_run(
            path, ids[m], scores[m], run_tag=f"{tag_prefix}/{s.name}", valid=valid[m]
        )
        paths[s.name] = path
    return paths


def run_experiment(
    spec: ExperimentSpec,
    *,
    out_dir: str,
    seed: int = 0,
    resume: bool = True,
    fail_at_segment: int | None = None,
    fail_at_shard: int = 0,
    collection: Collection | None = None,
    pipelined: bool = True,
    max_workers: int | None = None,
    faults: Any | None = None,
    max_retries: int = 0,
    speculative: bool = False,
    trace_out: str | None = None,
    tuning: TuningConfig | None = None,
    tune_lookup: bool = False,
    tune_cache: str | None = None,
) -> dict:
    """Execute the full lifecycle; returns (and writes) the report dict.

    Artifacts under ``out_dir``: ``runs/<variant>.run``, ``qrels.txt``,
    ``ckpt/`` (segment checkpoints + progress manifests; per-shard subdirs
    when ``spec.n_shards > 1``), ``report.json``. Run files are byte-
    identical at every shard count (the `repro.cluster` merge contract), so
    shard count is an execution knob, not part of the experiment identity —
    as are ``pipelined`` (the overlapped executor: concurrent shards,
    segment prefetch, async checkpoints; byte-identical artifacts either
    way) and ``max_workers`` (caps the shard thread pool; default one
    worker per visible device).

    ``faults`` (a ``repro.cluster.FaultSchedule``), ``max_retries``, and
    ``speculative`` drive the reliability layer: injected failures are
    retried from their shard's last committed segment checkpoint and the
    slowest in-flight shard is speculatively duplicated when the queue
    drains — run files stay byte-identical regardless, and the report's
    ``job`` section records what the scheduler did (retries, steals,
    speculation, fired faults).

    ``tuning`` runs the scan under an explicit :class:`repro.tune.
    TuningConfig`; ``tune_lookup=True`` instead looks the spec's shape
    signature up in the persistent autotune winner cache (``tune_cache``
    path, default resolution in `repro.tune.cache`) and runs under the
    recorded winner — falling back to the defaults on a miss. Either way
    the report's ``job.tuning`` block records the config hash, source, and
    whether the cache hit; run files are byte-identical under every config
    (the `repro.tune` contract).

    ``trace_out`` enables the observability layer for this run: a fresh
    tracer + metrics registry are installed for the lifecycle, the Chrome
    ``trace_event`` JSON lands at that path (with the JSONL event log next
    to it), and the report's ``job.obs`` block carries the trace paths, the
    metrics rollup, and the per-shard time-per-phase summary. Tracing only
    observes — run files are byte-identical with it on or off
    (chaos-suite-enforced).
    """
    if fail_at_segment is not None:
        # convert here rather than forwarding, so the DeprecationWarning
        # points at *this function's caller*, not at the forwarding call
        # inside this module (test-pinned via warning filename)
        warnings.warn(
            "fail_at_segment/fail_at_shard are deprecated; use "
            "faults=FaultSchedule([FaultSpec(kind='crash', ...)])",
            DeprecationWarning,
            stacklevel=2,
        )
        legacy = FaultSchedule.from_legacy(fail_at_segment, fail_at_shard)
        if faults is None:
            faults = legacy
        else:
            faults.add(legacy.specs[0])
        fail_at_segment = None

    if tuning is not None and tune_lookup:
        raise ValueError("pass either tuning= or tune_lookup=True, not both")
    tuning_source = "explicit" if tuning is not None else "default"
    cache_hit = False
    if tune_lookup:
        tuning, cache_hit = tune.best_config(
            "scan_job",
            shape=tune.scan_shape_sig_for(spec),
            backend=tune.backend_sig(use_kernel=spec.use_kernel),
            path=tune_cache,
        )
        tuning_source = "cache"

    prev_obs = None
    if trace_out is not None:
        prev_obs = obs.install(obs.Tracer(), obs.Metrics())
    try:
        # install as the process-active config too, so knobs resolved off
        # the explicit path (serve helpers, direct kernel calls inside the
        # lifecycle) see the same tuning the job runs under
        with tune.use(tuning, source=tuning_source, cache_hit=cache_hit):
            return _run_experiment_traced(
                spec,
                out_dir=out_dir,
                seed=seed,
                resume=resume,
                collection=collection,
                pipelined=pipelined,
                max_workers=max_workers,
                faults=faults,
                max_retries=max_retries,
                speculative=speculative,
                trace_out=trace_out,
                tuning=tuning,
                tuning_source=tuning_source,
                cache_hit=cache_hit,
            )
    finally:
        if prev_obs is not None:
            obs.install(*prev_obs)


def _run_experiment_traced(
    spec: ExperimentSpec,
    *,
    out_dir: str,
    seed: int,
    resume: bool,
    collection: Collection | None,
    pipelined: bool,
    max_workers: int | None,
    faults: Any | None,
    max_retries: int,
    speculative: bool,
    trace_out: str | None,
    tuning: TuningConfig | None = None,
    tuning_source: str = "default",
    cache_hit: bool = False,
) -> dict:
    """The lifecycle body, running under whatever instruments are installed."""
    tr = obs.tracer()
    met = obs.metrics()
    cfg = tune.resolve(tuning)
    # clamp eval cutoffs to the run depth up front — failing in evaluation
    # after the whole scan job ran would discard all the work
    if spec.k < max(spec.eval_ks):
        ks = tuple(c for c in spec.eval_ks if c <= spec.k) or (spec.k,)
        spec = dataclasses.replace(spec, eval_ks=ks)
    with tr.span("experiment.prepare", "experiment", experiment=spec.name, seed=seed):
        coll = (
            collection if collection is not None else prepare_collection(spec, seed=seed)
        )
    scorers = spec.scorers()
    # the corpus stays on the host: each shard's segments are staged to the
    # device that folds them, so no device ever holds another shard's rows
    docs = (np.asarray(coll.corpus.tokens), np.asarray(coll.corpus.lengths))
    # pack on the producer: token segments shrink to the tuned width here,
    # before sharding/staging, and every consumer decodes exactly — run
    # files stay byte-identical to the unpacked oracle (the pack contract)
    pack_resolved = "none"
    if cfg.token_pack != "none" and all(s.kind == "lexical" for s in scorers):
        packed = packing.pack_corpus(
            np.asarray(coll.corpus.tokens),
            np.asarray(coll.corpus.lengths),
            vocab=spec.vocab,
            mode=cfg.token_pack,
        )
        if isinstance(packed, packing.PackedCorpus):
            pack_resolved = packed.spec.mode
            docs = packed

    # the tuned chunk replaces the spec's *for the scan fold only* (stats
    # preparation keeps the declared chunking — stats bytes depend on it);
    # a tuned chunk the plan can't cut falls back to the declared one: a
    # knob may be ignored, never fail a job. Chunk regrouping is byte-safe
    # (per-doc scores are chunk-independent; the top-k combiner's
    # positional tie-break is lexicographic on monotone id streams).
    chunk = spec.chunk_size
    if cfg.chunk_size is not None:
        per_shard = spec.n_docs // max(1, spec.n_shards)
        if spec.n_docs % max(1, spec.n_shards) == 0 and per_shard % cfg.chunk_size == 0:
            chunk = cfg.chunk_size

    # the scan is a cluster job at every shard count: n_shards=1 is the
    # classic single-host layout, >1 adds per-shard checkpoints/kill/resume
    # and a merge whose output is byte-identical to the one-shard run.
    # shards spread round-robin over the visible devices (one device = a
    # host-sequential cluster, the paper's own execution model).
    plan = plan_shards(spec.n_docs, n_shards=spec.n_shards, chunk_size=chunk)
    devices = jax.devices() if spec.n_shards > 1 else None
    with tr.span(
        "experiment.scan", "experiment", n_shards=plan.n_shards, pipelined=pipelined
    ):
        job = run_sharded_scan_job(
            jnp.asarray(coll.queries),
            docs,
            scorers,
            k=spec.k,
            chunk_size=chunk,
            segment_chunks=spec.segment_chunks,
            plan=plan,
            stats=coll.stats,
            ckpt_dir=os.path.join(out_dir, "ckpt"),
            resume=resume,
            use_kernel=spec.use_kernel,
            devices=devices,
            pipelined=pipelined,
            max_workers=max_workers,
            faults=faults,
            max_retries=max_retries,
            speculative=speculative,
            tuning=cfg,
        )

    with tr.span("experiment.run_files", "experiment"):
        run_paths = write_run_files(
            os.path.join(out_dir, "runs"), scorers, job.state, tag_prefix=spec.name
        )
        trec.write_qrels(os.path.join(out_dir, "qrels.txt"), coll.qrels)

    with tr.span("experiment.eval", "experiment"):
        # one pass over the qrels matrix, shared by every measure of every model
        with tr.span("eval.judgments", "eval") as sp:
            judged = judgments(coll.qrels, max(spec.eval_ks))
            sp.set(n_judged=judged.n_judged, n_docs=judged.qrels.shape[1])
        reports = {}
        per_query_ap = {}
        for m, s in enumerate(scorers):
            rep = evaluate_run(np.asarray(job.state.ids)[m], judged, ks=spec.eval_ks)
            reports[s.name] = rep["aggregate"]
            per_query_ap[s.name] = rep["per_query"]["ap"]

        significance = {}
        baseline = spec.baseline if spec.baseline in per_query_ap else scorers[0].name
        with tr.span("eval.significance", "eval"):
            for name, ap in per_query_ap.items():
                if name == baseline:
                    continue
                res = paired_randomization_test(ap, per_query_ap[baseline], seed=seed)
                significance[name] = {
                    "vs": baseline,
                    "metric": "ap",
                    "diff": res.diff,
                    "p_value": res.p_value,
                }

    obs_block = None
    if trace_out is not None:
        # the trace lives *outside* runs/ so artifact byte-identity checks
        # (traced run vs tracing-off oracle) diff the run dirs untouched
        trace_dir = os.path.dirname(trace_out)
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
        jsonl_path = os.path.splitext(trace_out)[0] + ".jsonl"
        obs.export.write_chrome_trace(trace_out, tr, metrics=met)
        obs.export.write_jsonl(jsonl_path, tr)
        obs_block = {
            "trace": trace_out,
            "events_jsonl": jsonl_path,
            "n_events": len(tr),
            "metrics": met.summary(),
            "phases": obs.export.phase_rollup(tr),
        }

    report = {
        "experiment": spec.name,
        "seed": seed,
        "n_docs": spec.n_docs,
        "n_queries": spec.n_queries,
        "k": spec.k,
        "models": [s.name for s in scorers],
        "job": {
            "n_shards": job.plan.n_shards,
            "pipelined": pipelined,
            "segments_total": job.segments_total,
            "segments_run": job.segments_run,
            "resumed_from": max(r.resumed_from for r in job.shard_results),
            "max_retries": max_retries,
            "speculative": speculative,
            "scheduler": job.scheduler.describe() if job.scheduler else None,
            "faults_fired": faults.fired if faults is not None else [],
            "tuning": {
                "config_hash": cfg.config_hash(),
                "source": tuning_source,
                "cache_hit": cache_hit,
                "overrides": cfg.overrides(),
                "chunk_size": chunk,
                "token_pack": cfg.token_pack,
                "pack_resolved": pack_resolved,
            },
            "obs": obs_block,
            "shards": [
                {
                    "segments_total": r.segments_total,
                    "segments_run": r.segments_run,
                    "resumed_from": r.resumed_from,
                    "device": _device_of(r.state.scores),
                }
                for r in job.shard_results
            ],
        },
        "runs": run_paths,
        "metrics": reports,
        "baseline": baseline,
        "significance": significance,
    }
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report
