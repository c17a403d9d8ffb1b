"""Checkpointed sharded scan jobs — MIREX's cluster, kill/resume per shard.

The Hadoop property the paper leans on (any split can be re-executed and
re-reduced without changing the answer) holds here at two nested levels:

* **within a shard** — the corpus folds one chunk-aligned *segment* at a
  time through a single jitted multi-scorer fold; after every segment the
  stacked ``TopKState`` commits via the atomic-rename checkpointer and a
  ``progress.json`` manifest is rewritten, so a killed shard restarts from
  its last committed segment and replays the exact per-chunk instruction
  stream of an uninterrupted run (bit-identical, test-enforced);
* **across shards** — each shard owns its own checkpoint directory and
  progress manifest, fails and resumes independently, and the final
  :func:`repro.cluster.mapreduce.reduce_states` merge is value-deterministic,
  so the merged state (and every TREC run file written from it) is
  byte-identical whatever subset of shards died, resumed, or ran on which
  device — and byte-identical to the one-shard job, which is literally this
  code with a trivial plan.

Failure injection goes through :mod:`repro.cluster.faults`: a seeded
``FaultSchedule`` can crash any shard at any segment (before or after the
checkpoint commit), fail the checkpoint writer mid-commit, slow shards down
(stragglers), and retire scheduler workers. The legacy
``fail_at_segment``/``fail_at_shard`` kwargs survive as thin deprecated
aliases for one transient post-commit crash — the canonical lost-ack kill
point, and the only fault the old plumbing could express.

**The reliability layer** (:mod:`repro.cluster.scheduler`) turns the
pipelined executor's static shard-per-worker assignment into a work queue:
idle workers steal queued shards, failed shards retry with capped
exponential backoff from their last committed segment checkpoint
(``max_retries``), and when the queue drains the slowest in-flight shard is
speculatively re-executed from its checkpoint (``speculative=True``),
first-committed-wins. None of it changes a byte of any artifact — every
attempt replays the same chunk-aligned fold, and the reduce stays
plan-ordered.

**The pipelined executor** (``pipeline=True``, the default) overlaps
everything the sequential path serializes, without changing a byte of any
artifact:

* one compiled fold — `cluster.mapreduce.segment_fold` is jit-cached per
  (grid, k, chunk, kernel) configuration, so all shards and segments of a
  job (and every later job with the same config) share one program instead
  of re-tracing per ``run_scan_job`` call;
* double-buffered segments — `pipeline.prefetch_segments` stages segment
  *s+1*'s host→device transfer while segment *s* folds, and stops eagerly
  staging a shard's whole doc slice on its device up front;
* async checkpoints — the ``save → progress → prune`` commit sequence runs
  on a `checkpoint.AsyncCheckpointer` writer thread in submission order,
  with a drain barrier before any reported kill/completion, so kill/resume
  disk states are exactly the synchronous path's;
* concurrent shards — ``run_sharded_scan_job`` runs shards on a
  device-aware thread pool (one worker per assigned device, round-robin
  placement preserved), then reduces through the same value-deterministic
  merge, so merged states stay byte-identical to the sequential executor
  and the single-host oracle.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import threading
import time
import warnings
from typing import Any, Sequence

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro import obs
from repro.core import pipeline, topk
from repro.core.scoring import CollectionStats, Scorer
from repro.tune import config as tune_config
from repro.tune.config import TuningConfig

from repro.cluster.faults import FaultSchedule, ShardCancelled, WorkerCrash
from repro.cluster.mapreduce import reduce_states, segment_fold
from repro.cluster.plan import ShardPlan, plan_shards
from repro.cluster.scheduler import SchedulerStats, ShardScheduler


@dataclasses.dataclass(frozen=True)
class ScanJobResult:
    state: topk.TopKState  # stacked [n_models, n_q, k]
    segments_run: int  # segments executed by *this* invocation
    segments_total: int
    resumed_from: int  # segment index the run started at (0 = fresh)


@dataclasses.dataclass(frozen=True)
class ShardedScanResult:
    """Merged result of a sharded job + each shard's own job result."""

    state: topk.TopKState  # merged [n_models, n_q, k]
    plan: ShardPlan
    shard_results: tuple[ScanJobResult, ...]
    scheduler: SchedulerStats | None = None  # retry/steal/speculation counters

    @property
    def segments_run(self) -> int:
        return sum(r.segments_run for r in self.shard_results)

    @property
    def segments_total(self) -> int:
        return sum(r.segments_total for r in self.shard_results)

    @property
    def resumed(self) -> bool:
        return any(r.resumed_from for r in self.shard_results)


def _job_fingerprint(
    queries, docs, scorers, k: int, chunk_size: int, segment_chunks: int,
    doc_id_offset: int, stats,
) -> str:
    """Cheap identity of (data, grid, chunking, segmentation) — guards resume.

    A checkpointed TopKState from a *different* job can have exactly the same
    array shapes (same model count / query count / k), so shape checks alone
    would silently resume the wrong experiment. Hash the configuration, the
    full query set (small) and a strided row sample of the corpus instead.
    ``segment_chunks`` matters because the checkpoint step counts *segments*:
    reinterpreting it under a different segmentation would skip or double-fold
    corpus rows without any shape mismatch. ``doc_id_offset`` makes every
    shard of a sharded job a *distinct* job, so shard checkpoints can never
    be cross-adopted (e.g. after re-planning the same dir at a different
    shard count).
    """
    h = hashlib.sha256()
    h.update(
        repr(
            (k, chunk_size, segment_chunks, doc_id_offset, [s.name for s in scorers])
        ).encode()
    )
    for leaf in jax.tree.leaves(queries):
        h.update(np.asarray(leaf).tobytes())
    for leaf in jax.tree.leaves(docs):
        h.update(repr(tuple(leaf.shape)).encode())
        stride = max(1, leaf.shape[0] // 64)
        h.update(np.asarray(leaf[::stride][:64]).tobytes())
    # stats shape the scores: resuming under different collection statistics
    # would merge incompatible partial scores without any shape mismatch
    if stats is not None:
        for leaf in jax.tree.leaves(stats):
            h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


# distinguishes "stream ended early" (scheduler cancel closed the prefetch
# stream) from any real segment value when pulling with a default
_STREAM_ENDED = object()


def _chain_first(first, rest):
    """Prepend an already-staged segment to a prefetch stream, keeping the
    stream's close() semantics (the consumer's ``finally`` closes us, we
    close the underlying prefetch iterator and its worker thread)."""
    try:
        yield first
        yield from rest
    finally:
        rest.close()


def _write_json(path: str, payload: dict) -> None:
    tmp = os.path.join(os.path.dirname(path), ".tmp-" + os.path.basename(path))
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)


def _write_progress(ckpt_dir: str, payload: dict) -> None:
    _write_json(os.path.join(ckpt_dir, "progress.json"), payload)


def read_progress(ckpt_dir: str) -> dict | None:
    path = os.path.join(ckpt_dir, "progress.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def run_scan_job(
    queries: Any,
    docs: Any,
    scorers: Sequence[Scorer],
    *,
    k: int,
    chunk_size: int,
    segment_chunks: int,
    stats: CollectionStats | None = None,
    ckpt_dir: str | None = None,
    resume: bool = True,
    keep_checkpoints: int | None = None,
    fail_at_segment: int | None = None,
    shard: int = 0,
    n_shards: int = 1,
    doc_id_offset: int = 0,
    use_kernel: bool = False,
    device: jax.Device | None = None,
    pipelined: bool = True,
    prefetch_depth: int | None = None,
    faults: FaultSchedule | None = None,
    attempt: int = 0,
    cancel: threading.Event | None = None,
    tuning: TuningConfig | None = None,
    first_segment: Any | None = None,
    writer: ckpt.AsyncCheckpointer | None = None,
) -> ScanJobResult:
    """Run (or resume) one shard's checkpointed multi-scorer scan — the map
    task of the sharded job, and the whole job when the plan has one shard.

    ``ckpt_dir=None`` degrades to a plain uncheckpointed single pass. The
    checkpoint step number is "segments completed", so ``latest_step`` *is*
    the resume point; ``keep_checkpoints`` bounds disk via ``ckpt.prune``.
    ``device`` pins the shard's fold (and its restored state) to one device —
    how :func:`run_sharded_scan_job` spreads shards over a mesh's devices.

    ``pipelined=True`` (default) runs the overlapped executor: segments
    stream to the device ``prefetch_depth`` ahead of the fold
    (`pipeline.prefetch_segments`) and checkpoint commits run on an async
    writer with a drain barrier (`checkpoint.AsyncCheckpointer`);
    ``pipelined=False`` is the fully synchronous reference executor.
    Both fold through the shared compiled program (`segment_fold`) and
    produce byte-identical states, checkpoints, and resume points.

    ``faults`` is the deterministic injection schedule consulted at each
    point of the per-segment loop (see :mod:`repro.cluster.faults`);
    ``attempt`` is this execution's attempt number for transient-fault
    matching (0 = first try). ``cancel`` is the scheduler's cooperative stop
    signal: when a rival attempt commits first, the event is set and this
    run raises :class:`ShardCancelled` at the next segment boundary.
    ``fail_at_segment`` is a deprecated alias for one transient post-commit
    crash at exactly that segment.

    ``tuning`` picks the execution-only knobs (explicit arg > the
    process-active :class:`repro.tune.TuningConfig`): ``prefetch_depth`` and
    ``keep_checkpoints`` default from it when passed as ``None``, and the
    kernel block geometry flows into the shared fold. ``first_segment`` is
    an already-staged (device-resident) copy of segment 0's docs — the
    cross-shard prefetch handoff from :func:`run_sharded_scan_job` — used
    only on a fresh pipelined start (a resumed job ignores it; the staged
    rows were already folded). ``writer`` is an externally-owned
    :class:`checkpoint.AsyncCheckpointer` to reuse across shards: the job
    drains it at the usual barriers but never closes it; ownership (and
    discarding it if this attempt fails) stays with the caller.
    """
    scorers = tuple(scorers)
    cfg = tune_config.resolve(tuning)
    if keep_checkpoints is None:
        keep_checkpoints = cfg.keep_checkpoints
    if prefetch_depth is None:
        prefetch_depth = cfg.prefetch_depth
    if fail_at_segment is not None:
        if faults is not None:
            raise ValueError(
                "pass the crash as a FaultSpec in `faults`, not via the "
                "deprecated fail_at_segment kwarg"
            )
        warnings.warn(
            "fail_at_segment is deprecated; use faults=FaultSchedule([...])",
            DeprecationWarning,
            stacklevel=2,
        )
        faults = FaultSchedule.from_legacy(fail_at_segment, shard)
    n_rows = jax.tree.leaves(docs)[0].shape[0]
    n_q = jax.tree.leaves(queries)[0].shape[0]
    segs = pipeline.segments(n_rows, chunk_size, segment_chunks)

    # host-built init state (no device dispatch): concurrent shard workers
    # would serialize on eager op dispatches, and the batched device_put
    # below ships it with the queries/stats in one transfer
    state = topk.init_host(k, (len(scorers), n_q))
    if device is not None:
        # one batched transfer (a device_put per leaf costs a dispatch each,
        # which concurrent shards would serialize on)
        queries, stats, state = jax.device_put((queries, stats, state), device)
        if not pipelined:
            # legacy eager staging: the whole shard slice moves up front;
            # the pipelined path streams per-segment instead
            docs = jax.device_put(docs, device)

    fingerprint = None
    if ckpt_dir:
        fingerprint = _job_fingerprint(
            queries, docs, scorers, k, chunk_size, segment_chunks, doc_id_offset, stats
        )
    start_seg = 0
    if ckpt_dir and resume:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            prev = read_progress(ckpt_dir)
            if prev is not None and prev.get("fingerprint") != fingerprint:
                raise ValueError(
                    f"checkpoint dir {ckpt_dir!r} belongs to a different job "
                    f"(scorers {prev.get('scorers')}, fingerprint "
                    f"{prev.get('fingerprint')} != {fingerprint}); use a fresh "
                    "dir or resume=False"
                )
            if latest > len(segs):
                raise ValueError(
                    f"checkpoint at segment {latest} but job has {len(segs)} segments"
                )
            state = ckpt.restore(ckpt_dir, latest, state)
            if device is not None:
                state = jax.device_put(state, device)
            start_seg = latest
    elif ckpt_dir:
        # fresh start over a dirty dir: drop stale commits so they can never
        # masquerade as this run's progress (or out-survive it via prune)
        for s in ckpt.all_steps(ckpt_dir):
            shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"))
        stale = os.path.join(ckpt_dir, "progress.json")
        if os.path.exists(stale):
            os.remove(stale)

    # the one compiled program every shard/segment/job of this config shares
    fold = segment_fold(
        scorers, k=k, chunk_size=chunk_size, use_kernel=use_kernel, tuning=cfg
    )

    def progress(done: int) -> dict:
        return {
            "fingerprint": fingerprint,
            "n_segments": len(segs),
            "chunk_size": chunk_size,
            "segment_chunks": segment_chunks,
            "k": k,
            "scorers": [s.name for s in scorers],
            "shards": {
                str(shard): {
                    "n_shards": n_shards,
                    "doc_id_offset": doc_id_offset,
                    "segments_done": done,
                    "rows_done": segs[done - 1][1] if done else 0,
                    "n_rows": n_rows,
                    "complete": done == len(segs),
                }
            },
        }

    def check_cancel() -> None:
        if cancel is not None and cancel.is_set():
            raise ShardCancelled(
                f"shard {shard} attempt {attempt} cancelled by the scheduler"
            )

    ran = 0
    tr = obs.tracer()
    if pipelined:
        stream_segs = segs[start_seg:]
        if first_segment is not None and start_seg == 0 and stream_segs:
            # cross-shard prefetch handoff: segment 0 was staged on this
            # device while the previous shard was still folding — start the
            # background stream at segment 1
            rest = pipeline.prefetch_segments(
                docs, stream_segs[1:], device=device, depth=prefetch_depth,
                cancel=cancel,
            )
            seg_stream = _chain_first(first_segment, rest)
        else:
            seg_stream = pipeline.prefetch_segments(
                docs, stream_segs, device=device, depth=prefetch_depth,
                cancel=cancel,
            )
    else:
        seg_stream = (
            jax.tree.map(lambda x: x[a:b], docs) for a, b in segs[start_seg:]
        )
    seg_iter = iter(seg_stream)
    writer_owned = writer is None
    if not (pipelined and ckpt_dir):
        writer = None  # the sync / uncheckpointed paths never touch a writer
    elif writer is None:
        writer = ckpt.AsyncCheckpointer()
    shard_span = tr.span(
        "shard.run", "job", shard=shard, attempt=attempt,
        resumed_from=start_seg, n_segments=len(segs),
    )
    with shard_span:
        try:
            for seg_idx in range(start_seg, len(segs)):
                check_cancel()
                # time spent waiting on the segment stream = pipeline-stall
                # time (prefetch not keeping up with the fold) made visible
                with tr.span(
                    "segment.prefetch_wait", "pipeline", shard=shard, segment=seg_idx
                ):
                    seg_docs = next(seg_iter, _STREAM_ENDED)
                if seg_docs is _STREAM_ENDED:
                    break  # the prefetch stream ends early on a cancel
                if faults is not None:
                    faults.maybe_delay(shard, seg_idx, attempt, cancel=cancel)
                    check_cancel()  # a cancelled straggler stops mid-nap
                    if faults.crash_at(shard, seg_idx, attempt, "pre_commit"):
                        # die *before* the commit: work since the last committed
                        # segment is lost and must be re-folded by the retry
                        raise WorkerCrash(
                            f"injected failure before segment {seg_idx} commit"
                        )
                a, _ = segs[seg_idx]
                # the fold's *dispatch*: the device runs it asynchronously,
                # and the program first waits on it in this segment's
                # ckpt.fetch (on the writer thread when pipelined)
                with tr.span("segment.fold", "job", shard=shard, segment=seg_idx):
                    state = fold(
                        state, queries, seg_docs, stats, np.int32(doc_id_offset + a)
                    )
                ran += 1
                if ckpt_dir:
                    on_commit = (
                        faults.commit_hook(shard, seg_idx, attempt) if faults else None
                    )
                    save_kw = {"shard": shard}
                    if on_commit is not None:
                        save_kw["on_commit"] = on_commit
                    if writer is not None:
                        # commit off the critical path; submission order keeps
                        # the on-disk sequence identical to the sync path's
                        # (an injected writer error poisons this writer exactly
                        # like a real I/O failure: later tasks skipped, error
                        # re-raised at the next drain). The actual save spans
                        # (ckpt.save > fetch / write / rename) appear on the
                        # writer thread.
                        with tr.span(
                            "segment.commit_submit", "ckpt",
                            shard=shard, segment=seg_idx,
                        ):
                            writer.submit(
                                ckpt.save, ckpt_dir, seg_idx + 1, state, **save_kw
                            )
                            writer.submit(
                                _write_progress, ckpt_dir, progress(seg_idx + 1)
                            )
                            writer.submit(ckpt.prune, ckpt_dir, keep_checkpoints)
                    else:
                        with tr.span(
                            "segment.commit", "ckpt", shard=shard, segment=seg_idx
                        ):
                            state = jax.block_until_ready(state)
                            ckpt.save(ckpt_dir, seg_idx + 1, state, **save_kw)
                            _write_progress(ckpt_dir, progress(seg_idx + 1))
                            ckpt.prune(ckpt_dir, keep_checkpoints)
                if faults is not None and faults.crash_at(
                    shard, seg_idx, attempt, "post_commit"
                ):
                    # die *after* the commit: the canonical lost-ack kill point
                    if writer is not None:
                        writer.drain()
                    raise WorkerCrash(f"injected failure after segment {seg_idx}")
            check_cancel()  # cooperative stop observed at the segment boundary
            if writer is not None:
                # barrier: every commit durable before we report done; waiting
                # here = the writer is the bottleneck, visible in the trace
                with tr.span("ckpt.drain_wait", "ckpt", shard=shard):
                    writer.drain()
        except BaseException:
            if writer is not None:
                # an external writer is only drained (no in-flight commit may
                # outlive this attempt); closing/discarding it is its owner's
                # call. The in-flight error (e.g. the injected kill) wins
                # over any writer error either way.
                with contextlib.suppress(BaseException):
                    writer.close() if writer_owned else writer.drain()
                writer = None
            raise
        finally:
            if pipelined:
                seg_stream.close()  # stop the prefetch thread on any exit path
            if writer is not None and writer_owned:
                writer.close()
    if ckpt_dir and start_seg == len(segs):
        _write_progress(ckpt_dir, progress(len(segs)))  # idempotent re-run
    return ScanJobResult(
        state=state,
        segments_run=ran,
        segments_total=len(segs),
        resumed_from=start_seg,
    )


def shard_ckpt_dir(ckpt_dir: str, plan: ShardPlan, index: int) -> str:
    """Shard ``index``'s checkpoint directory under the job's ``ckpt_dir``.

    The one-shard plan *is* the classic single-host job, flat layout and all
    — the special case the sharded job degrades to, not a parallel code path.
    """
    if plan.n_shards == 1:
        return ckpt_dir
    return os.path.join(ckpt_dir, f"shard_{index:04d}")


def read_cluster_manifest(ckpt_dir: str) -> dict | None:
    path = os.path.join(ckpt_dir, "cluster.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def spec_ckpt_dir(primary: str) -> str:
    """A speculative attempt's private checkpoint dir, next to the primary's."""
    return primary + ".spec"


def _seed_spec_dir(primary: str, spec_dir: str) -> None:
    """Seed a speculative clone's checkpoint dir from the primary's last
    committed segment, so the clone re-executes only the shard's tail.

    The primary attempt is still running (that's the point), so its commits
    and prunes race with this copy; any I/O error falls back to an empty
    dir — a full re-execution, slower but still byte-identical.
    """
    shutil.rmtree(spec_dir, ignore_errors=True)
    os.makedirs(spec_dir, exist_ok=True)
    try:
        latest = ckpt.latest_step(primary)
        if latest is not None:
            step = f"step_{latest:08d}"
            shutil.copytree(
                os.path.join(primary, step), os.path.join(spec_dir, step)
            )
            prog = os.path.join(primary, "progress.json")
            if os.path.exists(prog):
                shutil.copy(prog, os.path.join(spec_dir, "progress.json"))
    except OSError:
        shutil.rmtree(spec_dir, ignore_errors=True)
        os.makedirs(spec_dir, exist_ok=True)


class _ShardStager:
    """Cross-shard prefetch: stage the *next* queued shard's first segment
    while the current one is still folding.

    `pipeline.prefetch_segments` overlaps transfers *within* a shard but
    goes cold at shard boundaries — a worker picking up its next shard
    stalls on segment 0's host slice + device transfer. A worker entering a
    shard therefore asks the stager to start staging the lowest-index
    still-queued shard's first segment onto that shard's home device, on a
    background thread; whichever worker later claims that shard collects
    the staged segment with :meth:`take` and hands it to
    :func:`run_scan_job` as ``first_segment``.

    Purely an optimization, never a correctness dependency: a device
    mismatch (the shard was stolen onto another worker's device), a staging
    error, or a claim that raced the staging thread all degrade to ``None``
    — the job re-slices segment 0 itself, byte-identical either way.
    """

    def __init__(self, docs, plan: ShardPlan, devices, seg_rows: int):
        self._docs = docs
        self._plan = plan
        self._devices = list(devices)
        self._seg_rows = seg_rows
        self._lock = threading.Lock()
        self._pending = set(range(plan.n_shards))  # not yet claimed by a worker
        self._staged: dict[int, tuple[threading.Thread, list, Any]] = {}

    def take(self, index: int, device):
        """Claim shard ``index``; return its staged first segment if it was
        prefetched onto ``device``, else None."""
        with self._lock:
            self._pending.discard(index)
            entry = self._staged.pop(index, None)
        if entry is None:
            return None
        thread, box, dev = entry
        thread.join()
        if dev is not device or not box:
            return None
        return box[0]

    def stage_next(self) -> None:
        """Kick off staging for the lowest-index queued, un-staged shard
        (onto its round-robin home device). No-op when nothing is queued."""
        with self._lock:
            todo = sorted(i for i in self._pending if i not in self._staged)
            if not todo:
                return
            idx = todo[0]
            shard = self._plan.shards[idx]
            dev = self._devices[idx % len(self._devices)]
            box: list = []

            def _stage():
                try:
                    with obs.tracer().span(
                        "prefetch.stage_shard", "pipeline", shard=idx
                    ):
                        a = shard.start
                        b = min(shard.stop, a + self._seg_rows)
                        seg = jax.tree.map(lambda x: x[a:b], self._docs)
                        box.append(jax.device_put(seg, dev))
                except BaseException:  # noqa: BLE001 — a miss, not a failure
                    box.clear()

            t = threading.Thread(target=_stage, name=f"shard-stage-{idx}", daemon=True)
            # started before it is published: a worker claiming this shard
            # at once must find a thread it can join
            t.start()
            self._staged[idx] = (t, box, dev)


class _WriterPool:
    """Per-worker `checkpoint.AsyncCheckpointer` reuse for a sharded job.

    Spinning up a writer thread per shard attempt is pure overhead when one
    worker runs many shards back to back; the pool hands each worker thread
    one long-lived writer (``threading.local``) that successive
    `run_scan_job` calls drain-but-don't-close. A writer error poisons the
    writer permanently (by design — see `AsyncCheckpointer`), so a failed
    attempt must :meth:`discard` its worker's writer rather than return it.
    """

    def __init__(self):
        self._local = threading.local()
        self._all: list = []
        self._lock = threading.Lock()

    def get(self) -> ckpt.AsyncCheckpointer:
        w = getattr(self._local, "writer", None)
        if w is None:
            w = ckpt.AsyncCheckpointer()
            self._local.writer = w
            with self._lock:
                self._all.append(w)
        return w

    def discard(self) -> None:
        """Drop (and close) the calling worker's writer — it may be poisoned."""
        w = getattr(self._local, "writer", None)
        if w is None:
            return
        self._local.writer = None
        with self._lock:
            if w in self._all:
                self._all.remove(w)
        with contextlib.suppress(BaseException):
            w.close()

    def close_all(self) -> None:
        with self._lock:
            writers, self._all = self._all, []
        for w in writers:
            with contextlib.suppress(BaseException):
                w.close()


def run_sharded_scan_job(
    queries: Any,
    docs: Any,
    scorers: Sequence[Scorer],
    *,
    k: int,
    chunk_size: int,
    segment_chunks: int,
    plan: ShardPlan | None = None,
    n_shards: int = 1,
    stats: CollectionStats | None = None,
    ckpt_dir: str | None = None,
    resume: bool = True,
    keep_checkpoints: int | None = None,
    fail_at_segment: int | None = None,
    fail_at_shard: int = 0,
    use_kernel: bool = False,
    devices: Sequence[jax.Device] | None = None,
    pipelined: bool = True,
    max_workers: int | None = None,
    faults: FaultSchedule | None = None,
    max_retries: int = 0,
    backoff_base: float | None = None,
    backoff_cap: float | None = None,
    speculative: bool = False,
    tuning: TuningConfig | None = None,
) -> ShardedScanResult:
    """Run (or resume) a full sharded scan job: map every shard, reduce once.

    Pass a :class:`ShardPlan` (e.g. from ``plan_for_mesh``) or just
    ``n_shards`` to cut one here. Each shard runs :func:`run_scan_job` in its
    own checkpoint directory (``<ckpt_dir>/shard_NNNN``; the one-shard plan
    uses ``ckpt_dir`` itself — the classic single-host layout), so shards
    fail and resume independently; completed shards replay as no-op restores.
    ``devices`` spreads shards round-robin (``jax.devices()`` for the
    virtual-device smoke grid; real meshes at multi-process scale).

    ``pipelined=True`` (default) is the overlapped executor: shards become a
    work queue drained by :class:`repro.cluster.scheduler.ShardScheduler`
    with one worker per assigned device (override with ``max_workers``) — so
    a 4-device host actually scans 4 shards at once, and an idle worker
    steals whatever shard is queued instead of waiting for its round-robin
    assignment. Each shard's job streams segments and commits checkpoints
    asynchronously (see :func:`run_scan_job`). With no ``devices`` (or
    ``max_workers=1``) shards run in plan order on one worker, which
    preserves the sequential executor's exact failure ordering (shards after
    a permanently-failed shard never start).

    ``max_retries`` re-runs a failed shard from its last committed segment
    checkpoint with capped exponential backoff (``backoff_base``/
    ``backoff_cap``); once a shard exhausts its retries the job drain-stops
    and raises that shard's *original* error. ``speculative=True`` clones
    the slowest in-flight shard when the queue drains (first-committed-wins;
    the winning clone's checkpoint dir is promoted over the primary's).
    ``faults`` injects deterministic failures for all of the above (see
    :mod:`repro.cluster.faults`); the legacy ``fail_at_segment``/
    ``fail_at_shard`` kwargs are deprecated aliases for one transient
    post-commit crash. Scheduler counters (retries, steals, speculation,
    dead workers) come back on ``ShardedScanResult.scheduler``.

    The final merged state is byte-identical for every shard count *and*
    both executors — chunk alignment keeps per-chunk score bytes equal, the
    shared fold is one compiled program, and the lexicographic reduce is
    value-deterministic and applied in plan order whatever order shards
    finish — so run files written from it satisfy the same fingerprint
    contract as the single-host job.

    ``tuning`` (explicit arg > process-active config) supplies defaults for
    ``max_workers``/``keep_checkpoints``/``backoff_base``/``backoff_cap``
    when those are ``None``, flows the kernel block geometry into the shared
    fold, and gates two boundary optimizations: ``cross_shard_prefetch``
    (stage the next queued shard's first segment while the current shard
    folds — see :class:`_ShardStager`) and ``writer_reuse`` (one async
    checkpoint writer per worker across shards, only engaged when no fault
    injection or speculation could poison a shared writer). All of it is
    execution geometry: byte-identical artifacts under every config.
    """
    if fail_at_segment is not None:
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

    cfg = tune_config.resolve(tuning)
    if backoff_base is None:
        backoff_base = cfg.backoff_base
    if backoff_cap is None:
        backoff_cap = cfg.backoff_cap
    n_rows = jax.tree.leaves(docs)[0].shape[0]
    if plan is None:
        plan = plan_shards(n_rows, n_shards=n_shards, chunk_size=chunk_size)
    if plan.n_docs != n_rows:
        raise ValueError(f"docs have {n_rows} rows but plan covers {plan.n_docs}")
    if plan.chunk_size != chunk_size:
        raise ValueError(
            f"plan chunk_size {plan.chunk_size} != job chunk_size {chunk_size}"
        )

    if ckpt_dir and plan.n_shards > 1:
        manifest = read_cluster_manifest(ckpt_dir)
        if manifest is not None and resume and manifest["plan"] != plan.describe():
            raise ValueError(
                f"checkpoint dir {ckpt_dir!r} holds a different shard plan "
                f"({manifest['plan']['n_shards']} shards over "
                f"{manifest['plan']['n_docs']} docs); use a fresh dir or "
                "resume=False"
            )
        os.makedirs(ckpt_dir, exist_ok=True)
        _write_json(
            os.path.join(ckpt_dir, "cluster.json"),
            {"plan": plan.describe(), "scorers": [s.name for s in scorers], "k": k},
        )

    workers = 1
    if pipelined:
        workers = max_workers if max_workers else (
            cfg.max_workers or (len(devices) if devices else 1)
        )
        workers = max(1, min(workers, plan.n_shards))
        if devices and len(devices) > workers:
            # only `workers` threads ever execute, and each folds on
            # devices[worker % len(devices)] — staging queries/stats (and
            # prefetching shards) onto devices no worker drives is pure
            # waste (the anti-scaling seen on thin hosts: 4 shards staged
            # to 4 devices with 2 workers ran *slower* than 2 shards)
            devices = list(devices)[:workers]

    # stage the replicated inputs once per assigned device, outside the
    # worker pool: shards on the same device share the transfer, and the
    # in-job device_put then short-circuits instead of re-copying while
    # other workers hold the dispatch path
    staged: dict = {}
    if devices:
        for shard in plan.shards:
            dev = devices[shard.index % len(devices)]
            if dev not in staged:
                staged[dev] = jax.device_put((queries, stats), dev)

    # cross-shard prefetch: stage the next queued shard's first segment
    # while the current one folds (worthless — and unconsumed — for the
    # one-shard plan or the eager-staging sequential path)
    stager = None
    if pipelined and cfg.cross_shard_prefetch and devices and plan.n_shards > 1:
        stager = _ShardStager(
            docs, plan, devices, seg_rows=chunk_size * segment_chunks
        )

    # one checkpoint writer per worker across its shards, only when no
    # speculation/fault-injection could leave a poisoned or racing writer
    # shared between attempts
    writer_pool = None
    if (
        pipelined and ckpt_dir and cfg.writer_reuse
        and faults is None and not speculative
    ):
        writer_pool = _WriterPool()

    def run_attempt(
        shard, *, worker=None, attempt=0, cancel=None, speculative=False
    ) -> ScanJobResult:
        device = None
        q, st = queries, stats
        if devices:
            # the executing worker's device, not the shard's round-robin
            # home — a stolen shard folds wherever it was picked up (byte
            # identity doesn't care: same compiled program, same bits)
            owner = shard.index if worker is None else worker
            device = devices[owner % len(devices)]
            q, st = staged[device]
        sdir = shard_ckpt_dir(ckpt_dir, plan, shard.index) if ckpt_dir else None
        if speculative and sdir is not None:
            primary, sdir = sdir, spec_ckpt_dir(sdir)
            _seed_spec_dir(primary, sdir)
        first_seg = None
        if stager is not None and not speculative:
            first_seg = stager.take(shard.index, device)
            stager.stage_next()  # overlap the *next* shard with this fold
        ext_writer = writer_pool.get() if writer_pool is not None else None
        try:
            return run_scan_job(
                q,
                shard.take(docs),
                scorers,
                k=k,
                chunk_size=chunk_size,
                segment_chunks=segment_chunks,
                stats=st,
                ckpt_dir=sdir,
                # retries and speculative clones always resume: the last
                # committed segment checkpoint is the unit of re-execution
                resume=resume or attempt > 0 or speculative,
                keep_checkpoints=keep_checkpoints,
                shard=shard.index,
                n_shards=plan.n_shards,
                doc_id_offset=shard.doc_id_offset,
                use_kernel=use_kernel,
                device=device,
                pipelined=pipelined,
                faults=faults,
                attempt=attempt,
                cancel=cancel,
                tuning=cfg,
                first_segment=first_seg,
                writer=ext_writer,
            )
        except BaseException:
            if writer_pool is not None:
                writer_pool.discard()  # a failed attempt may have poisoned it
            raise

    def finalize_spec(index: int, won: bool) -> None:
        # both attempts have stopped (scheduler invariant), so nothing is
        # writing to either dir: promote the winning clone's lineage over
        # the primary's, or drop the losing clone's
        if not ckpt_dir:
            return
        primary = shard_ckpt_dir(ckpt_dir, plan, index)
        sdir = spec_ckpt_dir(primary)
        if won and os.path.exists(sdir):
            ckpt.replace_dir(sdir, primary)
        else:
            shutil.rmtree(sdir, ignore_errors=True)

    if not pipelined:
        # the synchronous reference executor: plan order, one attempt in
        # flight, retries inline (no threads, no stealing, no speculation)
        results: list[ScanJobResult] = []
        attempts: list[int] = []
        retries = 0
        for s in plan.shards:
            failures = 0
            while True:
                try:
                    results.append(run_attempt(s, attempt=failures))
                    attempts.append(failures + 1)
                    break
                except ShardCancelled:
                    raise  # no scheduler to cancel us — never expected
                except BaseException:
                    failures += 1
                    if failures > max_retries:
                        raise
                    retries += 1
                    time.sleep(
                        min(backoff_cap, backoff_base * (2 ** (failures - 1)))
                    )
        stats_out = SchedulerStats(
            n_workers=1,
            attempts=tuple(attempts),
            retries=retries,
            steals=0,
            speculative_launched=0,
            speculative_won=0,
            dead_workers=(),
        )
    else:
        # the reliability layer: work queue + stealing + backoff retries +
        # speculation; results (and any failure) come back in plan order
        # however shards interleave, so the reduce below and the raised
        # error are deterministic
        sched = ShardScheduler(
            plan,
            run_attempt,
            n_workers=workers,
            max_retries=max_retries,
            backoff_base=backoff_base,
            backoff_cap=backoff_cap,
            speculative=speculative,
            faults=faults,
            finalize_spec=finalize_spec if speculative else None,
        )
        try:
            results, stats_out = sched.run()
        finally:
            if writer_pool is not None:
                writer_pool.close_all()

    states = [r.state for r in results]
    if devices:
        # reduce on one device: shard states live where their folds ran
        # (one batched transfer — k-bounded payloads, the paper's shuffle)
        states = jax.device_put(states, devices[0])
    merged = reduce_states(states)
    return ShardedScanResult(
        state=merged, plan=plan, shard_results=tuple(results), scheduler=stats_out
    )
