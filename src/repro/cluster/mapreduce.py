"""The cluster's map and reduce: per-shard scan folds + the one merge.

Paper §2, literally: **map** = sequentially scan one shard of the collection
against the full query (and model-grid) block; **reduce** = merge per-shard
top-k lists, at most ``k`` entries per query per shard ever crossing a
shard boundary. Both halves are the *same code* on every execution substrate:

* :func:`map_shard` is the single fold every shard runs — multi-model
  single-pass (`scan.search_local_multi`), fused Pallas lexical kernel under
  ``use_kernel``, sentinel-preserving global doc ids via the shard's offset.
  :func:`segment_fold` is that fold compiled *once per configuration* and
  shared by every shard, segment, job, and session with the same grid — the
  retrace fix that lets a sharded job scale instead of re-compiling per
  shard (`FOLD_TRACE_COUNTS` makes the compile count testable).
* :func:`reduce_states` is the k-bounded lexicographic bitonic merge
  (`topk.reduce_lex`): value-deterministic, so 1/2/4/N shards merge to the
  same bits, which is the shard-count-invariance contract jobs and serve
  both inherit.
* :func:`search_mesh` stamps the two onto a JAX mesh with ``shard_map`` —
  corpus sharded over the scan axes, queries/stats replicated, local map,
  hierarchical lexicographic reduce — for one-shot and serve-path scans.
  Checkpointed jobs use the host-loop driver in `cluster.job` instead (a
  shard that lives inside one XLA program can't kill/resume independently).
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Any, Callable, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core import scan, topk
from repro.core.scoring import CollectionStats, Scorer
from repro.tune import config as tune_config
from repro.tune.config import TuningConfig

from repro.cluster.plan import ShardPlan, mesh_scan_axes


def map_shard(
    queries: Any,
    shard_docs: Any,
    scorers: Sequence[Scorer],
    *,
    k: int,
    chunk_size: int,
    stats: CollectionStats | None = None,
    doc_id_offset: jax.Array | int = 0,
    init_state: topk.TopKState | None = None,
    use_kernel: bool = False,
    tuning: TuningConfig | None = None,
) -> topk.TopKState:
    """The map task: fold one shard into a stacked ``[n_models, n_q, k]`` state.

    A thin, named seam over `scan.search_local_multi` — jobs, the mesh path,
    and serve sessions all dispatch the same fold, so "works under sharding"
    is one property proven once. Dense single-model kernel scans route
    through `scan.search_local` (the fused dense kernel has no grid axis) and
    are re-stacked to the grid shape. ``tuning`` picks kernel block geometry
    (byte-identical under any config; see `repro.tune`).
    """
    scorers = tuple(scorers)
    if use_kernel and len(scorers) == 1 and scorers[0].kind == "dense":
        flat = scan.search_local(
            queries, shard_docs, scorers[0], k=k, chunk_size=chunk_size,
            stats=stats, doc_id_offset=doc_id_offset, use_kernel=True,
            tuning=tuning,
        )
        state = topk.TopKState(scores=flat.scores[None], ids=flat.ids[None])
        return state if init_state is None else topk.merge(init_state, state)
    return scan.search_local_multi(
        queries,
        shard_docs,
        scorers,
        k=k,
        chunk_size=chunk_size,
        stats=stats,
        doc_id_offset=doc_id_offset,
        init_state=init_state,
        use_kernel=use_kernel,
        tuning=tuning,
    )


def _scorer_key(scorers: Sequence[Scorer]) -> tuple:
    """Hashable identity of a scorer grid — the model-config part of
    `cluster.job._job_fingerprint`, kept as a plain tuple so it can key the
    shared fold cache (name encodes base + bound params for grid variants;
    ``params`` guards explicitly-renamed variants that reuse a name). The
    *underlying* score function's identity rides along so a re-registered
    or hand-built scorer that reuses a name can never inherit another
    scorer's compiled program — while `make_variant` grid points, whose
    ``functools.partial`` wrappers are fresh objects but share the registry
    base function, still share one compile."""

    def fn_id(s: Scorer):
        return s.fn.func if isinstance(s.fn, functools.partial) else s.fn

    return tuple((s.kind, s.name, s.base, s.params, fn_id(s)) for s in scorers)


# One compiled fold per (scorer grid, k, chunk_size, use_kernel) — shapes and
# dtypes are jax.jit's own cache key, so every equal-shaped shard and segment
# of a job (and of every job sharing the config) reuses one compiled program
# instead of re-tracing per `run_scan_job` call. `FOLD_TRACE_COUNTS` records
# actual traces per config key; tests pin "a 4-shard job compiles once" on it.
# Both module caches are FIFO-bounded so a long-lived process churning
# through configs (e.g. sessions over a growing corpus) can't accumulate
# traced programs forever; eviction is safe because callers keep their own
# reference to the program they were handed.
_FOLD_CACHE: dict[tuple, "_SharedFold"] = {}
_FOLD_CACHE_MAX = 128
_FOLD_CACHE_LOCK = threading.Lock()
FOLD_TRACE_COUNTS: collections.Counter = collections.Counter()


def _fifo_insert(cache: dict, key, value, max_entries: int):
    value = cache.setdefault(key, value)  # first builder wins
    while len(cache) > max_entries:
        cache.pop(next(iter(cache)))  # dicts iterate in insertion order
    return value


class _SharedFold:
    """A jit-cached segment fold whose *first* call (the trace+compile) is
    serialized, so a concurrent-shard executor hitting a cold cache compiles
    the program once instead of racing N identical traces."""

    def __init__(self, fn: Callable, key: tuple):
        self.key = key
        self._fn = fn
        self._lock = threading.Lock()
        self._warm = False

    def __call__(self, state, queries, seg_docs, stats, offset):
        if not self._warm:
            with self._lock:
                out = self._fn(state, queries, seg_docs, stats, offset)
                self._warm = True
                return out
        return self._fn(state, queries, seg_docs, stats, offset)


def segment_fold(
    scorers: Sequence[Scorer], *, k: int, chunk_size: int, use_kernel: bool = False,
    tuning: TuningConfig | None = None,
) -> _SharedFold:
    """The one compiled per-segment fold all shards/segments/jobs share.

    Returns a callable ``fold(state, queries, seg_docs, stats, offset) ->
    TopKState`` — :func:`map_shard` under ``jax.jit`` with the *data* as
    traced arguments, so the program is keyed by configuration here and by
    shapes inside jit. Every equal-shaped shard of a sharded job (the
    `cluster.plan` equal-shards invariant) therefore folds through one
    compiled program; a resumed job re-traces nothing; two sessions or jobs
    with the same grid share the compile. All args must live on one device —
    callers pin ``state``/``queries``/``stats``/segments to the shard's
    device (``offset`` may stay an uncommitted scalar; it follows).

    ``tuning`` is resolved *here*, at fold-build time (drivers resolve on
    their own thread; worker threads get the captured config), and the
    kernel-shaping knobs join the cache key via
    :meth:`TuningConfig.fold_key` — two tunings that would trace different
    Pallas programs can never alias one cache entry. Host folds ignore the
    block knobs, so their key component is empty and all tunings share the
    one host program.
    """
    scorers = tuple(scorers)
    cfg = tune_config.resolve(tuning)
    key = (
        _scorer_key(scorers), k, chunk_size, bool(use_kernel),
        cfg.fold_key(bool(use_kernel)),
    )
    with _FOLD_CACHE_LOCK:
        fold = _FOLD_CACHE.get(key)
        if fold is None:

            def _fold(state, queries, seg_docs, stats, offset):
                FOLD_TRACE_COUNTS[key] += 1  # trace-time side effect, on purpose
                return map_shard(
                    queries,
                    seg_docs,
                    scorers,
                    k=k,
                    chunk_size=chunk_size,
                    stats=stats,
                    doc_id_offset=offset,
                    init_state=state,
                    use_kernel=use_kernel,
                    tuning=cfg,
                )

            fold = _fifo_insert(
                _FOLD_CACHE, key, _SharedFold(jax.jit(_fold), key), _FOLD_CACHE_MAX
            )
    return fold


@jax.jit
def _reduce_states_jit(states: list[topk.TopKState]) -> topk.TopKState:
    return topk.reduce_lex(states)


def reduce_states(states: Sequence[topk.TopKState]) -> topk.TopKState:
    """The reduce task: lexicographic k-bounded merge of per-shard states.

    Order- and grouping-free (`topk.reduce_lex`), so the host loop, the mesh
    all-gather, and a future multi-process tree all produce the same bits.
    Jitted (cached per shard count + shapes): the bitonic merge network is
    dozens of tiny ops per pair, which dispatched eagerly would cost more
    than a whole shard's fold on a fast host.
    """
    states = list(states)
    if len(states) == 1:
        return states[0]
    return _reduce_states_jit(states)


def scan_shards(
    plan: ShardPlan,
    queries: Any,
    docs: Any,
    scorers: Sequence[Scorer],
    *,
    k: int,
    stats: CollectionStats | None = None,
    use_kernel: bool = False,
    devices: Sequence[jax.Device] | None = None,
    tuning: TuningConfig | None = None,
) -> topk.TopKState:
    """Uncheckpointed host-driven sharded scan: map every shard, reduce once.

    ``devices`` places shard ``i`` on ``devices[i % len(devices)]``
    (round-robin over the mesh's devices when the plan came from a mesh) —
    the degenerate None runs every shard on the default device, which is the
    substrate the shard-count-invariance tests pin down. Every shard folds
    through the shared :func:`segment_fold` program (equal shard shapes ⇒
    one compile for the whole plan, and for every later plan with the same
    grid/geometry). Checkpointed / resumable execution — and the concurrent
    pipelined executor — live in `cluster.job.run_sharded_scan_job`.
    """
    n_rows = jax.tree.leaves(docs)[0].shape[0]
    if n_rows != plan.n_docs:
        raise ValueError(f"docs have {n_rows} rows but plan covers {plan.n_docs}")
    scorers = tuple(scorers)
    n_q = jax.tree.leaves(queries)[0].shape[0]
    fold = segment_fold(
        scorers, k=k, chunk_size=plan.chunk_size, use_kernel=use_kernel,
        tuning=tuning,
    )
    state_init = topk.init_host(k, (len(scorers), n_q))
    states = []
    for shard in plan.shards:
        shard_docs = shard.take(docs)
        # host-built init state + one batched transfer per shard
        state0 = state_init
        q, st = queries, stats
        if devices:
            dev = devices[shard.index % len(devices)]
            q, st, state0, shard_docs = jax.device_put(
                (queries, stats, state0, shard_docs), dev
            )
        states.append(fold(state0, q, shard_docs, st, shard.doc_id_offset))
    if devices:
        states = jax.device_put(states, devices[0])
    return reduce_states(states)


# Mesh programs are memoized the same way the segment fold is: the program
# depends only on (mesh, axes, grid config, corpus size, tree structures) —
# data arrives as call arguments — so two ShardedLexicalSessions over the
# same resident corpus, or a rebuilt session after a service restart, share
# one traced shard_map program instead of compiling their own. FIFO-bounded
# like the fold cache (sessions hold their own reference, so eviction only
# forgets, never breaks).
_MESH_CACHE: dict[tuple, Callable] = {}
_MESH_CACHE_MAX = 64
_MESH_CACHE_LOCK = threading.Lock()


def search_mesh(
    mesh: Mesh,
    queries: Any,
    docs: Any,
    scorers: Sequence[Scorer] | Scorer,
    *,
    k: int,
    chunk_size: int,
    stats: CollectionStats | None = None,
    axis_names: tuple[str, ...] | None = None,
    use_kernel: bool = False,
    tuning: TuningConfig | None = None,
):
    """Full MIREX job as one XLA program: ``shard_map`` over the mesh.

    Corpus sharded over ``axis_names`` (default: every mesh axis — the
    logical "scan" axis), queries and stats replicated; each shard runs
    :func:`map_shard` (multi-model, kernel-dispatched), then the
    hierarchical lexicographic reduce replicates the merged state.

    Returns a jitted ``(queries, docs, stats) -> TopKState`` with stacked
    ``[n_models, n_q, k]`` shapes (``n_models == 1`` for a single scorer —
    callers index ``[0]`` or keep the grid axis). The returned program is
    memoized on (mesh, axes, grid config, corpus size, pytree structures):
    ``queries``/``docs``/``stats`` here are *prototypes* — only their tree
    structure (and the corpus row count, which fixes shard id offsets) is
    baked in, so equal-config callers get the same compiled program.
    """
    scorers = (scorers,) if isinstance(scorers, Scorer) else tuple(scorers)
    if axis_names is None:
        axis_names = mesh_scan_axes(mesh)
    cfg = tune_config.resolve(tuning)
    n_docs_total = jax.tree.leaves(docs)[0].shape[0]
    cache_key = (
        mesh,
        tuple(axis_names),
        _scorer_key(scorers),
        k,
        chunk_size,
        bool(use_kernel),
        cfg.fold_key(bool(use_kernel)),
        n_docs_total,
        jax.tree.structure(queries),
        jax.tree.structure(docs),
        None if stats is None else jax.tree.structure(stats),
    )
    with _MESH_CACHE_LOCK:
        cached = _MESH_CACHE.get(cache_key)
    if cached is not None:
        return cached
    doc_spec = P(axis_names)  # shard the leading (document) dim
    docs_specs = jax.tree.map(lambda _: doc_spec, docs)
    q_specs = jax.tree.map(lambda _: P(), queries)
    stats_specs = None if stats is None else jax.tree.map(lambda _: P(), stats)

    n_shards = 1
    for a in axis_names:
        n_shards *= mesh.shape[a]
    if n_docs_total % n_shards:
        raise ValueError(f"{n_docs_total} docs not divisible by {n_shards} shards")
    per_shard = n_docs_total // n_shards

    def local_job(queries, docs, stats):
        # global shard index = flattened index over the sharding axes
        idx = 0
        for a in axis_names:
            idx = idx * compat.axis_size(a) + jax.lax.axis_index(a)
        state = map_shard(
            queries,
            docs,
            scorers,
            k=k,
            chunk_size=chunk_size,
            stats=stats,
            doc_id_offset=idx * per_shard,
            use_kernel=use_kernel,
            tuning=cfg,
        )
        return topk.merge_across_lex(state, axis_names)

    sharded = jax.shard_map(
        local_job,
        mesh=mesh,
        in_specs=(q_specs, docs_specs, stats_specs),
        out_specs=topk.TopKState(P(), P()),
        check_vma=False,
    )
    fn = jax.jit(sharded)
    with _MESH_CACHE_LOCK:
        # first builder wins (a concurrent builder's fn is equivalent)
        fn = _fifo_insert(_MESH_CACHE, cache_key, fn, _MESH_CACHE_MAX)
    return fn
