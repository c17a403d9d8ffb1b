"""Open-loop requests through ``RetrievalService`` over a collection sharded
on a mesh of chips, on the wall clock.

The configuration names the mesh (``mesh`` over ``mesh_axes``); the corpus
is sharded over all its axes. Set-up draws each chip's share of the tokens,
lengths and dense vectors on that chip from the seed, so that no device ever
holds the whole collection; builds a ``ShardedLexicalSession`` (the sharded
statistics job over the placed shards) and a ``ShardedDenseSession`` over
those arrays as they lie, behind a ``RetrievalService`` with the default
``TuningConfig`` and no admission controller or adaptive policy; draws the
window's requests and due times from the seed (a lexical query's terms from
a passage's own positions, gathered from the chip that holds it); and warms
every bucket shape of the cell's own kind. The window, the sample of
compared requests and the surface (``setup``, ``serve_window``, ``window``,
``finish``, ``bucket_ladder``, ``TuningConfig``, ``State``) are
``drivers/serve.py``'s, so ``run.py`` and ``sweep.py`` run this driver
unchanged. In a traced run the program's tracer is on from set-up, so the
sessions' ``session.place`` and ``session.stats`` spans join the run's spans.

``correct``: lexical requests are compared with ``references/lexical.py``
over the whole token matrix, gathered to the host once the service is
released; dense requests with ``references/dense.py`` run on each chip over
that chip's shard, the shards' best merged on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from chipbench import compare, data, schedules, spec
from chipbench.harness import Check, Run
from repro import obs
from repro.serve import RetrievalService
from repro.serve.session import ShardedDenseSession, ShardedLexicalSession

serve = spec.Layout().module("drivers", "serve")
State = serve.State
TuningConfig = serve.TuningConfig
bucket_ladder = serve.bucket_ladder
serve_window = serve.serve_window


def make_mesh(cfg: dict) -> Mesh:
    shape = tuple(cfg["mesh"])
    devices = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, tuple(cfg["mesh_axes"]))


def draw_on_mesh(mesh: Mesh, key: jax.Array, draw):
    """``draw(key)`` on every device of ``mesh`` with the key folded in by
    the device's shard index; the results, doc-sharded over all the mesh's
    axes, as one global array (or tuple of them)."""
    axes = mesh.axis_names

    def local(key):
        idx = 0
        for a in axes:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return draw(jax.random.fold_in(key, idx))

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(axes), check_vma=False)
    return jax.jit(fn)(key)


def shards_of(arr: jax.Array) -> list[tuple[int, jax.Array]]:
    """``(first row, the device's rows)`` of a doc-sharded array, in row order."""
    rows = {(s.index[0].start or 0): s.data for s in arr.addressable_shards}
    return sorted(rows.items(), key=lambda item: item[0])


def rows_of(arr: jax.Array, docs: np.ndarray) -> np.ndarray:
    """``arr[docs]`` on the host, each row fetched from the device holding it."""
    out = np.empty((len(docs), *arr.shape[1:]), arr.dtype)
    for lo, shard in shards_of(arr):
        sel = np.nonzero((docs >= lo) & (docs < lo + shard.shape[0]))[0]
        if len(sel):
            out[sel] = np.asarray(shard[docs[sel] - lo])
    return out


def lexical_queries(tokens, lengths, counts, slots, rng) -> np.ndarray:
    """``data.lexical_queries`` over a corpus on the mesh: only the drawn
    passages' rows come to the host."""
    n = len(counts)
    docs = rng.integers(0, tokens.shape[0], size=n)
    rows, lens = rows_of(tokens, docs), rows_of(lengths, docs)
    pos = (rng.random((n, slots)) * lens[:, None]).astype(np.int64)
    terms = rows[np.arange(n)[:, None], pos]
    return np.where(np.arange(slots)[None, :] < counts[:, None], terms, data.PAD).astype(np.int32)


def build(run: Run):
    """The deployment and the window's requests, without warming."""
    cfg, traffic = run.config, run.traffic
    if "matmul_precision" in cfg:  # the precision the deployment's scores are stated in
        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    mesh = make_mesh(cfg)
    per_chip, (min_len, pad) = cfg["n_docs"] // mesh.size, cfg["doc_len"]
    # data's one-device draws, run on each chip for its own share
    tokens, lengths = draw_on_mesh(
        mesh, data.key_of(run.seed, 1),
        lambda key: data._corpus(
            key, n_docs=per_chip, pad=pad, min_len=min_len, vocab=cfg["vocab"],
            alpha=float(cfg["zipf_alpha"]),
        ),
    )
    vectors = draw_on_mesh(
        mesh, data.key_of(run.seed, 2),
        lambda key: data._vectors(key, n=per_chip, dim=cfg["dim"]),
    )
    sessions = {
        "lexical": ShardedLexicalSession(
            mesh, tokens, lengths, cfg["lexical_model"], k=cfg["k"],
            chunk_size=cfg["chunk_size"], vocab=cfg["vocab"], use_kernel=None,
        ),
        "dense": ShardedDenseSession(
            mesh, vectors, k=cfg["k"], chunk_size=cfg["chunk_size"], use_kernel=True
        ),
    }
    service = RetrievalService(sessions, tuning=TuningConfig())
    rng = data.rng_of(run.seed, 5)
    due = schedules.arrivals(traffic, run.seconds, rng)
    kind = traffic["kind"]
    terms = None
    if kind == "lexical":
        terms = data.term_counts(len(due), *cfg["query_terms"], rng)
        queries = lexical_queries(tokens, lengths, terms, cfg["query_slots"], rng)
    else:
        queries = data.dense_queries(len(due), cfg["dim"], rng)
    return State(
        service=service, kind=kind, tokens=tokens, lengths=lengths,
        vectors=vectors, due=due, queries=queries, terms=terms,
    )


def setup(run: Run):
    tracer = obs.Tracer(max_events=4_000_000) if run.trace else None
    prev = obs.install(tracer) if tracer is not None else None
    try:
        st = build(run)
        serve.warm(st)
    finally:
        if prev is not None:
            obs.install(*prev)
            run.records["setup_spans"] = tracer.events()
    return st


def window(run: Run, st) -> None:
    serve.window(run, st)
    run.spans = run.records.pop("setup_spans", []) + run.spans


def _merge_best(parts, k: int) -> tuple[np.ndarray, np.ndarray]:
    """The ``k`` best ``(ids, scores)`` a query over the shards' best lists."""
    ids = np.concatenate([p[0] for p in parts], axis=1)
    scores = np.concatenate([p[1] for p in parts], axis=1)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(ids, order, axis=1), np.take_along_axis(scores, order, axis=1)


def _finish_dense(run: Run, st, control: bool) -> list[Check]:
    cfg = run.config
    k, n_docs = cfg["k"], cfg["n_docs"]
    picked = serve.sample(run, st)
    ids = np.stack([st.results[j].ids for j in picked]).astype(np.int64)
    scores = np.stack([st.results[j].scores for j in picked])
    q = st.queries[picked]
    ref_mod = run.layout.module("references", "dense")
    refs = [(lo, ref_mod.DenseReference(shard, q)) for lo, shard in shards_of(st.vectors)]

    def ranked(dtype):
        parts = []
        for lo, ref in refs:
            r_ids, r_scores = ref.ranked(k, dtype=dtype)
            parts.append((np.where(r_ids >= 0, r_ids + lo, -1), r_scores))
        return _merge_best(parts, k)

    best = ranked(jnp.float32)[1].astype(np.float64)

    def ref_of(x):
        out = np.full(x.shape, np.nan)
        for lo, ref in refs:
            n = ref.vectors.shape[0]
            mine = (x >= lo) & (x < lo + n)
            out = np.where(mine, ref.scores_of(np.clip(x - lo, 0, n - 1)), out)
        return out

    answers = {"": (ids, scores)}
    if control:
        answers["control."] = ranked(jnp.bfloat16)
    lim = cfg["limits"]["dense"]
    checks = []
    for prefix, (a_ids, a_scores) in answers.items():
        of = ref_of(np.clip(a_ids, 0, n_docs - 1))
        score_gap, rank_gap = compare.gaps(a_ids, a_scores, of, best)
        checks += [
            Check(f"{prefix}bad_ids", float(compare.bad_ids(a_ids, n_docs)), 0.0),
            Check(f"{prefix}score_gap", score_gap, lim["score_gap"]),
            Check(f"{prefix}rank_gap", rank_gap, lim["rank_gap"]),
        ]
    run.note(f"compared {len(picked)} requests of {len(st.results)} answered, shard by shard")
    return checks


def finish(run: Run, st, control: bool = False) -> list[Check]:
    st.service = None  # the system's state goes before the reference runs
    if st.kind == "lexical":
        st.vectors = None
        # the whole collection to the host: drivers/serve.py's comparison from here
        st.tokens, st.lengths = np.asarray(st.tokens), np.asarray(st.lengths)
        return serve.finish(run, st, control=control)
    st.tokens = st.lengths = None
    return _finish_dense(run, st, control)
