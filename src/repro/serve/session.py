"""Resident-corpus sessions: the corpus lives on device, queries stream by.

A session owns one scorer kind's device-resident state — token matrices +
collection statistics for lexical scans, the vector matrix for dense scans —
plus a jitted scan handler. The handler is traced once per padded batch
bucket (``jax.jit`` caches by shape; the microbatcher's power-of-two
buckets bound the number of traces), so steady-state serving never
recompiles. This is the paper's "keep the collection on the cluster,
ship only queries and top-k back" discipline, with HBM as the cluster —
and with a real mesh as the cluster for :class:`ShardedLexicalSession` and
:class:`ShardedDenseSession`, which keep the corpus resident *sharded* and
reduce every microbatch through the `repro.cluster` merge contract.

The sharded sessions record, in the active `repro.obs` tracer, a
``session.place`` span (``shards``, ``bytes_per_chip``) when they lay their
corpus on the mesh, a ``session.stats`` span for the sharded statistics
job, and a ``session.mesh_search`` span a block (``shards``, ``rows``,
``gather_bytes``); nothing while tracing is off.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import cluster, obs
from repro.core import anchors, packing, scan, topk
from repro.core.scoring import PAD_TOKEN, CollectionStats, Scorer, get_scorer
from repro.tune import config as tune_config


def _pack_resident(tokens, lengths, *, vocab: int | None, mode: str | None):
    """Resolve the resident corpus representation for a lexical session.

    ``mode=None`` follows the active tuning's ``token_pack`` knob. Returns
    host arrays — the plain int32 ``(tokens, lengths)`` tuple, or a
    ``PackedCorpus`` holding the narrow representation — which the session
    then places on its device(s): resident HBM drops by the pack ratio, so
    bigger corpora fit resident, and the scan decodes per chunk/tile with
    bit-identical results. Packing needs the vocab (for the sentinel);
    without one we stay unpacked rather than fail.
    """
    if mode is None:
        mode = tune_config.active().config.token_pack
    t32 = np.asarray(tokens, np.int32)
    l32 = np.asarray(lengths, np.int32)
    if mode == "none" or vocab is None:
        return (t32, l32)
    return packing.pack_corpus(t32, l32, vocab=vocab, mode=mode)


def _sharded_stats(mesh, axis_names, docs, *, vocab: int, chunk_size: int):
    """The statistics job on a shard-resident corpus: each device folds its
    own rows and the additive states ``psum`` across the scan axes, so no
    device ever holds the whole corpus. Integer sums, so the result equals
    the single-host job's bit for bit."""
    spec = P(axis_names)

    def local(tokens, lengths):
        return anchors.collection_stats(
            tokens, lengths, vocab=vocab, chunk_size=chunk_size, axis_name=axis_names
        )

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec), out_specs=P(), check_vma=False
    )
    return jax.jit(fn)(*docs)


def _place_on_mesh(x, sharding: NamedSharding, dtype) -> jax.Array:
    """One corpus leaf laid out doc-sharded on the mesh: a host array goes to
    each device as its own shard (no device stages the whole), and a
    ``jax.Array`` already laid out with ``sharding`` is used as it is, with
    no host round trip and no copy."""
    if isinstance(x, jax.Array):
        x = jax.device_put(x, sharding)
        return x if x.dtype == dtype else x.astype(dtype)
    return jax.device_put(np.asarray(x, dtype), sharding)


def _pack_placed(docs, *, vocab: int, mode: str | None):
    """The resident representation :func:`_pack_resident` picks for the same
    vocab and knob, from int32 ``(tokens, lengths)`` already on the mesh:
    packed on the devices that hold each shard."""
    if mode is None:
        mode = tune_config.active().config.token_pack
    tokens, lengths = docs
    spec = None if mode == "none" else packing.make_spec(vocab, tokens.shape[1], mode)
    if spec is None:
        return docs
    return packing.PackedCorpus(packing.pack_tokens_device(tokens, spec), lengths, spec)


def _gather_bytes(mesh: Mesh, axis_names: tuple[str, ...], rows: int, k: int) -> int:
    """Bytes of the gathered ``[axis_size, rows, k]`` float32 score and int32
    id buffers that `topk.merge_across_lex` fills on each device, summed over
    its stages (one per scan axis longer than 1)."""
    return sum(mesh.shape[a] for a in axis_names if mesh.shape[a] > 1) * rows * k * 8


class _MeshSearch:
    """What both sharded sessions share: the scan axes, the shard plan, the
    doc sharding, and ``search`` through the memoized mesh program."""

    def _init_mesh(self, mesh: Mesh, n_docs: int, chunk_size: int, axis_names) -> None:
        self.mesh = mesh
        if axis_names is None:
            axis_names = cluster.mesh_scan_axes(mesh)
        self.axis_names = tuple(axis_names)
        # the plan validates the geometry (equal chunk-aligned shards over
        # the scan axes) even though placement is by NamedSharding here
        self.plan = cluster.plan_for_mesh(
            mesh, n_docs, chunk_size=chunk_size, axis_names=self.axis_names
        )
        self._doc_sharding = NamedSharding(mesh, P(self.axis_names))

    def _search(self, q: jax.Array, docs, stats) -> topk.TopKState:
        rows = int(q.shape[0])
        with obs.tracer().span(
            "session.mesh_search", "serve", kind=self.kind, shards=self.plan.n_shards,
            rows=rows, gather_bytes=_gather_bytes(self.mesh, self.axis_names, rows, self.k),
        ):
            state = self._fn(q, docs, stats)
            # one scorer -> drop the grid axis: service rows are [n_q, k]
            return jax.block_until_ready(
                topk.TopKState(scores=state.scores[0], ids=state.ids[0])
            )


class LexicalSession:
    """Raw-token scan service state for one lexical scorer (ql_lm/bm25/...).

    The fold path is :func:`repro.core.scan.search_local`'s chunked scan —
    term frequencies recomputed from raw text per block, no index. The tf
    reduction is tiled over document positions on every path, so per-chunk
    memory stays ``O(n_q·L_q·chunk)`` however large the batch grows (the
    serve-path amortization fix: the seed rank-4 form made big batches
    *slower*, inverting claim C1). ``use_kernel=None`` resolves from the
    Pallas backend — the fused lexical kernel where it compiles (TPU), the
    tiled pure-JAX fold elsewhere; pass True/False to force.
    """

    kind = "lexical"
    pad_value = PAD_TOKEN

    def __init__(
        self,
        tokens: np.ndarray,
        lengths: np.ndarray,
        scorer: Scorer | str,
        *,
        k: int,
        chunk_size: int,
        stats: CollectionStats | None = None,
        vocab: int | None = None,
        use_kernel: bool | None = None,
        token_pack: str | None = None,
    ):
        self.scorer = get_scorer(scorer) if isinstance(scorer, str) else scorer
        if self.scorer.kind != "lexical":
            raise ValueError(f"scorer {self.scorer.name!r} is not lexical")
        self.use_kernel = use_kernel  # None = auto-resolve at each (re)trace
        self.k = k
        self.chunk_size = chunk_size
        tokens32 = jnp.asarray(tokens, jnp.int32)
        self._lengths = jnp.asarray(lengths, jnp.int32)
        if tokens32.shape[0] % chunk_size:
            raise ValueError(
                f"{tokens32.shape[0]} docs not divisible by chunk {chunk_size}"
            )
        if stats is None:
            if vocab is None:
                raise ValueError("need stats or vocab to derive collection statistics")
            stats = anchors.collection_stats(
                tokens32, self._lengths, vocab=vocab, chunk_size=chunk_size
            )
        self._stats = jax.tree.map(jnp.asarray, stats)
        # the resident corpus: packed when the knob (argument or active
        # tuning) says so — the int32 matrix then never stays on device,
        # only the narrow representation does. Stats above were computed
        # from the raw tokens, pack-invariantly. The sentinel needs the
        # vocab; derive it from the stats' cf table when not passed.
        if vocab is None:
            vocab = int(self._stats.cf.shape[0])
        self._docs = jax.tree.map(
            jnp.asarray, _pack_resident(tokens, lengths, vocab=vocab, mode=token_pack)
        )

        scorer_, k_, chunk_ = self.scorer, k, chunk_size

        # the corpus and stats are arguments: closed over, they would be
        # compiled into the executable as constants (a second device copy,
        # minutes of compile at full width, a program too big to cache)
        @jax.jit
        def _scan(q, docs, st):
            # resolved at trace time: set_kernel_backend clears jit caches,
            # so a backend flip re-resolves on the next call (ops.py contract)
            kern = use_kernel
            if kern is None:
                from repro.kernels import ops

                kern = ops.kernel_backend() == "compiled"
            return scan.search_local(
                q, docs, scorer_, k=k_, chunk_size=chunk_, stats=st, use_kernel=kern
            )

        self._scan = _scan

    @property
    def n_docs(self) -> int:
        return int(self._lengths.shape[0])

    @property
    def pack_mode(self) -> str:
        """Resolved resident storage: ``none`` or the PackSpec mode."""
        if isinstance(self._docs, packing.PackedCorpus):
            return self._docs.spec.mode
        return "none"

    @property
    def resident_corpus_bytes(self) -> int:
        """Device bytes held by the resident corpus (tokens + lengths)."""
        return packing.tree_nbytes(self._docs)

    def search(self, q_block: np.ndarray) -> topk.TopKState:
        """Scan one padded query block; blocks until results are on host."""
        q = jnp.asarray(q_block, jnp.int32)
        return jax.block_until_ready(self._scan(q, self._docs, self._stats))


class ShardedLexicalSession(_MeshSearch):
    """Shard-resident lexical session: the corpus lives *sharded* on a mesh.

    The paper's cluster as a service: each device holds one contiguous
    corpus shard (placed once at construction via ``NamedSharding`` over the
    scan axes), microbatches of queries are replicated to every shard, each
    shard runs the same map fold as the single-host session
    (`cluster.map_shard`, kernel-dispatched), and shard results reduce
    through the cluster merge contract (`topk.merge_across_lex`) — so a
    sharded session's rankings are bit-identical to the resident single-host
    session's, whatever the mesh shape. Drop-in for ``LexicalSession`` under
    `repro.serve.service.RetrievalService` (same ``kind``/``pad_value``/
    ``search`` surface, same ``[n_q, k]`` result shape).

    ``tokens``/``lengths`` are host arrays, or ``jax.Array`` s already laid
    out doc-sharded over the scan axes (``NamedSharding(mesh,
    P(axis_names))``), which are used where they are: a corpus too large for
    one device or for a trip through the host is drawn or loaded shard by
    shard and handed over. The statistics come from the sharded job over the
    placed shards; the resident representation is the one
    ``LexicalSession`` picks for the same vocab and ``token_pack``.

    The mesh program comes from the shared `cluster.search_mesh` cache
    (memoized on mesh/axes/grid config/corpus size), so a second session
    over the same resident corpus — or one rebuilt after a service restart —
    reuses the already-traced program instead of compiling its own, the same
    compile-once discipline the pipelined scan executor applies to shard
    folds (`cluster.segment_fold`).

    ``use_kernel=None`` resolves from the Pallas backend once, at
    construction (the mesh program is built here, not per call).
    """

    kind = "lexical"
    pad_value = PAD_TOKEN

    def __init__(
        self,
        mesh: Mesh,
        tokens: np.ndarray | jax.Array,
        lengths: np.ndarray | jax.Array,
        scorer: Scorer | str,
        *,
        k: int,
        chunk_size: int,
        stats: CollectionStats | None = None,
        vocab: int | None = None,
        use_kernel: bool | None = None,
        axis_names: tuple[str, ...] | None = None,
        token_pack: str | None = None,
    ):
        self.scorer = get_scorer(scorer) if isinstance(scorer, str) else scorer
        if self.scorer.kind != "lexical":
            raise ValueError(f"scorer {self.scorer.name!r} is not lexical")
        if use_kernel is None:
            from repro.kernels import ops

            use_kernel = ops.kernel_backend() == "compiled"
        self.use_kernel = use_kernel
        self.k = k
        self.chunk_size = chunk_size
        self._init_mesh(mesh, int(np.shape(tokens)[0]), chunk_size, axis_names)
        repl = NamedSharding(mesh, P())
        if stats is None and vocab is None:
            raise ValueError("need stats or vocab to derive collection statistics")
        if vocab is None:
            vocab = int(np.shape(stats.cf)[0])

        n_shards, tr = self.plan.n_shards, obs.tracer()
        with tr.span("session.place", "serve", kind=self.kind, shards=n_shards) as span:
            raw = (
                _place_on_mesh(tokens, self._doc_sharding, np.int32),
                _place_on_mesh(lengths, self._doc_sharding, np.int32),
            )
            self._docs = jax.block_until_ready(
                _pack_placed(raw, vocab=vocab, mode=token_pack)
            )
            span.set(bytes_per_chip=packing.tree_nbytes(self._docs) // n_shards)
        if stats is None:
            with tr.span("session.stats", "serve", shards=n_shards):
                stats = jax.block_until_ready(
                    _sharded_stats(
                        mesh, self.axis_names, raw, vocab=vocab, chunk_size=chunk_size
                    )
                )
        del raw  # the unpacked int32 shards, where the resident corpus is packed
        self._stats = jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), repl), stats)
        self._lengths = (
            self._docs.lengths
            if isinstance(self._docs, packing.PackedCorpus)
            else self._docs[1]
        )

        self._fn = cluster.search_mesh(
            mesh,
            jnp.zeros((1, 1), jnp.int32),  # query prototype: specs need structure only
            self._docs,
            self.scorer,
            k=k,
            chunk_size=chunk_size,
            stats=self._stats,
            axis_names=self.axis_names,
            use_kernel=use_kernel,
        )

    @property
    def n_docs(self) -> int:
        return int(self._lengths.shape[0])

    @property
    def pack_mode(self) -> str:
        """Resolved resident storage: ``none`` or the PackSpec mode."""
        if isinstance(self._docs, packing.PackedCorpus):
            return self._docs.spec.mode
        return "none"

    @property
    def resident_corpus_bytes(self) -> int:
        """Device bytes held by the resident corpus (tokens + lengths)."""
        return packing.tree_nbytes(self._docs)

    def search(self, q_block: np.ndarray) -> topk.TopKState:
        """Scan one padded query block across all shards; blocks until the
        merged (replicated) top-k is on host."""
        return self._search(jnp.asarray(q_block, jnp.int32), self._docs, self._stats)


class ShardedDenseSession(_MeshSearch):
    """Shard-resident dense session: the vector matrix lives *sharded* on a
    mesh, each device scanning its own rows with the same fold as
    ``DenseSession`` (`cluster.map_shard`: the ``score_topk`` kernel under
    ``use_kernel``), and shard results reduce through
    `topk.merge_across_lex`. Each document's score is the same product on
    the same chunk geometry (shards are chunk-aligned) and the merge is
    value-deterministic, so the rankings are bit-identical to a
    ``DenseSession`` over the same vectors, whatever the mesh shape. Drop-in
    for ``DenseSession`` under `repro.serve.service.RetrievalService`.

    ``vectors`` is a host array or a ``jax.Array`` already laid out
    doc-sharded over the scan axes, used where it is (a matrix too large for
    one device is drawn or loaded shard by shard). Products take the matmul
    precision in force for the process when the program is traced, as
    ``DenseSession``'s do.
    """

    kind = "dense"
    pad_value = 0.0

    def __init__(
        self,
        mesh: Mesh,
        vectors: np.ndarray | jax.Array,
        scorer: Scorer | str = "dense_dot",
        *,
        k: int,
        chunk_size: int,
        use_kernel: bool = True,
        axis_names: tuple[str, ...] | None = None,
    ):
        self.scorer = get_scorer(scorer) if isinstance(scorer, str) else scorer
        if self.scorer.kind != "dense":
            raise ValueError(f"scorer {self.scorer.name!r} is not dense")
        self.k = k
        self.chunk_size = chunk_size
        self.use_kernel = use_kernel
        self._init_mesh(mesh, int(np.shape(vectors)[0]), chunk_size, axis_names)
        n_shards = self.plan.n_shards
        with obs.tracer().span(
            "session.place", "serve", kind=self.kind, shards=n_shards
        ) as span:
            self._vectors = jax.block_until_ready(
                _place_on_mesh(vectors, self._doc_sharding, np.float32)
            )
            span.set(bytes_per_chip=int(self._vectors.nbytes) // n_shards)
        self._fn = cluster.search_mesh(
            mesh,
            jnp.zeros((1, self.dim), jnp.float32),  # query prototype
            self._vectors,
            self.scorer,
            k=k,
            chunk_size=chunk_size,
            axis_names=self.axis_names,
            use_kernel=use_kernel,
        )

    @property
    def n_docs(self) -> int:
        return int(self._vectors.shape[0])

    @property
    def dim(self) -> int:
        return int(self._vectors.shape[1])

    def search(self, q_block: np.ndarray) -> topk.TopKState:
        """Scan one padded query block across all shards; blocks until the
        merged (replicated) top-k is on host."""
        return self._search(jnp.asarray(q_block, jnp.float32), self._vectors, None)


class DenseSession:
    """Vector-scan service state; the hot path is the Pallas score+top-k
    kernel (``use_kernel=True``), falling back to the pure-JAX chunked fold.
    """

    kind = "dense"
    pad_value = 0.0

    def __init__(
        self,
        vectors: np.ndarray,
        scorer: Scorer | str = "dense_dot",
        *,
        k: int,
        chunk_size: int,
        use_kernel: bool = True,
    ):
        self.scorer = get_scorer(scorer) if isinstance(scorer, str) else scorer
        if self.scorer.kind != "dense":
            raise ValueError(f"scorer {self.scorer.name!r} is not dense")
        self.k = k
        self.chunk_size = chunk_size
        self.use_kernel = use_kernel
        self._vectors = jnp.asarray(vectors, jnp.float32)
        if self._vectors.shape[0] % chunk_size:
            raise ValueError(
                f"{self._vectors.shape[0]} docs not divisible by chunk {chunk_size}"
            )

        scorer_, k_, chunk_, kern = self.scorer, k, chunk_size, use_kernel

        @jax.jit
        def _scan(q, vecs):  # vectors as an argument, as in LexicalSession
            return scan.search_local(
                q, vecs, scorer_, k=k_, chunk_size=chunk_, use_kernel=kern
            )

        self._scan = _scan

    @property
    def n_docs(self) -> int:
        return int(self._vectors.shape[0])

    @property
    def dim(self) -> int:
        return int(self._vectors.shape[1])

    def search(self, q_block: np.ndarray) -> topk.TopKState:
        q = jnp.asarray(q_block, jnp.float32)
        return jax.block_until_ready(self._scan(q, self._vectors))
