"""Resident-corpus sessions: the corpus lives on device, queries stream by.

A session owns one scorer kind's device-resident state — token matrices +
collection statistics for lexical scans, the vector matrix for dense scans —
plus a jitted scan handler. The handler is traced once per padded batch
bucket (``jax.jit`` caches by shape; the microbatcher's power-of-two
buckets bound the number of traces), so steady-state serving never
recompiles. This is the paper's "keep the collection on the cluster,
ship only queries and top-k back" discipline, with HBM as the cluster —
and with a real mesh as the cluster for :class:`ShardedLexicalSession`,
which keeps the corpus resident *sharded* and reduces every microbatch
through the `repro.cluster` merge contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import cluster
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


class ShardedLexicalSession:
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
        tokens: np.ndarray,
        lengths: np.ndarray,
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
        self.mesh = mesh
        if axis_names is None:
            axis_names = cluster.mesh_scan_axes(mesh)
        self.axis_names = axis_names
        # the plan validates the geometry (equal chunk-aligned shards over
        # the scan axes) even though placement is by NamedSharding here
        self.plan = cluster.plan_for_mesh(
            mesh, int(np.asarray(tokens).shape[0]), chunk_size=chunk_size,
            axis_names=axis_names,
        )
        doc_sharding = NamedSharding(mesh, P(axis_names))
        repl = NamedSharding(mesh, P())
        if stats is None and vocab is None:
            raise ValueError("need stats or vocab to derive collection statistics")
        if vocab is None:
            vocab = int(np.shape(stats.cf)[0])

        def place(tree):
            # host -> each device's own shard: both corpus leaves (packed or
            # not) share the doc leading dim, so one PartitionSpec places
            # either representation, and no device stages the whole corpus
            return jax.tree.map(lambda x: jax.device_put(x, doc_sharding), tree)

        self._docs = place(_pack_resident(tokens, lengths, vocab=vocab, mode=token_pack))
        if stats is None:
            raw_docs = self._docs
            if isinstance(raw_docs, packing.PackedCorpus):
                raw_docs = place(_pack_resident(tokens, lengths, vocab=vocab, mode="none"))
            stats = _sharded_stats(
                mesh, axis_names, raw_docs, vocab=vocab, chunk_size=chunk_size
            )
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
            axis_names=axis_names,
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
        state = self._fn(
            jnp.asarray(q_block, jnp.int32), self._docs, self._stats
        )
        # one scorer -> drop the grid axis: service rows are [n_q, k]
        return jax.block_until_ready(
            topk.TopKState(scores=state.scores[0], ids=state.ids[0])
        )


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
