"""Mergeable top-k state — the MIREX *combiner*.

The paper's reducer/combiner keeps a ranked list of at most ``k`` (doc, score)
pairs per query; because the state is associative+commutative to merge, it can
be maintained per machine (combiner), per chunk (streaming scan), or per mesh
shard, and merged cheaply. At most ``k`` entries per query ever cross the
network — the paper's central communication bound — which here becomes "at
most ``k`` entries per query enter the all-gather".
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro import compat

NEG_INF = float("-inf")
REDUCE_SCOPE = "mesh_reduce"  # the name the mesh reduce's ops carry


class TopKState(NamedTuple):
    """Running top-k of (score, id) pairs, sorted descending by score.

    Shapes: ``scores [..., k]`` float, ``ids [..., k]`` int32. Empty slots have
    score ``-inf`` and id ``-1``.
    """

    scores: jax.Array
    ids: jax.Array

    @property
    def k(self) -> int:
        return self.scores.shape[-1]


def init(k: int, batch_shape: tuple = (), dtype=jnp.float32) -> TopKState:
    """Fresh state with no entries."""
    return TopKState(
        scores=jnp.full((*batch_shape, k), NEG_INF, dtype=dtype),
        ids=jnp.full((*batch_shape, k), -1, dtype=jnp.int32),
    )


def init_host(k: int, batch_shape: tuple = ()) -> TopKState:
    """:func:`init` as host (numpy) arrays — same sentinel contract, zero
    device dispatches. Concurrent shard executors build their fresh states
    with this and ship them in one batched ``device_put``, instead of
    serializing eager ``full`` ops through the dispatch path."""
    import numpy as np

    return TopKState(
        scores=np.full((*batch_shape, k), NEG_INF, np.float32),
        ids=np.full((*batch_shape, k), -1, np.int32),
    )


def valid_mask(state: TopKState) -> jax.Array:
    """Boolean mask of occupied slots (corpus smaller than k leaves empties).

    Empty slots carry ``(-inf, -1)`` sentinels; run-file writers and eval
    must drop them rather than rank a nonexistent document.
    """
    return (state.ids >= 0) & (state.scores > NEG_INF)


def update(state: TopKState, cand_scores: jax.Array, cand_ids: jax.Array) -> TopKState:
    """Fold a block of candidates into the state (the combiner step).

    ``cand_scores [..., m]``, ``cand_ids [..., m]``. Cost is one
    ``top_k(k+m → k)`` — independent of how many candidates were seen before.
    """
    all_scores = jnp.concatenate([state.scores, cand_scores.astype(state.scores.dtype)], axis=-1)
    all_ids = jnp.concatenate([state.ids, cand_ids.astype(jnp.int32)], axis=-1)
    top_scores, pos = jax.lax.top_k(all_scores, state.k)
    top_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
    return TopKState(scores=top_scores, ids=top_ids)


def merge(a: TopKState, b: TopKState) -> TopKState:
    """Associative merge of two states (reduce step)."""
    return update(a, b.scores, b.ids)


def merge_lex(a: TopKState, b: TopKState) -> TopKState:
    """k-bounded **lexicographic** merge — the cluster reduce contract.

    Both inputs must be sorted by (score desc, id asc), which every fold in
    this framework produces (``lax.top_k``'s positional tie-break over a
    monotone-id candidate stream *is* that order; the Pallas combiner sorts
    by it explicitly). The merge is one O(k log k) bitonic merge network
    (`kernels.score_topk.bitonic_merge_desc`), so its output is a pure
    function of the two value sets — no positional tie-break, no dependence
    on merge order or shard count. That value-determinism is what makes
    cross-shard rankings id-exact (and score-byte-exact) against a
    single-host oracle scan, which `repro.cluster` turns into the
    shard-count-invariance guarantee for merged TREC run files.

    Inputs are right-padded to a power-of-two width with ``(-inf, -1)``
    empty slots; a fold-produced state never holds a real-id entry at
    ``-inf`` (sentinels win that tie in both the host fold and the kernel
    combiner), so the padding preserves (score desc, id asc) sortedness.
    """
    # local import: core stays importable when the Pallas toolchain is absent
    from repro.kernels.score_topk import bitonic_merge_desc, pad_lanes

    if a.scores.shape != b.scores.shape:
        raise ValueError(f"merge_lex shape mismatch: {a.scores.shape} != {b.scores.shape}")
    k = a.k
    width = 1 if k <= 1 else 1 << (k - 1).bit_length()  # next pow2
    a_s, a_i = pad_lanes(a.scores, a.ids, width)
    b_s, b_i = pad_lanes(b.scores, b.ids, width)
    s, i = bitonic_merge_desc(a_s, a_i, b_s, b_i)
    return TopKState(scores=s[..., :k], ids=i[..., :k])


def reduce_lex(states) -> TopKState:
    """Fold any number of per-shard states through :func:`merge_lex`.

    Associative + value-deterministic, so grouping and shard order are free
    to vary (host loop, mesh all-gather, tree) without changing a bit of the
    result.
    """
    states = list(states)
    if not states:
        raise ValueError("reduce_lex needs at least one state")
    out = states[0]
    for s in states[1:]:
        out = merge_lex(out, s)
    return out


def merge_across_lex(state: TopKState, axis_name: str | tuple[str, ...]) -> TopKState:
    """Global lexicographic reduce across mesh axes (inside ``shard_map``).

    Same hierarchical staging as :func:`merge_across` (one stage per axis,
    re-reducing to k between stages, bounding the gather buffer at
    ``axis_size·k``), but folding with :func:`merge_lex` so the mesh reduce
    and the host-loop reduce (`repro.cluster`) share one merge contract.

    The gathers and merges are named :data:`REDUCE_SCOPE` twice: a
    ``jax.named_scope`` (each op's ``op_name``) and an XLA frontend attribute
    ``mirex_scope``, which a TPU device trace prints in each op's name, so a
    trace reduction finds the cross-chip reduce by name.
    """
    axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) else (axis_name,)
    with jax.named_scope(REDUCE_SCOPE), set_xla_metadata(mirex_scope=REDUCE_SCOPE):
        for a in axes:
            state = _merge_stage_lex(state, a)
    return state


def _merge_stage_lex(state: TopKState, axis_name: str) -> TopKState:
    gathered = TopKState(
        scores=jax.lax.all_gather(state.scores, axis_name, axis=0, tiled=False),
        ids=jax.lax.all_gather(state.ids, axis_name, axis=0, tiled=False),
    )
    n = gathered.scores.shape[0]
    return reduce_lex(
        TopKState(scores=gathered.scores[i], ids=gathered.ids[i]) for i in range(n)
    )


def merge_across(
    state: TopKState, axis_name: str | tuple[str, ...], *, method: str = "staged"
) -> TopKState:
    """Global reduce: merge per-shard states across mesh axes.

    Implements the paper's shuffle with its communication bound intact: each
    shard contributes exactly ``k`` entries per query. Inside ``shard_map``.

    Beyond-paper scaling fix: the paper's single-stage merge (every machine's
    k to one reducer) works at 15 machines but at 512 shards the gather
    buffer is ``n_shards·k`` per query (21 GiB for scan_5kq on the 2-pod
    mesh). A tuple of axes is therefore merged **hierarchically** — one
    stage per mesh axis, re-reducing to k between stages — bounding the peak
    buffer at ``max(axis_size)·k`` per query. Associativity of the combiner
    (test_topk) is exactly what makes the staging legal.
    """
    if isinstance(axis_name, (tuple, list)):
        for a in axis_name:
            state = merge_across(state, a, method=method)
        return state
    if method == "tree":
        return merge_across_tree(state, axis_name)
    gathered_scores = jax.lax.all_gather(state.scores, axis_name, axis=-2, tiled=False)
    gathered_ids = jax.lax.all_gather(state.ids, axis_name, axis=-2, tiled=False)
    # [..., n_shards, k] -> [..., n_shards*k]
    flat_scores = gathered_scores.reshape(*gathered_scores.shape[:-2], -1)
    flat_ids = gathered_ids.reshape(*gathered_ids.shape[:-2], -1)
    top_scores, pos = jax.lax.top_k(flat_scores, state.k)
    top_ids = jnp.take_along_axis(flat_ids, pos, axis=-1)
    return TopKState(scores=top_scores, ids=top_ids)


def merge_across_tree(state: TopKState, axis_name: str) -> TopKState:
    """Log-depth tree merge via ``collective_permute`` (recursive halving).

    Communication-optimal alternative to :func:`merge_across` when ``k`` is
    large: each round exchanges ``k`` entries and immediately re-reduces to
    ``k``, so peak per-link traffic is ``k`` instead of ``n_shards * k``.
    Requires the axis size to be a power of two. All shards end with the
    global state (butterfly/all-reduce pattern).
    """
    n = compat.axis_size(axis_name)
    if n & (n - 1):
        raise ValueError(f"tree merge requires power-of-two axis size, got {n}")
    idx = jax.lax.axis_index(axis_name)
    step = 1
    while step < n:
        partner = idx ^ step
        perm = [(i, i ^ step) for i in range(n)]
        other = TopKState(
            scores=jax.lax.ppermute(state.scores, axis_name, perm),
            ids=jax.lax.ppermute(state.ids, axis_name, perm),
        )
        del partner
        state = merge(state, other)
        step <<= 1
    return state


@functools.partial(jax.jit, static_argnames=("k",))
def topk_dense(scores: jax.Array, k: int) -> TopKState:
    """One-shot top-k over a dense score row (utility for baselines/tests)."""
    top_scores, ids = jax.lax.top_k(scores, k)
    return TopKState(scores=top_scores, ids=ids.astype(jnp.int32))
