"""Fused streaming score + top-k Pallas kernel — MIREX map+combine in VMEM.

The paper's hot loop scores every query against a stream of documents and
keeps a running top-k. On TPU that is: stream document blocks HBM→VMEM, hit
the MXU with a ``[n_q, dim] × [dim, block_d]`` tile, and fold the block's
scores into a resident ``[n_q, W]`` top-k state — the full ``[n_q, n_d]``
score matrix never exists, so HBM traffic is ``O(n_d · dim)`` instead of
``O(n_q · n_d)``. The TPU grid executes sequentially, which is exactly the
combiner semantics: the output refs double as the running state.

Combiner fold (:func:`fold_block`): the resident state is kept sorted by
(score desc, id asc) at a power-of-two width ``W >= k`` (at least one lane
tile, 128). Each block is bitonic-sorted *ascending* in VMEM, so the state
and the block's best ``W`` form a bitonic sequence without any reversal; one
half-cleaner keeps the better of each lane pair and ``log2(W)`` more
compare-exchange stages re-sort it. Every stage is a lane permutation —
``pltpu.roll`` by the stride plus a select on ``lane & stride`` — and a
lexicographic compare on the VPU: no ``top_k``, no gathers, no lane-splitting
reshapes, so Mosaic lowers it at every stride. The same network, with XLA's
lowering of the roll, is the cross-shard reduce (`topk.merge_lex`), so the
kernel combiner and the cluster merge share one ordering contract.

The fold walks the query rows ``ROWS`` (one sublane tile) at a time, so a
step's sort and merge stay in vector registers and compile once, not once
per row tile.

BlockSpecs: Q ``(n_q, dim)`` resident across steps (padded to whole row
tiles); D ``(block_d, dim)`` streamed; outputs ``(n_q, W)`` pinned to block
(0, 0) and cut to ``(n_q, k)`` by the wrapper. Compiled alignment wants
``dim % 128 == 0`` and ``block_d % 128 == 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pipeline import next_pow2

LANES = 128  # one vreg row: the narrowest width the compiled network runs at
ROWS = 8  # query rows folded per loop step: one sublane tile, held in vregs


def state_width(k: int) -> int:
    """Lane width of the resident top-k state: a power of two, >= k, >= 128."""
    return max(next_pow2(k), LANES)


def _better(s, i, ps, pi):
    """(s, i) ranks ahead of (ps, pi) under (score desc, id asc)."""
    return (s > ps) | ((s == ps) & (i < pi))


def _exchange(s, i, stride: int, take_max, roll):
    """One compare-exchange stage along the last axis.

    Lane ``j`` meets lane ``j ^ stride``: a roll by ``stride`` brings the
    lower partner to the upper lane, a roll by ``n - stride`` the upper
    partner to the lower lane, and ``lane & stride`` picks which one applies.
    ``take_max`` marks the lanes that keep the better entry of their pair.
    ``roll`` is ``pltpu.roll`` inside kernels and ``jnp.roll`` outside (the
    two agree; only the first lowers to Mosaic, only the second runs eagerly).
    """
    n = s.shape[-1]
    axis = s.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
    upper = (lane & stride) != 0
    ps = jnp.where(upper, roll(s, stride, axis), roll(s, n - stride, axis))
    pi = jnp.where(upper, roll(i, stride, axis), roll(i, n - stride, axis))
    keep = _better(s, i, ps, pi) == take_max
    return jnp.where(keep, s, ps), jnp.where(keep, i, pi)


def bitonic_sort(s, i, *, descending: bool, roll=pltpu.roll):
    """Sort ``[..., n]`` (score, id) pairs along the last axis by (score
    desc, id asc), or its exact reverse; ``n`` must be a power of two."""
    n = s.shape[-1]
    assert n & (n - 1) == 0, f"bitonic sort needs power-of-two width, got {n}"
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    size = 2
    while size <= n:
        # runs of `size` alternate direction, so each pair of runs is bitonic
        # for the next phase; the last phase (size == n) runs one direction
        run_desc = ((lane & size) == 0) == descending
        stride = size // 2
        while stride:
            s, i = _exchange(s, i, stride, ((lane & stride) == 0) == run_desc, roll)
            stride //= 2
        size *= 2
    return s, i


def bitonic_top(a_s, a_i, b_s, b_i, *, roll=pltpu.roll):
    """Top ``m`` of two ``[..., m]`` lists, ``a`` sorted (score desc, id asc)
    and ``b`` sorted the exact reverse way; ``m`` a power of two.

    ``a ++ b`` is bitonic, so its half-cleaner (lane ``j`` against lane
    ``j + m``, i.e. ``a[j]`` against ``b[j]``) leaves the ``m`` best entries,
    themselves bitonic, in the lower half; ``log2(m)`` stages sort them.
    """
    m = a_s.shape[-1]
    assert m & (m - 1) == 0, f"bitonic merge needs power-of-two width, got {m}"
    keep = _better(a_s, a_i, b_s, b_i)
    s, i = jnp.where(keep, a_s, b_s), jnp.where(keep, a_i, b_i)
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    stride = m // 2
    while stride:
        s, i = _exchange(s, i, stride, (lane & stride) == 0, roll)
        stride //= 2
    return s, i


def bitonic_merge_desc(
    a_s: jax.Array, a_i: jax.Array, b_s: jax.Array, b_i: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Merge two ``[..., m]`` (score, id) lists sorted by (score desc, id
    asc); keep the top m under that same lexicographic order.

    Ties break toward the **smaller id**. Scan candidates carry strictly
    increasing doc ids across stream blocks, so this is exactly
    ``lax.top_k``'s positional tie-break on the host fold (`topk.update`) —
    what keeps kernel and host rankings id-exact even on the equal scores
    lexical scoring mass-produces (e.g. every zero-match document under
    BM25). The order is total on distinct ids, so the output is a pure
    function of the two value sets. ``m`` must be a power of two (pad with
    ``-inf``/``-1`` first). Outside kernels only: the reverse of ``b`` is an
    XLA ``rev``.
    """
    return bitonic_top(a_s, a_i, b_s[..., ::-1], b_i[..., ::-1], roll=jnp.roll)


def pad_lanes(s, i, width: int, *, front: bool = False):
    """Pad the last axis to ``width`` with ``(-inf, -1)`` empty slots."""
    pad = width - s.shape[-1]
    if pad == 0:
        return s, i
    fill_s = jnp.full((*s.shape[:-1], pad), -jnp.inf, s.dtype)
    fill_i = jnp.full((*i.shape[:-1], pad), -1, i.dtype)
    if front:
        return (jnp.concatenate([fill_s, s], axis=-1),
                jnp.concatenate([fill_i, i], axis=-1))
    return (jnp.concatenate([s, fill_s], axis=-1),
            jnp.concatenate([i, fill_i], axis=-1))


def fold_block(state_s, state_i, s, ids):
    """Fold one ``[rows, block_d]`` block of candidates into a ``[rows, W]``
    state sorted by (score desc, id asc); returns the new state.

    The block is sorted ascending at a power-of-two width of at least one
    lane tile; its best ``W`` (the last lanes) then meet the state in
    :func:`bitonic_top`. Empty slots are ``(-inf, -1)``.
    """
    width = state_s.shape[-1]
    bp = max(next_pow2(s.shape[-1]), LANES)
    s, ids = pad_lanes(s, ids, bp)
    s, ids = bitonic_sort(s, ids, descending=False)
    if bp > width:
        s, ids = s[:, bp - width:], ids[:, bp - width:]
    else:
        s, ids = pad_lanes(s, ids, width, front=True)
    return bitonic_top(state_s, state_i, s, ids)


def _score_topk_kernel(q_ref, d_ref, out_s_ref, out_i_ref, s_ref, *, block_d: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_s_ref[...] = jnp.full(out_s_ref.shape, -jnp.inf, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    s_ref[...] = jax.lax.dot_general(
        q_ref[...], d_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [n_q, block_d] on the MXU

    def fold_rows(g, carry):
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        s = s_ref[rows, :]
        ids = step * block_d + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        out_s_ref[rows, :], out_i_ref[rows, :] = fold_block(
            out_s_ref[rows, :], out_i_ref[rows, :], s, ids
        )
        return carry

    jax.lax.fori_loop(0, s_ref.shape[0] // ROWS, fold_rows, 0)


def pad_rows(x, multiple: int = 8, value=0):
    """Pad the leading axis up to a multiple of ``multiple`` rows."""
    pad = -x.shape[0] % multiple
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=value)


def score_topk_pallas(
    q: jax.Array,  # [n_q, dim]
    d: jax.Array,  # [n_d, dim]
    *,
    k: int,
    block_d: int = 1024,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    n_q, dim = q.shape
    n_d, _ = d.shape
    assert n_d % block_d == 0, (n_d, block_d)
    q = pad_rows(q, ROWS)
    n_rows = q.shape[0]
    width = state_width(k)
    kernel = functools.partial(_score_topk_kernel, block_d=block_d)
    scores, ids = pl.pallas_call(
        kernel,
        grid=(n_d // block_d,),
        in_specs=[
            pl.BlockSpec((n_rows, dim), lambda i: (0, 0)),  # Q resident in VMEM
            pl.BlockSpec((block_d, dim), lambda i: (i, 0)),  # D streamed
        ],
        out_specs=[
            pl.BlockSpec((n_rows, width), lambda i: (0, 0)),
            pl.BlockSpec((n_rows, width), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, width), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, width), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n_rows, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="score_topk",  # the kernel's name in device traces
    )(q, d)
    return scores[:n_q, :k], ids[:n_q, :k]
