"""Fused Pallas lexical-scan kernel — the paper's *actual* hot loop in VMEM.

MIREX's headline claim is that sequentially scanning raw documents is fast
enough for large-scale IR experiments. Each TPU grid step streams one
``[block_d, L_d]`` document-token tile HBM→VMEM and:

1. **tf reduction on-chip** — the tile is transposed into a VMEM scratch
   with documents along lanes, cut into lane tiles of 128 documents
   (``[block_d/128, L_d, 128]``). Queries are taken ``ROWS`` (one sublane
   tile) at a time, their ``L_q·ROWS`` slots laid out term-major. The
   compare loop is blocked to fit the vector registers (`tf_geometry`):
   for each group of whole query terms and each document sub-block of
   ``lanes`` documents (256 at 8 query terms), every position's row,
   loaded broadcast over sublanes, is compared against the group's query
   column, ``[slots, lanes] == [1, lanes]``, into an int32 accumulator that
   stays in registers with the column; the finished counts go to a
   ``[L_q·ROWS, block_d]`` VMEM scratch. tf is an exact integer sum, so its
   order and blocking are free; ``tile_d`` positions are unrolled per loop
   step. The rank-4 ``[n_q, L_q, n_d, L_d]`` cross-product never exists.
2. **scorer epilogues on the VPU** — each model in the grid applies its
   declarative epilogue spec to the *shared* tf scratch via
   `scoring.epilogue_scores`, literally the same code the pure-JAX fold
   runs, with the sum over query terms as the same explicit left fold.
3. **resident top-k fold** — each model's block scores go to a ``[G·ROWS,
   block_d]`` VMEM scratch, one row tile after another, and fold into a
   resident ``[n_models, n_q, W]`` state with the k-bounded bitonic
   combiner (`score_topk.fold_block`) ``G`` row tiles at a time
   (`fold_tiles`: up to 8, all of a 64-row serve block). The network's
   stages depend on one another, so it runs at the latency of its stages
   on one row tile; more rows give each stage independent work. The output
   refs double as the running state because the TPU grid executes
   sequentially (combiner semantics).

Because the tf reduction is computed once per tile and shared by every
epilogue, a whole **model grid scans in a single kernel pass** (claim C1 on
the model axis, in VMEM).

The comparisons cost ``n_q·L_q·L_d`` per document against ``L_d`` streamed
tokens, so the kernel is bound by the vector units, not by the document
stream. On a TPU v5e at the ``msmarco-passage`` serve shapes (64 rows, 8
query terms, 128 positions, 512-document tiles, one model), with one row
tile a fold, the compare loop took under a third of the kernel's time and
the fold about two thirds; folding the block's 8 row tiles together cut
the kernel by two fifths.

BlockSpecs: the query-slot column and the weights (``[n_models, n_q/ROWS,
L_q, ROWS, 1]``) are resident across steps, the ``(alpha, beta)`` scalars
sit in SMEM; doc tokens ``[block_d, L_d]`` and lengths ``[1, block_d]`` are
streamed; outputs ``[n_models, n_q, W]`` are pinned to block (0, 0, 0) and
cut to ``(n_q, k)`` by the wrapper, which pads the queries to whole row
tiles. Compiled alignment wants ``block_d % 128 == 0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.scoring import PAD_TOKEN, EpilogueMode, epilogue_scores
from repro.kernels.score_topk import LANES, ROWS, fold_block, pad_rows, state_width


# int32 vregs (8 x 128) the compare loop keeps live: one slot group's
# broadcast query column and its tf accumulator over one document sub-block
TF_VREG_BUDGET = 32


def lane_tile(block_d: int) -> int:
    """Documents a lane tile of the transposed tile holds: one vreg row
    (128), or the whole block where it is not a multiple of 128 (interpret
    mode only). The tile is kept as ``[block_d / 128, L_d, 128]`` because
    Mosaic loads one 128-lane row at a dynamic position from a ref whose
    last axis is 128 lanes, and not from a wider one."""
    return LANES if block_d % LANES == 0 else block_d


def tf_geometry(l_q: int, block_d: int) -> tuple[int, int]:
    """``(terms, lanes)`` of the register-blocked tf loop: query terms per
    slot group and documents per sub-block, a function of the shapes alone.

    A group of ``terms`` whole query terms is ``terms·ROWS`` slots, so its
    query column and accumulator, ``[terms·ROWS, lanes]`` int32 each, take
    ``2·terms·lanes/128`` vregs, which stay within `TF_VREG_BUDGET`. All
    ``l_q`` terms go in one group where one lane tile holds them (``l_q <=
    16``), else in groups of as many as fit. Lanes are then the widest
    multiple of the lane tile that divides ``block_d`` and fits.
    """
    terms = min(l_q, TF_VREG_BUDGET // 2)
    lanes = lane_tile(block_d)
    while block_d % (2 * lanes) == 0 and 4 * terms * lanes <= TF_VREG_BUDGET * LANES:
        lanes *= 2
    return terms, lanes


# row tiles whose scores one bitonic network folds: the network's stages
# depend on each other, so independent rows give each stage work to overlap
FOLD_TILES = 8


def fold_tiles(tiles: int) -> int:
    """Row tiles folded together: the most, up to `FOLD_TILES`, that divide
    the block's ``tiles`` row tiles (8 of a 64-row serve block's 8, 7 of the
    7 that 50 queries fill)."""
    return max(g for g in range(1, min(tiles, FOLD_TILES) + 1) if tiles % g == 0)


def _block_term_frequencies(
    q_ref, q0, d_t_ref, tf_ref, *, l_q: int, tile_d: int
) -> None:
    """On-chip tf for one doc tile: the ``l_q·ROWS`` query slots at
    ``q_ref[q0:]`` against the transposed tile ``d_t_ref [block_d / w, L_d,
    w]`` (lane tiles of ``w`` documents), written to ``tf_ref [l_q·ROWS,
    block_d]`` int32.

    The loop is blocked so that its live set stays in vector registers
    (`tf_geometry`): for each slot group and each ``lanes``-wide document
    sub-block, every position is compared against the group's query column,
    ``[slots, lanes] == [1, lanes]`` (the position's row, loaded broadcast
    over sublanes), into a ``[slots, lanes]`` accumulator; only the finished
    counts go to VMEM. tf is an exact integer count whose order is free, so
    the blocking changes no bit of it. Query pads are pre-remapped by the
    wrapper to a token that matches nothing, and so is every doc-side pad,
    so no validity mask is needed.
    """
    n_lt, length, width = d_t_ref.shape
    terms, lanes = tf_geometry(l_q, n_lt * width)
    per_sub = lanes // width  # lane tiles a sub-block
    n_tiles = length // tile_d
    for t0 in range(0, l_q, terms):  # static: slot groups of whole terms
        slots = min(terms, l_q - t0) * ROWS
        q_col = q_ref[pl.ds(q0 + t0 * ROWS, slots), :]  # [slots, 1]
        q_b = jnp.broadcast_to(q_col[None], (per_sub, slots, width))
        for c in range(0, n_lt, per_sub):  # static: document sub-blocks

            def add_rows(p0, n, acc, c=c, q_b=q_b):
                for u in range(n):  # static unroll of one tile of positions
                    row = d_t_ref[pl.ds(c, per_sub), pl.ds(p0 + u, 1), :]
                    acc = acc + (q_b == row).astype(jnp.int32)
                return acc

            acc = jnp.zeros(q_b.shape, jnp.int32)
            acc = jax.lax.fori_loop(
                0, n_tiles,
                lambda t, a: add_rows(pl.multiple_of(t * tile_d, tile_d), tile_d, a),
                acc,
            )
            acc = add_rows(n_tiles * tile_d, length % tile_d, acc)
            for i in range(per_sub):
                tf_ref[pl.ds(t0 * ROWS, slots), pl.ds((c + i) * width, width)] = acc[i]


def _lexical_scan_kernel(
    q_ref,  # [n_q * L_q, 1] int32 — resident query slots, (tile, term, row)
    w_ref,  # [n_models, n_q / ROWS, L_q, ROWS, 1] f32 — resident weight tables
    ab_ref,  # [n_models, 2] f32 in SMEM — (alpha, beta) per model
    d_ref,  # [block_d, L_d] int32 — or packed [block_d, W] when pack_spec
    dlen_ref,  # [1, block_d] int32 — this step's doc lengths
    out_s_ref,  # [n_models, n_q, W] f32 — resident top-k scores
    out_i_ref,  # [n_models, n_q, W] int32 — resident top-k ids
    d_t_ref,  # [block_d / w, L_d, w] int32 VMEM scratch — the transposed tile
    tf_ref,  # [L_q * ROWS, block_d] int32 VMEM scratch — one row tile's tf
    s_ref,  # [n_models, G * ROWS, block_d] f32 VMEM scratch — a fold group's scores
    *,
    modes: tuple[EpilogueMode, ...],
    block_d: int,
    tile_d: int,
    pack_spec: packing.PackSpec | None = None,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_s_ref[...] = jnp.full(out_s_ref.shape, -jnp.inf, jnp.float32)
        out_i_ref[...] = jnp.full(out_i_ref.shape, -1, jnp.int32)

    d = d_ref[...]
    if pack_spec is not None:
        # decode the packed tile in VMEM: the stream tile stays
        # `pack_spec.packed_width` wide in HBM and the int32 view only ever
        # exists on-chip
        d = packing.unpack_tokens(d, pack_spec)
    d_t = d.T  # [L_d, block_d]: documents along lanes
    width = d_t_ref.shape[2]
    for c in range(d_t_ref.shape[0]):  # static: one store a lane tile
        d_t_ref[c] = d_t[:, c * width : (c + 1) * width]
    dlen = dlen_ref[...]  # [1, block_d]
    l_q = w_ref.shape[2]
    slots = l_q * ROWS
    group = s_ref.shape[1] // ROWS

    def score_rows(t, carry):
        # one tile of ROWS queries: its L_q * ROWS slots are contiguous
        q0 = pl.multiple_of(t * slots, slots)
        _block_term_frequencies(q_ref, q0, d_t_ref, tf_ref, l_q=l_q, tile_d=tile_d)
        tf = tf_ref[...].astype(jnp.float32).reshape(l_q, ROWS, block_d)
        rows = pl.ds(pl.multiple_of(t % group * ROWS, ROWS), ROWS)
        for m, mode in enumerate(modes):  # static: unrolled epilogues
            s_ref[m, rows, :] = epilogue_scores(
                mode, w_ref[m, t], ab_ref[m, 0], ab_ref[m, 1], tf, dlen
            )
        return carry

    def fold_group(g, carry):
        jax.lax.fori_loop(g * group, (g + 1) * group, score_rows, 0)
        rows = pl.ds(pl.multiple_of(g * group * ROWS, group * ROWS), group * ROWS)
        for m in range(len(modes)):
            s = s_ref[m]
            ids = step * block_d + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # zero-length rows score -inf; blank their ids so the merged
            # state carries the host fold's (-inf, -1) empty-slot sentinel,
            # never a padded corpus row
            ids = jnp.where(s == -jnp.inf, -1, ids)
            out_s_ref[m, rows, :], out_i_ref[m, rows, :] = fold_block(
                out_s_ref[m, rows, :], out_i_ref[m, rows, :], s, ids
            )
        return carry

    jax.lax.fori_loop(0, out_s_ref.shape[1] // (group * ROWS), fold_group, 0)


def lexical_scan_topk_pallas(
    q_tokens: jax.Array,  # [n_q, L_q] int32, PAD_TOKEN-padded
    weights: jax.Array,  # [n_models, n_q, L_q] f32
    ab: jax.Array,  # [n_models, 2] f32
    d_tokens: jax.Array,  # [n_d, L_d] int32, PAD_TOKEN-padded — or packed [n_d, W]
    d_len: jax.Array,  # [n_d] int32
    *,
    modes: tuple[EpilogueMode, ...],
    k: int,
    block_d: int = 512,
    tile_d: int = 16,
    interpret: bool = True,
    pack_spec: packing.PackSpec | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused multi-model lexical scan -> ``(scores, ids) [n_models, n_q, k]``.

    Ids are block-local (0-based over ``n_d``); empty slots carry the
    ``(-inf, -1)`` sentinels of `topk.TopKState`.

    With ``pack_spec``, ``d_tokens`` is the packed matrix from
    `packing.pack_tokens` — the stream tile is ``pack_spec.packed_width``
    columns instead of ``L_d`` and each tile is decoded in VMEM before the
    tf loop. The decode is exact, so results are bit-identical to the
    unpacked call. Bit-plane packing decodes through lane-splitting reshapes
    that Mosaic does not lower, so a compiled call refuses it.
    """
    n_q, l_q = q_tokens.shape
    n_d = d_tokens.shape[0]
    n_models = weights.shape[0]
    if len(modes) != n_models:
        raise ValueError(f"{len(modes)} modes for {n_models} weight tables")
    if n_d % block_d:
        raise ValueError(f"{n_d} docs not divisible by block_d {block_d}")
    length = d_tokens.shape[1]
    if pack_spec is not None:
        if length != pack_spec.packed_width:
            raise ValueError(
                f"packed width {length} != spec {pack_spec.packed_width}"
            )
        if pack_spec.mode == "bitpack" and not interpret:
            raise NotImplementedError(
                "bit-plane packed tokens cannot be decoded in a compiled TPU "
                "kernel; use token_pack 'none', '8' or '16' with use_kernel"
            )
        length = pack_spec.length
    # query pads -> a token that matches nothing (doc pads are PAD_TOKEN or
    # the pack sentinel, real tokens are in [0, vocab)); query rows padded
    # to whole tiles of ROWS, and the slots of each tile laid out term-major
    q_safe = jnp.where(q_tokens == PAD_TOKEN, jnp.int32(PAD_TOKEN - 1), q_tokens)
    q_safe = pad_rows(q_safe, ROWS, PAD_TOKEN - 1)
    n_rows = q_safe.shape[0]
    tiles = n_rows // ROWS
    q_col = q_safe.reshape(tiles, ROWS, l_q).transpose(0, 2, 1).reshape(-1, 1)
    w_t = jnp.pad(weights, ((0, 0), (0, n_rows - n_q), (0, 0)))
    w_t = w_t.reshape(n_models, tiles, ROWS, l_q).transpose(0, 1, 3, 2)[..., None]
    width = state_width(k)
    kernel = functools.partial(
        _lexical_scan_kernel, modes=modes, block_d=block_d, tile_d=tile_d,
        pack_spec=pack_spec,
    )
    scores, ids = pl.pallas_call(
        kernel,
        grid=(n_d // block_d,),
        in_specs=[
            pl.BlockSpec((n_rows * l_q, 1), lambda i: (0, 0)),  # Q resident
            pl.BlockSpec(w_t.shape, lambda i: (0, 0, 0, 0, 0)),  # weights resident
            pl.BlockSpec(memory_space=pltpu.SMEM),  # norm scalars
            pl.BlockSpec((block_d, d_tokens.shape[1]), lambda i: (i, 0)),  # streamed
            pl.BlockSpec((1, block_d), lambda i: (0, i)),  # doc lengths streamed
        ],
        out_specs=[
            pl.BlockSpec((n_models, n_rows, width), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_models, n_rows, width), lambda i: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_models, n_rows, width), jnp.float32),
            jax.ShapeDtypeStruct((n_models, n_rows, width), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (block_d // lane_tile(block_d), length, lane_tile(block_d)), jnp.int32
            ),
            pltpu.VMEM((l_q * ROWS, block_d), jnp.int32),
            pltpu.VMEM((n_models, fold_tiles(tiles) * ROWS, block_d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lexical_scan",  # the kernel's name in device traces
    )(q_col, w_t, ab, d_tokens, d_len.reshape(1, n_d))
    return scores[:, :n_q, :k], ids[:, :n_q, :k]
