"""Fused Pallas lexical-scan kernel — the paper's *actual* hot loop in VMEM.

MIREX's headline claim is that sequentially scanning raw documents is fast
enough for large-scale IR experiments. Each TPU grid step streams one
``[block_d, L_d]`` document-token tile HBM→VMEM and:

1. **tf reduction on-chip** — the tile is transposed into a ``[L_d,
   block_d]`` VMEM scratch (documents along lanes). Queries are taken
   ``ROWS`` (one sublane tile) at a time; each document position is
   compared against the tile's term-major column of query slots,
   ``[L_q·ROWS, 1] == [1, block_d]``, accumulated in int32. tf is an exact
   integer sum, so its order is free; ``tile_d`` positions are unrolled per
   loop step. The rank-4 ``[n_q, L_q, n_d, L_d]`` cross-product never
   exists; the live tf block is ``[L_q·ROWS, block_d]``.
2. **scorer epilogues on the VPU** — each model in the grid applies its
   declarative epilogue spec to the *shared* tf block via
   `scoring.epilogue_scores`, literally the same code the pure-JAX fold
   runs, with the sum over query terms as the same explicit left fold.
3. **resident top-k fold** — each model's block scores fold into a resident
   ``[n_models, n_q, W]`` state with the k-bounded bitonic combiner
   (`score_topk.fold_block`): the output refs double as the running state
   because the TPU grid executes sequentially (combiner semantics).

Because the tf reduction — the dominant cost of a raw-token chunk — is
computed once per tile and shared by every epilogue, a whole **model grid
scans in a single kernel pass** (claim C1 on the model axis, in VMEM).

The comparisons cost ``n_q·L_q·L_d`` per document against ``L_d`` streamed
tokens, so at ``scan_50q`` widths the kernel is bound by the VPU, not by the
document stream.

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
from repro.kernels.score_topk import ROWS, fold_block, pad_rows, state_width


def _block_term_frequencies(q_col, d_t_ref, *, tile_d: int) -> jax.Array:
    """On-chip tf for one doc tile: ``[S, 1] x [L_d, block_d] -> [S, block_d]``
    int32 for ``S`` query slots.

    Query pads are pre-remapped by the wrapper to a token that matches
    nothing, and so is every doc-side pad, so no validity mask is needed.
    """
    length, block_d = d_t_ref.shape
    q_b = jnp.broadcast_to(q_col, (q_col.shape[0], block_d))

    def add_rows(p0, n, acc):
        for u in range(n):  # static unroll of one tile of positions
            row = d_t_ref[pl.ds(p0 + u, 1), :]  # [1, block_d]
            acc = acc + (q_b == row).astype(jnp.int32)
        return acc

    n_tiles = length // tile_d
    acc = jnp.zeros(q_b.shape, jnp.int32)
    acc = jax.lax.fori_loop(
        0, n_tiles, lambda t, a: add_rows(t * tile_d, tile_d, a), acc
    )
    return add_rows(n_tiles * tile_d, length % tile_d, acc)


def _lexical_scan_kernel(
    q_ref,  # [n_q * L_q, 1] int32 — resident query slots, (tile, term, row)
    w_ref,  # [n_models, n_q / ROWS, L_q, ROWS, 1] f32 — resident weight tables
    ab_ref,  # [n_models, 2] f32 in SMEM — (alpha, beta) per model
    d_ref,  # [block_d, L_d] int32 — or packed [block_d, W] when pack_spec
    dlen_ref,  # [1, block_d] int32 — this step's doc lengths
    out_s_ref,  # [n_models, n_q, W] f32 — resident top-k scores
    out_i_ref,  # [n_models, n_q, W] int32 — resident top-k ids
    d_t_ref,  # [L_d, block_d] int32 VMEM scratch — the transposed tile
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
    d_t_ref[...] = d.T
    dlen = dlen_ref[...]  # [1, block_d]
    l_q = w_ref.shape[2]
    slots = l_q * ROWS

    def fold_rows(g, carry):
        # one tile of ROWS queries: its L_q * ROWS slots are contiguous
        q_col = q_ref[pl.ds(pl.multiple_of(g * slots, slots), slots), :]
        tf = _block_term_frequencies(q_col, d_t_ref, tile_d=tile_d)
        tf = tf.astype(jnp.float32).reshape(l_q, ROWS, block_d)
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        for m, mode in enumerate(modes):  # static: unrolled epilogues
            s = epilogue_scores(mode, w_ref[m, g], ab_ref[m, 0], ab_ref[m, 1], tf, dlen)
            ids = step * block_d + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # zero-length rows score -inf; blank their ids so the merged
            # state carries the host fold's (-inf, -1) empty-slot sentinel,
            # never a padded corpus row
            ids = jnp.where(s == -jnp.inf, -1, ids)
            out_s_ref[m, rows, :], out_i_ref[m, rows, :] = fold_block(
                out_s_ref[m, rows, :], out_i_ref[m, rows, :], s, ids
            )
        return carry

    jax.lax.fori_loop(0, out_s_ref.shape[1] // ROWS, fold_rows, 0)


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
        scratch_shapes=[pltpu.VMEM((length, block_d), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="lexical_scan",  # the kernel's name in device traces
    )(q_col, w_t, ab, d_tokens, d_len.reshape(1, n_d))
    return scores[:, :n_q, :k], ids[:, :n_q, :k]
