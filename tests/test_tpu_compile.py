"""Compile-only checks of the retrieval main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jax compiles
for a ``v5e:2x2`` topology that is described, not attached, and refuses what
Mosaic cannot lower, kernels that overrun scoped VMEM and programs that do
not fit HBM — failures interpret mode never shows. Shapes are the `mirex`
config at ``MIREX_SHAPES["scan_50q"]`` widths. The topology is described
inside a fixture (never at import: the TPU runtime may be loaded by one
process at a time), and every case runs in this one file so that a single
test worker owns it.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import cluster
from repro.configs.archs import mirex
from repro.configs.shapes import MIREX_SHAPES
from repro.core import packing, scan, scoring, topk
from repro.kernels import lexical_scan, ops
from repro.kernels.lexical_scan import lexical_scan_topk_pallas
from repro.kernels.score_topk import score_topk_pallas

CFG = mirex.config()
SCAN = MIREX_SHAPES["scan_50q"].dims
N_DOCS, N_Q, L_D = SCAN["n_docs"], SCAN["n_queries"], SCAN["doc_len"]
GRID = (scoring.get_scorer("ql_lm"), scoring.get_scorer("bm25"))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache, so keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        ops.set_kernel_backend("compiled")
        try:
            yield desc
        finally:
            ops.set_kernel_backend(None)
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stats(sharding):
    return scoring.CollectionStats(
        cf=_shape((CFG.vocab,), jnp.int32, sharding),
        df=_shape((CFG.vocab,), jnp.int32, sharding),
        total_terms=_shape((), jnp.int32, sharding),
        n_docs=_shape((), jnp.int32, sharding),
        avg_doc_len=_shape((), jnp.float32, sharding),
    )


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_models", [1, 2])
def test_lexical_kernel_scan_50q(one_chip, n_models):
    """The scan fold with the fused kernel, as a scan job's segment runs it."""
    docs = (
        _shape((N_DOCS, L_D), jnp.int32, one_chip),
        _shape((N_DOCS,), jnp.int32, one_chip),
    )

    def fold(q, docs, stats):
        return scan.search_local_multi(
            q, docs, GRID[:n_models], k=CFG.k, chunk_size=CFG.chunk_size,
            stats=stats, use_kernel=True,
        )

    compiled = (
        jax.jit(fold)
        .lower(_shape((N_Q, CFG.max_q_len), jnp.int32, one_chip), docs, _stats(one_chip))
        .compile()
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize("mode", ["u8", "u16"])
def test_lexical_kernel_packed(one_chip, mode):
    """Packed tiles decode inside the kernel. The `mirex` vocab needs a
    17-bit sentinel, so the spec's vocab is the largest the width holds;
    the compiled program depends on the width only."""
    vocab = {"u8": 0xFF, "u16": 0xFFFF}[mode]
    spec = packing.PackSpec(mode=mode, vocab=vocab, length=L_D)
    modes = tuple(scoring.EpilogueMode(m) for m in ("ql", "bm25"))

    def kernel(q, w, ab, tokens, lengths):
        return lexical_scan_topk_pallas(
            q, w, ab, tokens, lengths, modes=modes, k=CFG.k, block_d=512,
            interpret=False, pack_spec=spec,
        )

    compiled = (
        jax.jit(kernel)
        .lower(
            _shape((N_Q, CFG.max_q_len), jnp.int32, one_chip),
            _shape((2, N_Q, CFG.max_q_len), jnp.float32, one_chip),
            _shape((2, 2), jnp.float32, one_chip),
            _shape((N_DOCS, spec.packed_width), spec.packed_dtype(), one_chip),
            _shape((N_DOCS,), jnp.int32, one_chip),
        )
        .compile()
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize(
    "rows, l_q", [(64, 8), (16, 32)], ids=["marco-serve", "slot-split"]
)
def test_lexical_kernel_register_blocked(one_chip, rows, l_q):
    """The register-blocked tf loop at the `msmarco-passage` serve shapes
    (a 64-row block of 8-term queries over 128-token passages, 512-document
    tiles, one `bm25` model, its 8 row tiles folded in one network), and
    with 32-term queries, whose slots are split into groups of whole
    terms."""
    n_docs, length = 2_211_840, 128
    assert lexical_scan.tf_geometry(l_q, 512)[0] < l_q or l_q <= 16
    modes = (scoring.EpilogueMode("bm25"),)

    def kernel(q, w, ab, tokens, lengths):
        return lexical_scan_topk_pallas(
            q, w, ab, tokens, lengths, modes=modes, k=CFG.k, block_d=512,
            interpret=False,
        )

    compiled = (
        jax.jit(kernel)
        .lower(
            _shape((rows, l_q), jnp.int32, one_chip),
            _shape((1, rows, l_q), jnp.float32, one_chip),
            _shape((1, 2), jnp.float32, one_chip),
            _shape((n_docs, length), jnp.int32, one_chip),
            _shape((n_docs,), jnp.int32, one_chip),
        )
        .compile()
    )
    assert _has_kernel(compiled)


def test_bitplane_refused_when_compiled():
    """Bit-plane decode needs lane-splitting reshapes Mosaic refuses, so a
    compiled call says so instead of falling back to the host fold."""
    spec = packing.make_spec(CFG.vocab, L_D, "auto")
    assert spec.mode == "bitpack"
    tokens = jnp.zeros((512, spec.packed_width), jnp.int32)
    with pytest.raises(NotImplementedError, match="bit-plane"):
        lexical_scan_topk_pallas(
            jnp.zeros((8, 8), jnp.int32), jnp.zeros((1, 8, 8)), jnp.zeros((1, 2)),
            tokens, jnp.zeros((512,), jnp.int32),
            modes=(scoring.EpilogueMode("ql"),), k=16, interpret=False,
            pack_spec=spec,
        )


def test_score_topk_dense(one_chip):
    """The dense score+top-k kernel at the serving shape: 128 queries of
    dim 256, k = 1000, 1024-document blocks."""

    def kernel(q, d):
        return score_topk_pallas(q, d, k=CFG.k, block_d=1024, interpret=False)

    compiled = (
        jax.jit(kernel)
        .lower(
            _shape((128, CFG.dense_dim), jnp.float32, one_chip),
            _shape((N_DOCS, CFG.dense_dim), jnp.float32, one_chip),
        )
        .compile()
    )
    assert _has_kernel(compiled)


def test_xla_fold_scan_50q(one_chip):
    """The reference the kernel is checked against: the XLA fold at full
    width fits one chip with room to spare."""

    def fold(q, docs, stats):
        return scan.search_local(
            q, docs, GRID[0], k=CFG.k, chunk_size=CFG.chunk_size, stats=stats,
        )

    docs = (
        _shape((N_DOCS, L_D), jnp.int32, one_chip),
        _shape((N_DOCS,), jnp.int32, one_chip),
    )
    compiled = (
        jax.jit(fold)
        .lower(_shape((N_Q, CFG.max_q_len), jnp.int32, one_chip), docs, _stats(one_chip))
        .compile()
    )
    assert not _has_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20


def test_search_mesh_kernel_four_chips(topo):
    """The shard_map serve program over the 2x2 mesh, kernel on every shard,
    each chip holding its ``scan_50q`` share of a 4x corpus."""
    devices = np.asarray(topo.devices).reshape(2, 2)
    mesh = Mesh(devices, ("data", "model"))
    doc_sh = NamedSharding(mesh, P(("data", "model")))
    repl = NamedSharding(mesh, P())
    docs = (
        _shape((4 * N_DOCS, L_D), jnp.int32, doc_sh),
        _shape((4 * N_DOCS,), jnp.int32, doc_sh),
    )
    stats = _stats(repl)
    fn = cluster.search_mesh(
        mesh, jnp.zeros((1, 1), jnp.int32), docs, GRID[0], k=CFG.k,
        chunk_size=CFG.chunk_size, stats=stats, use_kernel=True,
    )
    compiled = fn.lower(_shape((N_Q, CFG.max_q_len), jnp.int32, repl), docs, stats).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text


# the whole MS MARCO passage collection over the 2x2 mesh: 540 chunks of
# 16,384 passages, 135 a chip, 128 tokens and 768 float32 dims a passage
MARCO_DOCS, MARCO_LEN, MARCO_DIM, MARCO_CHUNK, BLOCK = 8_847_360, 128, 768, 16_384, 64


def _marco_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    return mesh, NamedSharding(mesh, P(("data", "model"))), NamedSharding(mesh, P())


def _reduce_is_named(text: str) -> bool:
    """Every all-gather of the compiled program carries the reduce's name."""
    gathers = [ln for ln in text.splitlines() if " all-gather(" in ln]
    return bool(gathers) and all(f'mirex_scope="{topk.REDUCE_SCOPE}"' in ln for ln in gathers)


def test_search_mesh_whole_marco_lexical_four_chips(topo):
    """The sharded lexical serve program of the whole-collection deployment:
    the kernel on every chip's 2,211,840 passages, the reduce named in the
    compiled program, and the program within a chip's memory beside its
    1.13 GB of tokens."""
    mesh, doc_sh, repl = _marco_mesh(topo)
    docs = (
        _shape((MARCO_DOCS, MARCO_LEN), jnp.int32, doc_sh),
        _shape((MARCO_DOCS,), jnp.int32, doc_sh),
    )
    stats = _stats(repl)
    fn = cluster.search_mesh(
        mesh, jnp.zeros((1, 1), jnp.int32), docs, scoring.get_scorer("bm25"), k=CFG.k,
        chunk_size=MARCO_CHUNK, stats=stats, use_kernel=True,
    )
    compiled = fn.lower(_shape((BLOCK, 8), jnp.int32, repl), docs, stats).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and _reduce_is_named(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_search_mesh_whole_marco_dense_four_chips(topo):
    """The sharded dense serve program of the same deployment: the
    score_topk kernel over each chip's 6.8 GB of vectors at the matmul
    precision the configuration sets, the reduce named."""
    mesh, doc_sh, repl = _marco_mesh(topo)
    vectors = _shape((MARCO_DOCS, MARCO_DIM), jnp.float32, doc_sh)
    with jax.default_matmul_precision("highest"):
        fn = cluster.search_mesh(
            mesh, jnp.zeros((1, MARCO_DIM), jnp.float32), vectors,
            scoring.get_scorer("dense_dot"), k=CFG.k, chunk_size=MARCO_CHUNK, use_kernel=True,
        )
        compiled = fn.lower(_shape((BLOCK, MARCO_DIM), jnp.float32, repl), vectors, None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and _reduce_is_named(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
