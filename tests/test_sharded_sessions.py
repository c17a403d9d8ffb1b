"""Shard-resident sessions on four virtual CPU devices (2x2 and 4x1 meshes):
``ShardedDenseSession`` against ``DenseSession`` and a plain float32
inner product, ``ShardedLexicalSession`` against a float64 BM25, both fed
host arrays and arrays already laid out on the mesh, and the sessions'
spans; plus the on-device token packer against the host one."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import packing
from repro.core.scoring import PAD_TOKEN
from repro.serve.session import ShardedDenseSession, ShardedLexicalSession

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import obs
from repro.data import synthetic
from repro.serve import (
    DenseSession, LexicalSession, ShardedDenseSession, ShardedLexicalSession,
)

jax.config.update("jax_default_matmul_precision", "highest")
K, CHUNK, PAD = 20, 128, -1


def same(a, b):
    return bool(np.asarray(a.ids).tobytes() == np.asarray(b.ids).tobytes()
                and np.asarray(a.scores).tobytes() == np.asarray(b.scores).tobytes())


def buffers(arr):
    return [s.data.unsafe_buffer_pointer() for s in arr.addressable_shards]


def gaps(got, ref):
    # widest |returned - reference score of that id| and reference best - reference
    # of the i-th returned id, over max(1, best): 0 for the exact top k
    ids, scores = np.asarray(got.ids), np.asarray(got.scores, np.float64)
    of = np.take_along_axis(ref, ids, axis=1)
    best = -np.sort(-ref, axis=1)[:, :K]
    scale = np.maximum(np.abs(best[:, :1]), 1.0)
    return float((np.abs(scores - of) / scale).max()), float(((best - of) / scale).max())


def bm25_f64(tokens, lengths, queries, k1=1.2, b=0.75):
    n_docs = float((lengths > 0).sum())
    avgdl = lengths.sum() / n_docs
    out = np.zeros((len(queries), len(tokens)))
    for qi, q in enumerate(queries):
        for t in q:
            if t == PAD:
                continue
            tf = (tokens == t).sum(axis=1).astype(np.float64)
            df = float((tf > 0).sum())
            if df == 0:
                continue
            idf = np.log1p((n_docs - df + 0.5) / (df + 0.5))
            out[qi] += idf * (k1 + 1) * tf / (tf + k1 * (1 - b) + k1 * b * lengths / avgdl)
    out[:, lengths == 0] = -np.inf
    return out


out = {}
rng = np.random.default_rng(7)
vectors = (rng.standard_normal((2048, 64)) / 8).astype(np.float32)
dq = (rng.standard_normal((16, 64)) / 8).astype(np.float32)
dense_ref = np.asarray(jnp.dot(dq, vectors.T, precision=jax.lax.Precision.HIGHEST), np.float64)
corpus = synthetic.make_corpus(n_docs=2048, vocab=512, max_len=24, seed=3)
lq = synthetic.make_queries(corpus, n_queries=16, max_q_len=4, seed=5)
lex_ref = bm25_f64(corpus.tokens, corpus.lengths.astype(np.float64), lq)

for shape in ((2, 2), (4, 1)):
    mesh = jax.make_mesh(shape, ("data", "model"))
    sharding = NamedSharding(mesh, P(("data", "model")))
    row = out[f"{shape[0]}x{shape[1]}"] = {}
    for kern in (False, True):
        one = DenseSession(vectors, k=K, chunk_size=CHUNK, use_kernel=kern).search(dq)
        host = ShardedDenseSession(mesh, vectors, k=K, chunk_size=CHUNK, use_kernel=kern)
        placed = jax.device_put(vectors, sharding)
        pre = ShardedDenseSession(mesh, placed, k=K, chunk_size=CHUNK, use_kernel=kern)
        got = host.search(dq)
        row[f"dense_kernel={kern}"] = {
            "equals_one_device": same(got, one),
            "presharded_same_bytes": same(pre.search(dq), got),
            "presharded_in_place": buffers(pre._vectors) == buffers(placed),
            "gaps": gaps(got, dense_ref),
        }
    for pack in ("none", "16"):
        kw = dict(k=K, chunk_size=CHUNK, vocab=512, token_pack=pack)
        host = ShardedLexicalSession(mesh, corpus.tokens, corpus.lengths, "bm25", **kw)
        t_dev = jax.device_put(corpus.tokens, sharding)
        l_dev = jax.device_put(corpus.lengths, sharding)
        pre = ShardedLexicalSession(mesh, t_dev, l_dev, "bm25", **kw)
        one = LexicalSession(corpus.tokens, corpus.lengths, "bm25", **kw).search(lq)
        got = host.search(lq)
        row[f"lexical_pack={pack}"] = {
            "equals_one_device": same(got, one),
            "presharded_same_bytes": same(pre.search(lq), got),
            "pack_mode": [host.pack_mode, pre.pack_mode],
            "presharded_in_place": buffers(pre._docs[0]) == buffers(t_dev) if pack == "none" else None,
            "gaps": gaps(got, lex_ref),
        }
    with obs.session() as (tr, _):
        lex = ShardedLexicalSession(mesh, corpus.tokens, corpus.lengths, "bm25", k=K,
                                    chunk_size=CHUNK, vocab=512)
        den = ShardedDenseSession(mesh, vectors, k=K, chunk_size=CHUNK, use_kernel=False)
        lex.search(lq[:8])
        den.search(dq)
    row["spans"] = [(s.name, dict(s.attrs)) for s in tr.spans() if s.name.startswith("session.")]
print(json.dumps(out))
"""

MESHES = ("2x2", "4x1")


@pytest.fixture(scope="module")
def four_devices():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("kernel", [False, True])
def test_sharded_dense_equals_one_device_and_the_float32_product(four_devices, mesh, kernel):
    row = four_devices[mesh][f"dense_kernel={kernel}"]
    assert row["equals_one_device"]  # ids and score bytes
    score_gap, rank_gap = row["gaps"]
    # float32 products of the same vectors: the order of a 64-term sum apart
    assert score_gap <= 1e-6 and rank_gap <= 1e-6


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("pack", ["none", "16"])
def test_sharded_lexical_equals_one_device_and_float64_bm25(four_devices, mesh, pack):
    row = four_devices[mesh][f"lexical_pack={pack}"]
    assert row["equals_one_device"]
    assert row["pack_mode"] == (["none", "none"] if pack == "none" else ["u16", "u16"])
    score_gap, rank_gap = row["gaps"]
    assert score_gap <= 1e-5 and rank_gap <= 1e-5  # float32 against float64


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize(
    "case", ["dense_kernel=False", "dense_kernel=True", "lexical_pack=none", "lexical_pack=16"]
)
def test_presharded_corpus_gives_the_same_bytes(four_devices, mesh, case):
    row = four_devices[mesh][case]
    assert row["presharded_same_bytes"]
    if row["presharded_in_place"] is not None:
        assert row["presharded_in_place"]  # used where it lies: no copy


@pytest.mark.parametrize("mesh", MESHES)
def test_spans_on_four_devices(four_devices, mesh):
    spans = four_devices[mesh]["spans"]
    names = [n for n, _ in spans]
    assert names == ["session.place", "session.stats", "session.place",
                     "session.mesh_search", "session.mesh_search"]
    place_lex, _, place_dense, search_lex, search_dense = (a for _, a in spans)
    assert place_lex == {"kind": "lexical", "shards": 4, "bytes_per_chip": 512 * 25 * 4}
    assert place_dense == {"kind": "dense", "shards": 4, "bytes_per_chip": 512 * 64 * 4}
    # 2x2: two gathers of 2 states; 4x1: one of 4 (the length-1 axis moves nothing)
    assert search_lex == {"kind": "lexical", "shards": 4, "rows": 8, "gather_bytes": 4 * 8 * 20 * 8}
    assert search_dense == {"kind": "dense", "shards": 4, "rows": 16,
                            "gather_bytes": 4 * 16 * 20 * 8}


def test_sharded_sessions_record_spans_only_while_tracing(mesh11):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50, size=(256, 8)).astype(np.int32)
    lengths = np.full(256, 8, np.int32)
    vectors = rng.standard_normal((256, 16)).astype(np.float32)
    with obs.session(tracer=obs.Tracer(enabled=False)) as (off, _):
        lex = ShardedLexicalSession(mesh11, tokens, lengths, "bm25", k=4, chunk_size=64, vocab=50)
        den = ShardedDenseSession(mesh11, vectors, k=4, chunk_size=64, use_kernel=False)
        lex.search(tokens[:8, :2])
        den.search(vectors[:8])
    assert len(off) == 0
    with obs.session() as (on, _):
        lex = ShardedLexicalSession(mesh11, tokens, lengths, "bm25", k=4, chunk_size=64, vocab=50)
        lex.search(tokens[:8, :2])
    got = {s.name: dict(s.attrs) for s in on.spans() if s.name.startswith("session.")}
    assert got == {
        "session.place": {"kind": "lexical", "shards": 1, "bytes_per_chip": 256 * 9 * 4},
        "session.stats": {"shards": 1},
        "session.mesh_search": {"kind": "lexical", "shards": 1, "rows": 8, "gather_bytes": 0},
    }


@pytest.mark.parametrize("mode", ["8", "16", "bitpack"])
def test_device_packer_equals_host_packer(mode):
    rng = np.random.default_rng(1)
    vocab = 200 if mode == "8" else 3000
    tokens = rng.integers(0, vocab, size=(64, 40)).astype(np.int32)
    tokens[:, 30:] = PAD_TOKEN
    spec = packing.make_spec(vocab, 40, mode)
    want = packing.pack_tokens(tokens, spec)
    got = packing.pack_tokens_device(jnp.asarray(tokens), spec)
    assert got.dtype == want.dtype and np.asarray(got).tobytes() == want.tobytes()
    np.testing.assert_array_equal(np.asarray(packing.unpack_tokens(got, spec)), tokens)


def test_device_packer_refuses_tokens_outside_the_vocab():
    spec = packing.make_spec(100, 4, "16")
    with pytest.raises(ValueError, match="cannot be packed"):
        packing.pack_tokens_device(jnp.asarray([[1, 2, 100, -1]], jnp.int32), spec)
