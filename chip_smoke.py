#!/usr/bin/env python3
"""Smoke run of the MIREX retrieval main path on a TPU, through the entry
points a user calls, at the ``mirex`` widths of ``MIREX_SHAPES["scan_50q"]``.

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # four chips: the sharded scan only

One chip, in one process, in order:

(a) device      platform, kind and count; anything but a TPU stops here.
(b) experiment  ``run_experiment`` over 1,048,576 docs x 128 tokens (vocab
                65,536), 64 queries of <= 8 terms, k = 1000, chunk 16,384,
                grid ``ql_lm`` + ``bm25``: once with the XLA fold, once with
                the fused lexical kernel, each in a fresh output directory.
                Kernel run files == fold run files, byte for byte; kernel
                scores == the host inverted index (`core.invindex`) within
                ``INDEX_RTOL``.
(c) serve       ``RetrievalService`` in front of a ``LexicalSession``
                (``use_kernel=None``, which must resolve to the kernel) on the
                same resident corpus answers 3 waves of 64 requests; every
                answer == a ``use_kernel=False`` session's.
(d) dense       ``DenseSession`` with the ``score_topk`` kernel over
                1,048,576 x 256 f32 (1 GiB), one block of 128 queries,
                k = 1000; answers == the XLA fold's. The vectors are rounded to
                multiples of 1/64, so every dot product is exact under any
                matmul precision or summation order and the comparison
                tests the kernel's ranking, not float rounding.

With ``--chips 4`` it runs only the cluster path and its reference: a
sharded experiment (``n_shards=4``, kernel on) over 4,194,304 docs, each chip
scanning a ``scan_50q`` share, against the same job with ``n_shards=1`` on
one chip (run files byte-identical, shards on 4 distinct devices); then a
``ShardedLexicalSession`` on a 2x2 mesh answers the serve waves, byte-equal
to a one-chip ``LexicalSession``.

Cuts: ``MIREX_SHAPES["dense_scan"]`` (16,777,216 x 256 f32, 4,096 queries)
exceeds one chip's 16 GB, so (d) keeps its width and cuts its depth to
``scan_50q``'s 1,048,576 docs and one 128-query block.

Kernel against XLA fold, and the sharded path against one chip: the same
ids and the same score bytes (Mosaic and XLA:TPU give the same float32
bits here, so the repo's bitwise contract holds on the chip too). The
compiled scan programs must contain a ``tpu_custom_call``.
Any failure raises: the script then exits non-zero without its last line.
Wall times are set-up/smoke timings, not metrics. Data is made from
``--seed``; outputs go under ``--out``; the tuning is the default one.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.configs.archs import mirex  # noqa: E402
from repro.configs.shapes import MIREX_SHAPES  # noqa: E402
from repro.core import invindex  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.eval import trec  # noqa: E402
from repro.experiments import runner  # noqa: E402
from repro.experiments.grid import ExperimentSpec, GridSpec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.serve import RetrievalService  # noqa: E402
from repro.serve.session import (  # noqa: E402
    DenseSession,
    LexicalSession,
    ShardedLexicalSession,
)
from repro.tune import TuningConfig  # noqa: E402

# scan vs the float64 inverted index: on a v5e, float32 log and log1p (XLA
# and Mosaic give the same bits) are off by up to ~4,000 ulps, 2.6e-4
# relative (division: 1.9 ulps), and a ql_lm score sums up to 9 logarithms;
# the largest gap measured at scan_50q was 3.92e-4, against ~2e-6 on a CPU
INDEX_ATOL, INDEX_RTOL = 1e-3, 1e-4
SCORERS = ("ql_lm", "bm25")
WAVES = 3


@dataclasses.dataclass(frozen=True)
class Widths:
    n_docs: int
    n_queries: int
    vocab: int
    doc_len: int
    q_len: int
    k: int
    chunk: int
    dense_dim: int
    dense_queries: int

    @classmethod
    def scan_50q(cls) -> "Widths":
        cfg, dims = mirex.config(), MIREX_SHAPES["scan_50q"].dims
        return cls(
            n_docs=dims["n_docs"], n_queries=dims["n_queries"], vocab=cfg.vocab,
            doc_len=dims["doc_len"], q_len=cfg.max_q_len, k=cfg.k,
            chunk=cfg.chunk_size, dense_dim=cfg.dense_dim, dense_queries=128,
        )


class KernelCheck:
    """Compiles each scan program before it runs and asserts that the Pallas
    kernel is in it; adds up the compile time. ``strict=False`` skips both
    (a CPU rehearsal, whose kernels run in interpret mode)."""

    def __init__(self, strict: bool = True):
        self.strict = strict
        self.compile_s = 0.0

    def expect_kernel(self, label: str, jitted, *args) -> None:
        if not self.strict:
            return
        t0 = time.perf_counter()
        text = jitted.lower(*args).compile().as_text()
        self.compile_s += time.perf_counter() - t0
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{label}: no tpu_custom_call in the compiled program")


def report(phase: str, started: float, **fields) -> None:
    wall = time.perf_counter() - started
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] ok {body} (set-up/smoke wall {wall:.1f} s)", flush=True)


def assert_same(label: str, ids, scores, ref_ids, ref_scores) -> None:
    """The same ids and the same score bytes."""
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids), err_msg=f"{label}: ids")
    if np.asarray(scores).tobytes() != np.asarray(ref_scores).tobytes():
        raise AssertionError(f"{label}: score bytes differ")


def assert_same_runs(label: str, runs, ref_runs) -> None:
    """Run files of every scorer byte-identical to the reference's."""
    for name in SCORERS:
        with open(runs[name], "rb") as f_a, open(ref_runs[name], "rb") as f_b:
            if f_a.read() != f_b.read():
                raise AssertionError(f"{label} {name}: run files differ")


def experiment_spec(w: Widths, *, n_docs: int, n_shards: int, use_kernel: bool):
    return ExperimentSpec(
        name="scan_50q",
        grids=tuple(GridSpec(s) for s in SCORERS),
        n_docs=n_docs,
        n_queries=w.n_queries,
        vocab=w.vocab,
        max_doc_len=w.doc_len,
        max_q_len=w.q_len,
        k=w.k,
        chunk_size=w.chunk,
        n_shards=n_shards,
        use_kernel=use_kernel,
        baseline=SCORERS[0],
    )


def run_fresh(spec, out: Path, coll, seed: int) -> dict:
    """One experiment in an emptied directory: nothing is resumed."""
    shutil.rmtree(out, ignore_errors=True)
    return runner.run_experiment(
        spec, out_dir=str(out), seed=seed, resume=False, collection=coll,
        tuning=TuningConfig(),
    )


def check_kernel_fold(check: KernelCheck, spec, coll) -> None:
    """The experiment's compiled segment fold must hold the kernel."""
    from repro import cluster
    from repro.core import topk

    scorers = spec.scorers()
    fold = cluster.segment_fold(
        scorers, k=spec.k, chunk_size=spec.chunk_size, use_kernel=True,
        tuning=TuningConfig(),
    )
    rows = spec.chunk_size * spec.segment_chunks
    seg = (coll.corpus.tokens[:rows], coll.corpus.lengths[:rows])
    state = topk.init_host(spec.k, (len(scorers), spec.n_queries))
    check.expect_kernel(
        "experiment fold", fold._fn, state, coll.queries, seg, coll.stats, np.int32(0)
    )


def phase_experiment(w: Widths, out: Path, seed: int, check: KernelCheck):
    t0 = time.perf_counter()
    fold_spec = experiment_spec(w, n_docs=w.n_docs, n_shards=1, use_kernel=False)
    kern_spec = dataclasses.replace(fold_spec, use_kernel=True)
    coll = runner.prepare_collection(fold_spec, seed=seed)
    t_prep = time.perf_counter() - t0
    check_kernel_fold(check, kern_spec, coll)
    t1 = time.perf_counter()
    fold = run_fresh(fold_spec, out / "experiment_fold", coll, seed)
    t_fold = time.perf_counter() - t1
    t1 = time.perf_counter()
    kern = run_fresh(kern_spec, out / "experiment_kernel", coll, seed)
    t_kern = time.perf_counter() - t1

    assert_same_runs("kernel experiment", kern["runs"], fold["runs"])
    idx = invindex.build_index(coll.corpus.tokens, coll.corpus.lengths, vocab=w.vocab)
    idx_stats = invindex.stats_from_index(idx)
    index_gap = 0.0
    for name in SCORERS:
        _, k_scores, _ = trec.read_run(kern["runs"][name], depth=w.k)
        ref_scores, _ = invindex.search(idx, coll.queries, idx_stats, k=w.k, scorer=name)
        np.testing.assert_allclose(
            k_scores, ref_scores, rtol=INDEX_RTOL, atol=INDEX_ATOL,
            err_msg=f"kernel {name} scores vs inverted index",
        )
        index_gap = max(index_gap, float(np.abs(k_scores - ref_scores).max()))
    report(
        "experiment", t0, docs=w.n_docs, queries=w.n_queries, k=w.k,
        models="+".join(SCORERS), run_files_identical=True,
        kernel_vs_index_max_abs=f"{index_gap:.3e}",
        map=f"{kern['metrics'][SCORERS[0]]['map']:.4f}",
        prepare_s=f"{t_prep:.1f}", fold_run_s=f"{t_fold:.1f}",
        kernel_run_s=f"{t_kern:.1f}",
    )
    return coll


def serve_waves(coll, w: Widths, seed: int) -> list[np.ndarray]:
    return [
        synthetic.make_queries(
            coll.corpus, n_queries=w.n_queries, max_q_len=w.q_len, seed=seed + 100 + i
        )
        for i in range(WAVES)
    ]


def serve(session, waves) -> list[tuple[np.ndarray, np.ndarray]]:
    """Answer each wave through the service; ``(ids, scores)`` per wave in
    submission order."""
    service = RetrievalService(
        {"lexical": session}, max_batch=len(waves[0]), max_bucket=len(waves[0])
    )
    out = []
    for wave in waves:
        rids = [service.submit(q) for q in wave]
        got = service.drain()
        if sorted(got) != sorted(rids):
            raise AssertionError("the service did not answer every request once")
        out.append(
            (np.stack([got[r].ids for r in rids]), np.stack([got[r].scores for r in rids]))
        )
    return out


def phase_serve(w: Widths, coll, seed: int, check: KernelCheck) -> None:
    t0 = time.perf_counter()
    tokens, lengths = coll.corpus.tokens, coll.corpus.lengths
    args = dict(k=w.k, chunk_size=w.chunk, stats=coll.stats)
    session = LexicalSession(tokens, lengths, SCORERS[0], use_kernel=None, **args)
    fold = LexicalSession(tokens, lengths, SCORERS[0], use_kernel=False, **args)
    waves = serve_waves(coll, w, seed)
    check.expect_kernel(
        "serve session", session._scan, waves[0], session._docs, session._stats
    )
    for i, (wave, (ids, scores)) in enumerate(zip(waves, serve(session, waves))):
        ref = fold.search(wave)
        assert_same(f"serve wave {i}", ids, scores, ref.ids, ref.scores)
    report(
        "serve", t0, waves=WAVES, requests=WAVES * w.n_queries,
        answers_identical_to_fold=True,
    )
    del session, fold


def dense_vectors(n: int, dim: int, seed: int) -> np.ndarray:
    """Unit vectors rounded to multiples of 1/64: their dot products are
    exact in float32 and bfloat16 inputs, whatever the summation order."""
    v = synthetic.make_dense_corpus(n_docs=n, dim=dim, seed=seed)
    return (np.round(v * 64) / 64).astype(np.float32)


def phase_dense(w: Widths, seed: int, check: KernelCheck) -> None:
    t0 = time.perf_counter()
    vecs = dense_vectors(w.n_docs, w.dense_dim, seed + 4)
    q = dense_vectors(w.dense_queries, w.dense_dim, seed + 5)
    session = DenseSession(vecs, k=w.k, chunk_size=w.chunk, use_kernel=True)
    fold = DenseSession(vecs, k=w.k, chunk_size=w.chunk, use_kernel=False)
    check.expect_kernel("dense session", session._scan, q, session._vectors)
    got, ref = session.search(q), fold.search(q)
    assert_same("dense", got.ids, got.scores, ref.ids, ref.scores)
    report(
        "dense", t0, docs=w.n_docs, dim=w.dense_dim, queries=w.dense_queries,
        k=w.k, answers_identical_to_fold=True,
    )


def phase_sharded(w: Widths, out: Path, seed: int, check: KernelCheck) -> None:
    devices = jax.devices()
    n_dev = len(devices)
    n_docs = 4 * w.n_docs
    t0 = time.perf_counter()
    ref_spec = experiment_spec(w, n_docs=n_docs, n_shards=1, use_kernel=True)
    coll = runner.prepare_collection(ref_spec, seed=seed)
    t_prep = time.perf_counter() - t0
    check_kernel_fold(check, ref_spec, coll)
    ref = run_fresh(ref_spec, out / "sharded_ref", coll, seed)
    shd = run_fresh(
        dataclasses.replace(ref_spec, n_shards=4), out / "sharded_4", coll, seed
    )
    assert_same_runs("sharded experiment", shd["runs"], ref["runs"])
    placed = [s["device"] for s in shd["job"]["shards"]]
    if len(set(placed)) != n_dev:
        raise AssertionError(f"4 shards ran on {placed}, not on all {n_dev} devices")
    report(
        "sharded-experiment", t0, docs=n_docs, shards=4, shard_devices=",".join(placed),
        reference_device=ref["job"]["shards"][0]["device"], run_files_identical=True,
        prepare_s=f"{t_prep:.1f}",
    )

    t0 = time.perf_counter()
    grid = (2, n_dev // 2) if n_dev % 2 == 0 else (n_dev, 1)
    mesh = Mesh(np.asarray(devices).reshape(grid), ("data", "model"))
    tokens, lengths = coll.corpus.tokens, coll.corpus.lengths
    sharded = ShardedLexicalSession(
        mesh, tokens, lengths, SCORERS[0], k=w.k, chunk_size=w.chunk,
        vocab=w.vocab, use_kernel=None,
    )
    if not sharded.use_kernel and check.strict:
        raise AssertionError("the sharded session did not resolve to the kernel")
    one = LexicalSession(
        tokens, lengths, SCORERS[0], k=w.k, chunk_size=w.chunk, stats=coll.stats,
        use_kernel=None,
    )
    waves = serve_waves(coll, w, seed)
    for i, (wave, (ids, scores)) in enumerate(zip(waves, serve(sharded, waves))):
        want = one.search(wave)
        assert_same(f"sharded serve wave {i}", ids, scores, want.ids, want.scores)
    report(
        "sharded-serve", t0, mesh="x".join(map(str, grid)),
        mesh_devices=",".join(f"{d.platform}:{d.id}" for d in mesh.devices.flat),
        waves=WAVES, requests=WAVES * w.n_queries, answers_identical_to_one_chip=True,
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "results" / "chip_smoke"))
    args = ap.parse_args(argv)

    backend = ops.kernel_backend()
    if backend != "compiled":
        sys.exit(
            f"chip_smoke: Pallas kernels resolve to {backend!r} on the "
            f"{jax.default_backend()!r} backend; this smoke needs compiled "
            "kernels on a TPU"
        )
    cache = use_compile_cache()

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU; JAX found {device}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, found {device}")
    report("device", t0, **device, compile_cache=cache)

    w, out, check = Widths.scan_50q(), Path(args.out), KernelCheck()
    if args.chips == 4:
        phase_sharded(w, out, args.seed, check)
    else:
        coll = phase_experiment(w, out, args.seed, check)
        phase_serve(w, coll, args.seed, check)
        phase_dense(w, args.seed, check)
    print(f"[compile] kernel programs compiled in {check.compile_s:.1f} s (set-up timing)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
