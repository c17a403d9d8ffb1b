"""Pipelined vs sequential scan executor on the checkpointed cluster job.

Times the same 4-shard, multi-model, segment-checkpointed scan job
(`cluster.run_sharded_scan_job`) through both executors on 4 virtual
devices:

* **sequential** (``pipelined=False``) — shards run one after another,
  each shard's doc slice is staged on its device up front, and every
  segment's ``save → progress → prune`` commit blocks the fold;
* **pipelined** (``pipelined=True``) — shards run concurrently on the
  device-aware worker pool, segments double-buffer host→device under the
  previous segment's fold, and commits run on the async writer thread
  behind a drain barrier.

Both executors share one compiled fold (`cluster.segment_fold`), and the
benchmark asserts their merged states — and the checkpoint step layouts
they leave behind — are byte-identical, which is the whole executor
contract: overlap is invisible in the artifacts. Runs in a subprocess (the
virtual-device flag must precede JAX init). Writes ``BENCH_pipeline.json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.serve.bench import write_bench_json

_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, shutil, tempfile, time
import jax, jax.numpy as jnp
import numpy as np
from repro import checkpoint as ckpt
from repro import cluster
from repro.core import anchors, scoring
from repro.data import synthetic

N_DOCS, VOCAB, CHUNK, K, N_Q = 16384, 4096, 128, 20, 32
SEGMENT_CHUNKS = 16  # 2048-row segments -> 2 checkpoint commits per shard
N_SHARDS = 4
REPS = 5

corpus = synthetic.make_corpus(n_docs=N_DOCS, vocab=VOCAB, max_len=64, seed=31)
stats = anchors.collection_stats(
    jnp.asarray(corpus.tokens), jnp.asarray(corpus.lengths), vocab=VOCAB,
    chunk_size=CHUNK,
)
queries = jnp.asarray(synthetic.make_queries(corpus, n_queries=N_Q, seed=32))
docs = (
    np.asarray(corpus.tokens, dtype=np.int32),
    np.asarray(corpus.lengths, dtype=np.int32),
)
scorers = [scoring.make_variant("ql_lm"), scoring.make_variant("bm25")]
devices = jax.devices()
workers = min(N_SHARDS, os.cpu_count() or 1)

root = tempfile.mkdtemp(prefix="bench-pipeline-")


def run_job(pipelined, ckpt_dir):
    job = cluster.run_sharded_scan_job(
        queries, docs, scorers,
        k=K, chunk_size=CHUNK, segment_chunks=SEGMENT_CHUNKS,
        n_shards=N_SHARDS, stats=stats, ckpt_dir=ckpt_dir,
        devices=devices[:N_SHARDS], pipelined=pipelined,
        max_workers=workers if pipelined else None,
    )
    return jax.block_until_ready(job.state)


def time_executor(pipelined, tag):
    state = run_job(pipelined, os.path.join(root, f"warm-{tag}"))  # warmup+compile
    walls = []
    for r in range(REPS):
        d = os.path.join(root, f"{tag}-{r}")  # fresh dir: no resume shortcuts
        t0 = time.perf_counter()
        run_job(pipelined, d)
        walls.append(time.perf_counter() - t0)
    return state, min(walls)


seq_state, seq_wall = time_executor(False, "seq")
pipe_state, pipe_wall = time_executor(True, "pipe")

# the executor contract: overlap changes nothing observable
assert (np.asarray(pipe_state.ids) == np.asarray(seq_state.ids)).all()
assert (
    np.asarray(pipe_state.scores).tobytes() == np.asarray(seq_state.scores).tobytes()
)
for shard in range(N_SHARDS):
    sub = f"shard_{shard:04d}"
    assert (
        ckpt.all_steps(os.path.join(root, "seq-0", sub))
        == ckpt.all_steps(os.path.join(root, "pipe-0", sub))
    ), sub
    pseq = cluster.read_progress(os.path.join(root, "seq-0", sub))
    ppipe = cluster.read_progress(os.path.join(root, "pipe-0", sub))
    assert pseq == ppipe, sub

shutil.rmtree(root, ignore_errors=True)
print(json.dumps({
    "n_docs": N_DOCS, "n_queries": N_Q, "k": K, "chunk_size": CHUNK,
    "segment_chunks": SEGMENT_CHUNKS, "n_shards": N_SHARDS,
    "n_models": len(scorers), "n_devices": len(devices),
    "max_workers": workers,
    "sequential_wall_s": seq_wall,
    "pipelined_wall_s": pipe_wall,
    "speedup_x": seq_wall / pipe_wall,
    "docs_per_s_sequential": N_DOCS / seq_wall,
    "docs_per_s_pipelined": N_DOCS / pipe_wall,
    "bit_identical": True,
}))
"""


def run(csv_rows: list):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.pop("XLA_FLAGS", None)  # the worker pins its own device count
    # the worker measures virtual CPU devices; it must never contend with
    # this process (which has imported JAX) for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    # the hard claim: the pipelined executor's artifacts are byte-identical
    # to the sequential reference. Speed is asserted only where the
    # executor can actually overlap (multiple workers): on a 1-core host
    # the two executors differ by noise plus thread overhead, and failing
    # the bench there would punish the hardware, not the code
    assert payload["bit_identical"]
    if payload["max_workers"] > 1:
        assert payload["speedup_x"] > 1.0, payload["speedup_x"]

    write_bench_json(payload, "BENCH_pipeline.json")
    csv_rows.append(
        (
            "pipeline_scan/sequential",
            payload["sequential_wall_s"] * 1e6,
            f"docs_per_s={payload['docs_per_s_sequential']:.0f}",
        )
    )
    csv_rows.append(
        (
            "pipeline_scan/pipelined",
            payload["pipelined_wall_s"] * 1e6,
            f"docs_per_s={payload['docs_per_s_pipelined']:.0f};"
            f"speedup_x={payload['speedup_x']:.2f}",
        )
    )
    return True
