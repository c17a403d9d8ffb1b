"""A tiny copy of the benchmark's cells for the CPU tests: the same drivers,
metrics, references and counts, with configurations cut to a few thousand
documents and a benchmark file of their own in a scratch root that is
searched before ``benchmarks/chip``."""

from __future__ import annotations

import json
from pathlib import Path

from chipbench import spec

CELLS = {  # tiny cell -> (tiny config, tiny traffic, the real cell it copies)
    "tiny-batch": ("tiny-web", "experiments", "web09-batch"),
    "tiny-lex": ("tiny-marco", "tiny-lex-poisson", "marco-lex-poisson"),
    "tiny-dense": ("tiny-marco", "tiny-dense-poisson", "marco-dense-poisson"),
}
SMALL = {"n_docs": 2048, "doc_len": [4, 32], "vocab": 512, "k": 20, "chunk_size": 512}


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def layout(root: Path) -> spec.Layout:
    """Write the tiny cells under ``root``; returns the layout that finds
    them (and everything else in ``benchmarks/chip``)."""
    real = spec.Layout()
    bench = real.benchmark()
    web = real.json("configs", "clueweb09b-web09")
    web.update(SMALL, name="tiny-web", n_queries=6, query_terms=[1, 3], segment_chunks=2,
               qrels_per_query=5, compare={"candidates": 64})
    marco = real.json("configs", "msmarco-passage")
    marco.update(SMALL, name="tiny-marco", dim=128,
                 compare={"candidates": 64, "sample_requests": 16})
    _write(root / "configs" / "tiny-web.json", web)
    _write(root / "configs" / "tiny-marco.json", marco)
    for kind in ("lex", "dense"):
        traffic = real.json("traffic", f"{kind}-poisson")
        traffic["rate_qps"] = 60.0
        _write(root / "traffic" / f"tiny-{kind}-poisson.json", traffic)
    rename = {real_cell: tiny for tiny, (_, _, real_cell) in CELLS.items()}
    bench["configs"] = [
        {"name": n, "source": "tiny", "file": f"configs/{n}.json", "reduced": [], "why": "tests"}
        for n in ("tiny-web", "tiny-marco")
    ]
    bench["workloads"] = [
        {"name": tiny, "config": cfg, "traffic": traffic, "chips": 1, "why": "tests"}
        for tiny, (cfg, traffic, _) in CELLS.items()
    ]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"] if w in rename]
    _write(root / "BENCHMARK.json", bench)
    return spec.Layout(bench_file=root / "BENCHMARK.json", roots=(root, spec.BENCH_ROOT))
