#!/usr/bin/env python3
"""Find a serve cell's highest sustained rate with one sweep on the chip.

    python3 benchmarks/chip/sweep.py --workload marco-lex-poisson \\
        --seed 5 --seconds 10 --out results/sweep-lex.json

One process builds the cell's deployment once, times one full block of the
largest bucket, and offers open-loop Poisson load at fractions of the rate
that block time allows (``--fractions``), each for ``--seconds``. A rate is
sustained when the requests answered per second reach 95% of the offered
rate and the latency does not grow through the run (the median of the last
third is within twice that of the first third). The result names the highest
sustained rate and 4/5 of it; write that number into the cell's traffic file
(``rate_qps``) and the sweep's table into ``PERF.md``.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402

from chipbench import data, harness, schedules, spec  # noqa: E402


def point(run, driver, st, pool, rate: float, seconds: float, rng) -> dict:
    n = max(1, int(round(rate * seconds)))
    st.due = schedules.fixed_count_poisson(n, seconds, rng)
    st.queries = pool[np.arange(n) % len(pool)]
    st.results = {}
    st.service.metrics.clear()
    run.seconds = seconds
    rec = driver.serve_window(run, st)
    lat = (rec["reply"] - rec["due"]) * 1e3
    third = max(1, n // 3)
    first, last = np.median(lat[:third]), np.median(lat[-third:])
    completed = float(np.isfinite(rec["reply"]).sum() / np.nanmax(rec["reply"]))
    return {
        "offered_qps": rate, "completed_qps": completed,
        "p50_ms": float(np.median(lat)), "p95_ms": float(np.quantile(lat, 0.95)),
        "first_third_p50_ms": float(first), "last_third_p50_ms": float(last),
        "mean_block_rows": float(rec["blocks"][:, 2].mean()),
        "sustained": bool(completed >= 0.95 * rate and last <= 2 * first),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fractions", default="0.3,0.5,0.7,0.85,1.0,1.15,1.3")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    layout = spec.Layout()
    cell = spec.load_cell(layout, args.workload)
    driver = layout.module("drivers", cell.traffic["driver"])
    device = harness.device_info(cell.chips)
    harness.use_compile_cache(spec.REPO_ROOT)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        run = harness.Run(
            cell=cell, layout=layout, seed=args.seed, seconds=args.seconds, trace=False,
            workdir=Path(tmp), peaks=layout.peaks(device["kind"]), t_process=T_PROCESS,
            profiler=harness.Profiler(False, Path(tmp)),
        )
        st = driver.setup(run)
        session = st.service.sessions[st.kind]
        block = max(driver.bucket_ladder(driver.TuningConfig()))
        rows = np.resize(st.queries, (block, *st.queries.shape[1:]))
        t0 = time.monotonic()
        for _ in range(5):
            session.search(rows)
        block_s = (time.monotonic() - t0) / 5
        capacity = block / block_s
        fractions = [float(f) for f in args.fractions.split(",")]
        pool = st.queries
        rng = data.rng_of(args.seed, 11)
        table = []
        for f in fractions:
            table.append(point(run, driver, st, pool, f * capacity, args.seconds, rng))
            print(json.dumps(table[-1]), flush=True)
    ok = [p["offered_qps"] for p in table if p["sustained"]]
    best = max(ok) if ok else None
    out = {
        "workload": args.workload, "device": device, "block_rows": block,
        "block_ms": block_s * 1e3, "block_capacity_qps": capacity, "seconds": args.seconds,
        "table": table, "highest_sustained_qps": best,
        "cell_rate_qps": None if best is None else 0.8 * best,
    }
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items() if k != "table"}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
