#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload web09-batch \\
        --seeds 11,12,13 --seconds 5 --out results/calibrate-web09.json

For each seed, in one process: one run of the cell (a short window at the
cell's own load) whose program is compared with the reference as in every
run, and the control (the reference computed one precision lower, bfloat16,
in the program's place) compared the same way. Prints and writes, for every
compared number, the program's readings (the largest is the lower reading)
and the control's (the smallest is the upper reading). The benchmark's own
runs never run the control.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = harness.run_cell(
            args.workload, seed=seed, seconds=args.seconds, trace=False,
            t_process=time.monotonic(), control=True,
        )
        rows.append({
            "seed": seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "control": {k[len("control."):]: v["value"] for k, v in res["control"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        })
        print(json.dumps(rows[-1]), flush=True)
    names = rows[0]["program"]
    summary = {
        n: {
            "lower": max(r["program"][n] for r in rows),
            "upper": min(r["control"].get(n, float("inf")) for r in rows),
        }
        for n in names
    }
    out = {"workload": args.workload, "seconds": args.seconds, "runs": rows, "summary": summary}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
