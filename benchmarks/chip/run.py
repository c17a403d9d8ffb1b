#!/usr/bin/env python3
"""The MIREX chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload web09-batch --seed 7 \\
        --seconds 30 --trace 0

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics from a device trace (``--trace 1``) as one JSON object on the last
line of standard output, with ``correct`` from the comparison against the
plain reference, whose numbers and limits are also the last lines of
standard error. Exits non-zero, with no result, off a TPU or with fewer
chips than the cell asks for.
"""

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = harness.run_cell(
        args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_process=T_PROCESS,
    )
    print(harness.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
