"""The dense score + top-k kernel's share of its roofline in the traced
stretch of a serve window: each call reads every stored vector for one
block of queries (the mean padded block size of the traced stretch)."""

import numpy as np

from chipbench import readers

KERNEL = r"score_topk"  # matched against the device op's name and HLO detail


def read(run):
    blocks, prof = run.records.get("blocks"), run.profiler
    if blocks is None or prof.mono_t0 is None or len(blocks) == 0:
        return None
    t0, t1 = prof.mono_t0 - run.window_start, prof.mono_t1 - run.window_start
    inside = blocks[(blocks[:, 0] >= t0) & (blocks[:, 0] <= t1)]
    rows = inside[:, 3] if len(inside) else blocks[:, 3]
    cfg = run.config
    shape = {"docs": cfg["n_docs"], "dim": cfg["dim"], "queries": float(np.mean(rows))}
    return readers.roofline(run, KERNEL, "score_topk", shape)
