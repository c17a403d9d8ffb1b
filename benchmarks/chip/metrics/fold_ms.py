"""Mean time from the dispatch of a segment's fold to its result on the
host, over the whole window: the end of the segment's ``ckpt.fetch`` (the
checkpoint writer's copy of the state, the first wait on the fold) less the
start of its ``segment.fold`` (the dispatch).

Each ``segment.fold`` (shard, segment) is paired with the first
``ckpt.fetch`` of the same shard at ``step == segment + 1`` that ends after
it: the window's experiments reuse the same pairs. A writer that falls
behind starts the fetch late, and that backlog inflates the reading; the
fetch's ``queued`` attribute counts the writer tasks waiting when it began.
"""

import bisect

import numpy as np


def read(run):
    ends = {}  # (shard, step) -> sorted fetch end times
    for s in run.spans:
        if s.name == "ckpt.fetch":
            ends.setdefault((s.attrs.get("shard"), s.attrs.get("step")), []).append(s.ts + s.dur)
    if not ends:
        return None
    for v in ends.values():
        v.sort()
    waits = []
    for s in run.spans:
        if s.name != "segment.fold":
            continue
        seg = s.attrs.get("segment")
        got = [] if seg is None else ends.get((s.attrs.get("shard"), seg + 1), [])
        i = bisect.bisect_left(got, s.ts + s.dur)
        if i < len(got):
            waits.append(got[i] - s.ts)
    if not waits:
        return None
    return float(np.mean(waits) * 1e3)
