"""Mean, per request, of the time from when it was due to the dispatch of
its block: its due-to-reply time less its block's ``session.search`` time
(``BatchRecord.latency_s``)."""

import numpy as np


def read(run):
    rec = run.records
    if "block_of" not in rec or len(rec["blocks"]) == 0:
        return None
    ok = rec["block_of"] >= 0
    dispatch = rec["blocks"][rec["block_of"][ok], 1]
    return float(np.mean(rec["reply"][ok] - rec["due"][ok] - dispatch) * 1e3)
