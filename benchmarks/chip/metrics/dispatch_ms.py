"""Mean time of one block in the session, ``session.search`` to results on
the host (the service's ``BatchRecord.latency_s``)."""

import numpy as np


def read(run):
    blocks = run.records.get("blocks")
    if blocks is None or len(blocks) == 0:
        return None
    return float(np.mean(blocks[:, 1]) * 1e3)
