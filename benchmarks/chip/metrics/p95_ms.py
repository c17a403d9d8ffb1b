"""95th percentile, over every request offered in the window, of the time
from when it was due to its reply (exact, from each request's own times)."""

import numpy as np

from chipbench import readers


def read(run):
    if "due" not in run.records:
        return None
    return float(np.quantile(readers.latencies_ms(run), 0.95))
