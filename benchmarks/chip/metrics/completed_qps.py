"""Requests answered, over the time from the window's start to the last
reply."""

import numpy as np


def read(run):
    reply = run.records.get("reply")
    if reply is None:
        return None
    answered = np.isfinite(reply)
    return float(answered.sum() / np.nanmax(reply))
