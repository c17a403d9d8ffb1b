"""Mean time a request waited in the microbatcher, admission to the close of
its block: the ``queued_s`` of the program's ``serve.request`` spans that end
inside the device-traced stretch (the stretch ``device_idle.serve`` reads).

A request's due-to-reply time splits as ``queue_wait_ms`` (due to reply,
less its block's session time) ~= lateness + batcher_wait + reply: how late
the generator submitted it, this wait, and the copy of its block's results
to the host (the ``serve.reply`` span).
"""

import numpy as np


def read(run):
    prof = run.profiler
    if prof.mono_t0 is None or prof.mono_t1 is None:
        return None
    waits = [
        s.attrs["queued_s"] for s in run.spans
        if s.name == "serve.request" and "queued_s" in s.attrs
        and prof.mono_t0 <= s.ts + s.dur <= prof.mono_t1
    ]
    if not waits:
        return None
    return float(np.mean(waits) * 1e3)
