"""Reductions that several metric readers share.

A reader returns ``None`` where its run holds nothing to read (an untraced
run, a trace with no call of its kernel), and the metric is then left out of
the result line. A roofline share is never made up: without a kernel event
there is no share.
"""

from __future__ import annotations

import numpy as np


NO_REPLY_MS = 1e9  # a request that never got a reply: beyond every tail


def latencies_ms(run) -> np.ndarray:
    """Every offered request's time from due to reply, ``NO_REPLY_MS`` where
    no reply came (a run with one is not correct either)."""
    rec = run.records
    lat = (rec["reply"] - rec["due"]) * 1e3
    return np.where(np.isfinite(lat), lat, NO_REPLY_MS)


def span_seconds(run, name: str) -> float:
    return float(sum(s.dur for s in run.spans if s.name == name))


def roofline(run, pattern: str, count: str, shape: dict) -> dict | None:
    """A kernel's share of its roofline in the traced window.

    ``count`` names ``counts/<count>.py``, whose ``per_call(shape)`` gives
    the bytes and operations one call needs. The least time of the window's
    calls is the larger of bytes over peak bandwidth and operations over
    peak rate; the share is that over the kernel's measured time.
    """
    trace = run.device_trace
    if trace is None:
        return None
    calls, seconds = trace.kernel(pattern)
    if calls == 0 or seconds <= 0:
        return None
    need = run.layout.module("counts", count).per_call(shape)
    t_bytes = calls * need["bytes"] / run.peaks["hbm_bytes_per_s"]
    t_ops = 0.0
    if need.get("flops"):
        t_ops = calls * need["flops"] / run.peaks["bf16_flops_per_s"]
    bound = "hbm_bytes" if t_bytes >= t_ops else "bf16_flops"
    return {"value": 100.0 * max(t_bytes, t_ops) / seconds, "bound": bound}


def idle_percent(run) -> float | None:
    trace = run.device_trace
    if trace is None:
        return None
    share = trace.idle_share()
    return None if share is None else 100.0 * share
