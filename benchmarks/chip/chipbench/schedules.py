"""Arrival schedules of the open-loop traffic mixes.

``burst_schedule`` is a copy of the one in ``repro.serve.loadgen``, kept
here so that the yardstick does not move with the program. Poisson traffic
is drawn conditioned on its count (``fixed_count_poisson``) rather than by
``loadgen.poisson_schedule``'s free-running gaps, so that every seed offers
the same number of requests in the window. ``arrivals`` picks one by the
traffic file's ``arrivals`` key.
"""

from __future__ import annotations

import numpy as np


def burst_schedule(
    qps: float,
    n: int,
    *,
    seed: int = 0,
    start: float = 0.0,
    burst_factor: float = 4.0,
    duty: float = 0.25,
    period_s: float = 1.0,
) -> np.ndarray:
    """Bursty arrivals: a Poisson process whose rate alternates each
    ``period_s`` between ``qps * burst_factor`` (for the ``duty`` fraction
    of the period) and a floor rate that keeps the mean near ``qps``."""
    if qps <= 0:
        raise ValueError("qps must be positive")
    if not 0.0 < duty < 1.0:
        raise ValueError(f"duty must be in (0,1): {duty}")
    if burst_factor < 1.0:
        raise ValueError(f"burst_factor must be >= 1: {burst_factor}")
    rng = np.random.default_rng(seed)
    high = qps * burst_factor
    low = max(qps * (1.0 - burst_factor * duty) / (1.0 - duty), qps * 0.05)
    out = np.empty(n)
    t = start
    for i in range(n):
        rate = high if (t % period_s) < duty * period_s else low
        t += rng.exponential(1.0 / rate)
        out[i] = t
    return out


def fixed_count_poisson(n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """A Poisson process conditioned on ``n`` arrivals in ``[0, seconds)``:
    sorted uniform draws. Every seed offers the same number of requests."""
    return np.sort(rng.random(n) * seconds)


def arrivals(traffic: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times, seconds after the window opens, for a traffic file."""
    rate = float(traffic["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    kind = traffic["arrivals"]
    if kind == "poisson":
        return fixed_count_poisson(n, seconds, rng)
    if kind == "burst":
        due = burst_schedule(
            rate, 4 * n, seed=int(rng.integers(1 << 62)),
            burst_factor=float(traffic["burst_factor"]), duty=float(traffic["duty"]),
            period_s=float(traffic["period_s"]),
        )
        return due[due < seconds]
    raise ValueError(f"unknown arrivals {kind!r}")
