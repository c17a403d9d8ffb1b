"""Reductions of a device trace of one program over several chips: each
block's call of a kernel on every chip, and the stretch after it in which a
named part of the program ran."""

from __future__ import annotations

import bisect
import re


def calls(trace, pattern: str) -> dict[str, list[tuple[float, float]]]:
    """``(start, end)`` ns of the events matching ``pattern``, inside the
    traced window, per chip, in time order."""
    rx = re.compile(pattern)
    out = {}
    for chip, events in trace.ops.items():
        out[chip] = sorted(
            (s, s + d) for name, s, d in events
            if s >= trace.lo_ns and s + d <= trace.hi_ns and rx.search(name)
        )
    return out


GAP_NS = 1e6  # ops of one program follow each other within microseconds; programs are ms apart


def after_each_call(trace, kernel: str, named: str) -> list[float]:
    """Per chip and per call of ``kernel``: ns from the start of the first
    event matching ``named`` to the end of the last one, among the events
    that follow the call in the same program (back to back, each starting
    within ``GAP_NS`` of the previous one's end)."""
    rx = re.compile(named)
    kernel_calls = calls(trace, kernel)
    out = []
    for chip, events in trace.ops.items():
        events = sorted(
            (s, s + d, name) for name, s, d in events
            if s >= trace.lo_ns and s + d <= trace.hi_ns
        )
        starts = [s for s, _, _ in events]
        for _, call_end in kernel_calls.get(chip, []):
            i, t, marked = bisect.bisect_left(starts, call_end), call_end, []
            while i < len(events) and events[i][0] - t <= GAP_NS:
                s, e, name = events[i]
                if rx.search(name):
                    marked.append((s, e))
                t, i = max(t, e), i + 1
            if marked:
                out.append(marked[-1][1] - marked[0][0])
    return out


def blocks(trace, kernel: str) -> list[list[tuple[float, float]]]:
    """The calls of ``kernel`` grouped into blocks, one call a chip: calls on
    different chips whose starts lie within half a call of the block's first.
    Only blocks with a call on every chip of the trace are kept."""
    chips = calls(trace, kernel)
    flat = sorted((s, e, chip) for chip, cs in chips.items() for s, e in cs)
    groups: list[list[tuple[float, float, str]]] = []
    for s, e, chip in flat:
        g = groups[-1] if groups else None
        if g and s - g[0][0] < 0.5 * (e - s) and chip not in {c for _, _, c in g}:
            g.append((s, e, chip))
        else:
            groups.append([(s, e, chip)])
    return [[(s, e) for s, e, _ in g] for g in groups if len(g) == len(chips)]
