"""From a profiler trace to device busy time, idle gaps and kernel time.

:func:`load_xplane` turns JAX's ``.xplane.pb`` into plain lists
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns]]}]}]}``), so that the reduction below runs the same on a recorded
fixture as on a fresh trace. Device planes are ``/device:TPU:<n>``; their op
line (``XLA Ops`` where there is one) holds one event per device operation,
named by its whole HLO instruction, custom-call target and all. Busy time is the union of those intervals inside the traced
window, averaged over the chips; an idle gap is a stretch of the window in
which no operation ran, named by the innermost host span open at its middle.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINES = ("XLA Ops", "XLA Modules")


def load_xplane(path: str) -> dict:
    """Planes, lines and events of a ``.xplane.pb`` as plain lists."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(name: str) -> str:
    """``fusion.3`` from an op event named by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...)``), as TPU traces name them."""
    return name.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class DeviceTrace:
    """The device side of one traced window ``[lo_ns, hi_ns]``."""

    ops: dict[str, list]  # device plane name -> op events [name, start, dur]
    host: list[tuple[str, float, float]]  # host spans (name, start_ns, end_ns)
    lo_ns: float
    hi_ns: float

    @classmethod
    def from_data(cls, data: dict, lo_ns: float, hi_ns: float, host_extra=()) -> "DeviceTrace":
        ops, host = {}, list(host_extra)
        for plane in data["planes"]:
            lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
            if DEVICE_PLANE.fullmatch(plane["name"]):
                name = next((n for n in OPS_LINES if n in lines), None)
                if name is not None:
                    ops[plane["name"]] = lines[name]
            elif plane["name"].startswith("/host:CPU"):
                for events in lines.values():
                    host.extend((e[0], e[1], e[1] + e[2]) for e in events if e[2] > 0)
        return cls(ops=ops, host=host, lo_ns=lo_ns, hi_ns=hi_ns)

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) * 1e-9

    def _intervals(self, events):
        return [(e[1], e[1] + e[2]) for e in events]

    def busy_s(self) -> float | None:
        """Seconds in which some operation ran, averaged over the chips;
        None where the trace holds no device operation."""
        if not any(self.ops.values()):
            return None
        per_chip = [
            union_ns(self._intervals(ev), self.lo_ns, self.hi_ns) for ev in self.ops.values()
        ]
        return sum(per_chip) / len(per_chip) * 1e-9

    def idle_share(self) -> float | None:
        busy = self.busy_s()
        if busy is None or self.window_s <= 0:
            return None
        return 1.0 - busy / self.window_s

    def kernel(self, pattern: str) -> tuple[int, float]:
        """``(calls, seconds)`` of the device events whose name matches
        ``pattern``, inside the window, summed over the chips."""
        rx = re.compile(pattern)
        calls, ns = 0, 0.0
        for events in self.ops.values():
            for name, start, dur in events:
                if start >= self.lo_ns and start + dur <= self.hi_ns and rx.search(name):
                    calls += 1
                    ns += dur
        return calls, ns * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` device operations, by name, that took most time."""
        total: dict[str, float] = {}
        for events in self.ops.values():
            for name, start, dur in events:
                if start >= self.lo_ns and start < self.hi_ns:
                    name = op_name(name)
                    total[name] = total.get(name, 0.0) + dur * 1e-9
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, n: int = 10) -> list[list]:
        """Idle seconds summed by the innermost host span open at each gap's
        middle (``host idle`` where none is), the ``n`` largest."""
        total: dict[str, float] = {}
        for events in self.ops.values():
            for s, e in gaps_ns(self._intervals(events), self.lo_ns, self.hi_ns):
                mid = 0.5 * (s + e)
                open_ = [h for h in self.host if h[1] <= mid <= h[2]]
                name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "host idle"
                total[name] = total.get(name, 0.0) + (e - s) * 1e-9 / len(self.ops)
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
