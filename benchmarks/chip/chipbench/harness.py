"""One run of one cell: device check, set-up, the measured window, the
comparison with the plain reference, the metrics and the result line.

The driver named by the cell's traffic file does the cell's own work in
three calls, each timed here:

* ``setup(run)``  -- make the inputs from the seed, build the system, warm
  every shape the window uses; returns the driver's state;
* ``window(run, state)`` -- run the traffic for ``run.seconds``, filling
  ``run.window_start`` and the driver's records;
* ``finish(run, state)`` -- free the system, run the plain reference and
  return the compared numbers as :class:`Check` s.

Metric readers (``metrics/<name>.py``) then reduce ``run`` to numbers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

from chipbench import devtrace, spec

TRACE_ANNOTATION = "chipbench.traced"


@dataclasses.dataclass
class Check:
    """One compared number beside its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Profiler:
    """The device trace of one stretch of the window (``--trace 1`` only).

    ``start``/``stop`` bracket the stretch; a host annotation opened right
    after ``start`` ties the trace's clock to ``time.monotonic``, so the
    program's own spans can be laid on the same timeline.
    """

    def __init__(self, enabled: bool, workdir: Path):
        self.enabled = enabled
        self.dir = workdir / "profile"
        self.mono_t0 = None
        self.mono_t1 = None
        self._annotation = None

    def start(self) -> None:
        if not self.enabled or self.mono_t0 is not None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation(TRACE_ANNOTATION)
        self._annotation.__enter__()
        self.mono_t0 = time.monotonic()

    @property
    def running(self) -> bool:
        return self._annotation is not None

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self.mono_t1 = time.monotonic()
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()

    def reduce(self, spans=()) -> devtrace.DeviceTrace | None:
        """Read the trace back; ``spans`` (name, t0, t1 on the monotonic
        clock) join the host timeline for naming idle gaps."""
        if self.mono_t0 is None:
            return None
        (path,) = glob.glob(str(self.dir / "**" / "*.xplane.pb"), recursive=True)
        data = devtrace.load_xplane(path)
        marks = [
            e for p in data["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"] if e[0] == TRACE_ANNOTATION
        ]
        if not marks:
            raise RuntimeError("the trace lost its window annotation")
        _, lo, dur = marks[0]
        offset = lo - self.mono_t0 * 1e9
        host = [(n, t0 * 1e9 + offset, t1 * 1e9 + offset) for n, t0, t1 in spans]
        shutil.rmtree(self.dir, ignore_errors=True)
        return devtrace.DeviceTrace.from_data(data, lo, lo + dur, host_extra=host)


@dataclasses.dataclass
class Run:
    """Everything one run knows; drivers fill the records, metric readers
    read them."""

    cell: spec.Cell
    layout: spec.Layout
    seed: int
    seconds: float
    trace: bool
    workdir: Path
    peaks: dict
    t_process: float
    profiler: Profiler
    window_start: float | None = None
    attempted: int = 0
    failed: int = 0
    records: dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)  # obs SpanEvents
    device_trace: devtrace.DeviceTrace | None = None
    memory_peak_bytes: int | None = None
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def note(self, text: str) -> None:
        """A line for standard error, before the checks."""
        self.notes.append(text)


def annotate(run: Run, name: str):
    """A host span in the device trace of a traced run (else nothing)."""
    if not run.trace:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def device_info(chips: int, *, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them; a machine without a
    TPU, or with fewer chips than the cell asks for, stops the run."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU; JAX found {info}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX found {info}")
    return info


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache(repo_root: Path) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else at the fixed path ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(repo_root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _metric_values(run: Run, metrics) -> tuple[dict, dict]:
    values, bounds = {}, {}
    for m in metrics:
        got = run.layout.module("metrics", m["name"]).read(run)
        if got is None:
            continue
        if isinstance(got, dict):
            if "bound" in got:
                bounds[m["name"]] = got["bound"]
            got = got["value"]
        values[m["name"]] = {"value": float(got), "unit": m["unit"]}
    return values, bounds


def run_cell(
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    layout: spec.Layout = spec.Layout(),
    require_tpu: bool = True,
    control: bool = False,
    err=sys.stderr,
) -> dict:
    """One run; returns the result object (the last stdout line).

    ``control=True`` (calibration and tests, never the benchmark's own runs)
    also reads the control, the reference computed one precision lower, in
    the program's place; its numbers go under ``control`` and do not count
    for ``correct``.
    """
    cell = spec.load_cell(layout, workload)
    driver = layout.module("drivers", cell.traffic["driver"])  # imports the program
    device = device_info(cell.chips, require_tpu=require_tpu)
    peaks = {}
    if require_tpu:
        peaks = layout.peaks(device["kind"])
        use_compile_cache(spec.REPO_ROOT)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        run = Run(
            cell=cell, layout=layout, seed=int(seed), seconds=float(seconds),
            trace=bool(trace), workdir=Path(tmp), peaks=peaks, t_process=t_process,
            profiler=Profiler(bool(trace), Path(tmp)),
        )
        state = driver.setup(run)
        driver.window(run, state)
        run.profiler.stop()
        run.memory_peak_bytes = memory_peak(cell.chips)
        if trace:
            timeline = run.records.get("timeline")
            if timeline is None:
                timeline = [(s.name, s.ts, s.ts + s.dur) for s in run.spans]
            run.device_trace = run.profiler.reduce(timeline)
        checks = driver.finish(run, state, control=control)
    controls = [c for c in checks if c.name.startswith("control.")]
    checks = [c for c in checks if not c.name.startswith("control.")]
    metrics, bounds = _metric_values(run, cell.per_layer if trace else cell.end_to_end)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    correct = bool(checks) and all(c.ok for c in checks) and run.failed == 0
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and run.device_trace is not None:
        dt = run.device_trace
        busy = dt.busy_s()
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = dt.window_s
        result["breakdown"] = {"device_ops": dt.top_ops(10), "idle_gaps": dt.idle_by_host(10)}
    if bounds:
        result["roofline_bound"] = bounds
    if control:
        result["control"] = {c.name: {"value": c.value, "limit": c.limit, "ok": c.ok}
                             for c in controls}
    # a non-finite reading (a score that never came) prints as a huge one
    result["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else 1e300, "limit": c.limit}
        for c in checks
    }
    for line in run.notes:
        print(line, file=err)
    for c in checks:
        print(
            f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}",
            file=err,
        )
    err.flush()
    return result


def dumps(result: dict) -> str:
    return json.dumps(result, allow_nan=False)
