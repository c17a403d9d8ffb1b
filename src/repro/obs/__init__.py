"""Cluster-wide tracing & metrics — the observability layer.

MIREX's viability argument is operational, so the framework's hot layers
(scan jobs, the shard scheduler, the prefetch pipeline, the checkpoint
writer, serve dispatch) are permanently instrumented against one
process-wide pair of instruments:

* :func:`tracer` — the active :class:`~repro.obs.trace.Tracer` (span
  timelines + instant markers; **disabled by default** and near-zero-cost
  while disabled, so instrumentation lives inside per-segment loops);
* :func:`metrics` — the active :class:`~repro.obs.metrics.Metrics`
  registry (counters / gauges / p50-p95-p99 histograms; always on — an
  observation is a couple of arithmetic ops under a short lock).

Enable tracing by installing an enabled tracer for a scope::

    from repro import obs
    with obs.session() as (tr, met):          # fresh enabled pair
        job = cluster.run_sharded_scan_job(...)
    obs.export.write_chrome_trace("trace.json", tr, metrics=met)

or pass ``--trace-out trace.json`` to ``repro.launch.experiment``, which
wraps the whole lifecycle and writes the Chrome trace, the JSONL event
log, and the ``report.json`` ``job.obs`` rollup.

While an enabled tracer is installed, JAX's own compile-path events land
in it as ``jit.trace`` / ``jit.lower`` / ``jit.compile`` /
``jit.cache_load`` spans (:func:`watch_compiles`): a jit cache miss inside
a measured stretch names itself.

The globals are plain module state, not contextvars, on purpose: the
instrumented layers hand work to long-lived helper threads (scheduler
workers, the checkpoint writer, the prefetch producer) that must record
into the *same* buffer as the thread that installed it — which contextvar
propagation across threads would silently break.

Tracing observes and never decides: no instrumented code path branches on
tracer state (beyond skipping the recording itself), so traced runs are
byte-identical to untraced ones — asserted by the chaos suite, which runs
with tracing ON.
"""

from __future__ import annotations

import contextlib
import platform
import sys
import threading

from repro.obs import export
from repro.obs.metrics import Counter, Gauge, Histogram, Metrics, latency_buckets
from repro.obs.trace import NULL_SPAN, SpanEvent, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Metrics",
    "NULL_SPAN",
    "SpanEvent",
    "Tracer",
    "export",
    "install",
    "latency_buckets",
    "metrics",
    "provenance",
    "session",
    "tracer",
    "watch_compiles",
]

# the process defaults: tracing off (guard-checked no-op), metrics on
_TRACER = Tracer(enabled=False)
_METRICS = Metrics()


def tracer() -> Tracer:
    """The active tracer (instrumented layers call this per operation, so
    an `install` mid-process takes effect everywhere immediately)."""
    return _TRACER


def metrics() -> Metrics:
    """The active metrics registry."""
    return _METRICS


def install(
    tracer: Tracer | None = None, metrics: Metrics | None = None
) -> tuple[Tracer, Metrics]:
    """Swap the active instruments; returns the previous pair (for restore).

    ``None`` leaves that instrument unchanged. Prefer :func:`session` in
    tests — it restores on exit. An enabled tracer also gets the compile
    spans (:func:`watch_compiles`), from its ``jit.watch`` instant on.
    """
    global _TRACER, _METRICS
    prev = (_TRACER, _METRICS)
    if tracer is not None:
        _TRACER = tracer
        if tracer.enabled:
            watch_compiles()
            # from here on this tracer holds the process's compile spans
            tracer.instant("jit.watch", "jit")
    if metrics is not None:
        _METRICS = metrics
    return prev


# JAX's duration events on the compile path (``jax/_src/dispatch.py``,
# ``jax/_src/compiler.py``) and the span each becomes. A persistent-cache hit
# fires ``jit.cache_load`` inside the ``jit.compile`` that wraps the lookup.
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
_WATCH_LOCK = threading.Lock()
_WATCHING = False


def _on_jax_duration(event: str, duration_secs: float, **kwargs) -> None:
    tr = _TRACER
    if not tr.enabled:
        return
    name = JIT_EVENTS.get(event)
    if name is not None:
        t1 = tr.now()  # JAX reports at the event's end
        tr.record(name, t1 - duration_secs, t1, "jit", fun=kwargs.get("fun_name", ""))


def watch_compiles() -> None:
    """Record JAX's trace, lower, compile and cache-load events as ``jit.*``
    spans of whichever tracer is active when they fire (nothing while it is
    disabled). One listener for the process, registered on the first call;
    :func:`install` of an enabled tracer makes that call."""
    global _WATCHING
    with _WATCH_LOCK:
        if _WATCHING:
            return
        import jax.monitoring  # deferred: obs must import without jax

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _WATCHING = True


@contextlib.contextmanager
def session(tracer: Tracer | None = None, metrics: Metrics | None = None):
    """Scoped observability: install a (default: fresh, enabled) tracer and
    a fresh metrics registry, restore the previous pair on exit. Yields
    ``(tracer, metrics)``."""
    tr = Tracer() if tracer is None else tracer
    met = Metrics() if metrics is None else metrics
    prev = install(tr, met)
    try:
        yield tr, met
    finally:
        install(*prev)


def provenance() -> dict:
    """Where a measurement was taken: host, platform, backend, versions.

    Stamped into every ``BENCH_*.json`` so perf trajectories recorded on
    different machines/backends are comparable (or visibly not).
    """
    import jax  # deferred: obs must import without initializing backends

    return {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
    }
