"""The readers of the program's own spans (``batcher_wait_ms``, ``fold_ms``,
``window_compiles.*``) on hand-built runs, against values worked out by
hand, and on runs without those spans (the program before they existed),
where each reads nothing."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import harness, spec  # noqa: E402
from repro.obs import SpanEvent  # noqa: E402

LAYOUT = spec.Layout()


def span(name, ts, dur, tid=1, ph="X", **attrs):
    return SpanEvent(name=name, cat="", ph=ph, ts=ts, dur=dur, tid=tid, attrs=attrs)


def make_run(tmp_path, spans, *, stretch=None, window_start=None):
    run = harness.Run(
        cell=None, layout=LAYOUT, seed=0, seconds=1.0, trace=True, workdir=tmp_path,
        peaks={}, t_process=0.0, profiler=harness.Profiler(True, tmp_path),
        window_start=window_start, spans=list(spans),
    )
    if stretch is not None:
        run.profiler.mono_t0, run.profiler.mono_t1 = stretch
    return run


def read(name, run):
    return LAYOUT.module("metrics", name).read(run)


def test_batcher_wait_is_the_mean_wait_of_requests_ending_in_the_stretch(tmp_path):
    spans = [
        span("serve.request", 9.0, 1.5, rid=0, block=3, queued_s=0.020),  # ends 10.5
        span("serve.request", 10.0, 2.0, rid=1, block=4, queued_s=0.040),  # ends 12.0
        span("serve.request", 12.5, 1.0, rid=2, block=5, queued_s=0.900),  # ends after
        span("serve.request", 8.0, 1.0, rid=3, block=2, queued_s=0.900),  # ends before
        span("serve.dispatch", 10.5, 0.2, block=4),
    ]
    run = make_run(tmp_path, spans, stretch=(10.0, 13.0))
    assert read("batcher_wait_ms", run) == pytest.approx(30.0)  # (20 + 40) / 2


def test_batcher_wait_reads_nothing_without_its_attribute_or_a_stretch(tmp_path):
    before = [span("serve.request", 10.0, 1.0, rid=0, kind="lexical")]
    assert read("batcher_wait_ms", make_run(tmp_path, before, stretch=(9.0, 12.0))) is None
    waited = [span("serve.request", 10.0, 1.0, rid=0, queued_s=0.01)]
    assert read("batcher_wait_ms", make_run(tmp_path, waited)) is None


def test_fold_time_pairs_each_dispatch_with_its_segments_next_fetch(tmp_path):
    spans = [
        # experiment 1: shard 0, two segments
        span("segment.fold", 1.00, 0.01, shard=0, segment=0),
        span("segment.fold", 1.10, 0.01, shard=0, segment=1),
        span("ckpt.fetch", 1.05, 0.25, tid=2, shard=0, step=1, queued=2),  # ends 1.30
        span("ckpt.fetch", 1.35, 0.15, tid=2, shard=0, step=2, queued=2),  # ends 1.50
        span("ckpt.fetch", 1.00, 0.20, tid=3, shard=1, step=1, queued=0),  # another shard
        # experiment 2 reuses (shard 0, segment 0/1); segment 1's fetch is missing,
        # and the one of experiment 1 ended before the fold: no pair
        span("segment.fold", 5.00, 0.01, shard=0, segment=0),
        span("segment.fold", 5.05, 0.01, shard=0, segment=1),
        span("ckpt.fetch", 5.10, 0.10, tid=2, shard=0, step=1, queued=5),  # ends 5.20
    ]
    # (1.30 - 1.00) + (1.50 - 1.10) + (5.20 - 5.00) = 0.9 s over 3 segments
    assert read("fold_ms", make_run(tmp_path, spans)) == pytest.approx(300.0)


def test_fold_time_reads_nothing_without_fetch_spans(tmp_path):
    spans = [
        span("segment.fold", 1.0, 0.01, shard=0, segment=0),
        span("ckpt.save", 1.05, 0.3, tid=2, step=1),
    ]
    assert read("fold_ms", make_run(tmp_path, spans)) is None


@pytest.mark.parametrize("name", ["window_compiles.batch", "window_compiles.serve"])
def test_window_compiles_counts_executables_after_the_window_opens(tmp_path, name):
    spans = [
        span("jit.watch", 99.0, 0.0, ph="i"),
        span("jit.compile", 99.5, 0.2),  # warm-up, before the window
        span("jit.trace", 100.9, 0.05),
        span("jit.compile", 101.0, 0.5, tid=1),
        span("jit.cache_load", 101.1, 0.2, tid=1),  # inside that compile: the same executable
        span("jit.cache_load", 102.0, 0.1, tid=2),  # on its own: one more
        span("jit.lower", 103.0, 0.1),
    ]
    assert read(name, make_run(tmp_path, spans, window_start=100.0)) == 2.0
    quiet = [span("jit.watch", 99.0, 0.0, ph="i"), span("jit.compile", 99.5, 0.2)]
    assert read(name, make_run(tmp_path, quiet, window_start=100.0)) == 0.0


@pytest.mark.parametrize("name", ["window_compiles.batch", "window_compiles.serve"])
def test_window_compiles_reads_nothing_where_compiles_were_not_watched(tmp_path, name):
    spans = [span("experiment.scan", 101.0, 1.0), span("serve.dispatch", 101.0, 0.1)]
    assert read(name, make_run(tmp_path, spans, window_start=100.0)) is None
    assert read(name, make_run(tmp_path, [], window_start=100.0)) is None
