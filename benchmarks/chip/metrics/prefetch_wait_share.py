"""Share of the scan jobs' time in which the fold waited for the next staged
segment: the program's ``segment.prefetch_wait`` spans over its
``experiment.scan`` spans (traced run, whole window)."""

from chipbench import readers


def read(run):
    scan = readers.span_seconds(run, "experiment.scan")
    if scan <= 0:
        return None
    return 100.0 * readers.span_seconds(run, "segment.prefetch_wait") / scan
