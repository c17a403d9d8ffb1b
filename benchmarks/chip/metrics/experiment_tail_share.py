"""Share of the experiments' wall time spent after the scan: the program's
``experiment.run_files`` and ``experiment.eval`` spans over the sum of the
experiments' times (traced run, whole window)."""

from chipbench import readers


def read(run):
    times = run.records.get("experiments")
    if not times or not run.spans:
        return None
    tail = readers.span_seconds(run, "experiment.run_files") + readers.span_seconds(
        run, "experiment.eval"
    )
    return 100.0 * tail / sum(t1 - t0 for t0, t1 in times)
