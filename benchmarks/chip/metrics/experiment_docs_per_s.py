"""Documents of all experiments completed in the window, over the time from
the window's start to the end of the last one."""


def read(run):
    times = run.records.get("experiments")
    if not times:
        return None
    docs = len(times) * run.records["docs_per_experiment"]
    return docs / (times[-1][1] - run.window_start)
