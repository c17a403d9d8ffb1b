"""Set-up: process start to the start of the measured window, with data
generation, the system's build, compilation and warm-up."""


def read(run):
    return run.window_start - run.t_process
