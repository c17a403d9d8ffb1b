"""Share of the traced window (a few seconds of the steady window) in which no operation
ran on the device."""

from chipbench import readers


def read(run):
    return readers.idle_percent(run)
