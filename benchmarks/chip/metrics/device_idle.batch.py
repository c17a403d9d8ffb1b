"""Share of the traced window (one whole experiment) in which no operation
ran on the device."""

from chipbench import readers


def read(run):
    return readers.idle_percent(run)
