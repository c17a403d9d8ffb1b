"""Executables built or loaded inside a serve window, after warm-up; read as
``window_compiles.batch`` reads an experiment window."""


def read(run):
    return run.layout.module("metrics", "window_compiles.batch").read(run)
