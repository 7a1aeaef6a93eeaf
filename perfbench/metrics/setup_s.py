"""Seconds from the process's start to the window's: imports, data,
base materialisation, warm-up and every compile."""


def read(run):
    return run.window_start - run.t0
