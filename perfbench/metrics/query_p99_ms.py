"""99th percentile of lookup latency, over every lookup due in the window,
timed from when it was due."""

from perfbench.loops import percentile


def read(run):
    lat = [a.answered - a.due for a in run.answers if a.answered is not None]
    return percentile(lat, 99) * 1e3 if lat else None
