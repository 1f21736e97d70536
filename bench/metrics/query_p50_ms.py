"""Median latency over every query of the window, in ms; an open
loop times each query from when it was due."""

import statistics


def read(run):
    if not run.latencies_s:
        return None
    return 1e3 * statistics.median(run.latencies_s)
