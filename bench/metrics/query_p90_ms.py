"""90th percentile of the latency over every query of the window,
in ms (``statistics.quantiles``, inclusive method)."""

import statistics


def read(run):
    lat = run.latencies_s
    if len(lat) < 2:
        return None
    return 1e3 * statistics.quantiles(lat, n=10, method="inclusive")[8]
