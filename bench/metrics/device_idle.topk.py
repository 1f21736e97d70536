"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device-op intervals) / window, from the trace."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
