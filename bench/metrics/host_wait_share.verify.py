"""Share of the traced window in which the host was blocked on the device
for a batch's result (total time of the program's ``ged.auto.wait``
spans), in %."""


def read(run):
    t = run.trace
    row = ((t or {}).get("spans") or {}).get("ged.auto.wait")
    if not row or t["window_s"] <= 0:
        return None
    return 100.0 * row["total_s"] / t["window_s"]
