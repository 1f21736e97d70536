"""Device time of the engine programs (``_run_batch``) in the trace, in
ms per completed query of the traced window."""


def read(run):
    t = run.trace
    if not t or run.answered == 0:
        return None
    s = sum(v for k, v in t["modules"].items() if "_run_batch" in k)
    return 1e3 * s / run.answered if s > 0 else None
