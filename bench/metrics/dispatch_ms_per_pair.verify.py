"""Host time spent dispatching engine programs (self time of the
program's ``ged.exec.dispatch`` and ``ged.exec.compile`` spans in the
traced window), in ms per certified answer of the window."""

from bench import spans


def read(run):
    s = spans.self_s(run, ("ged.exec.dispatch", "ged.exec.compile"))
    return 1e3 * s / run.answered if s is not None and run.answered else None
