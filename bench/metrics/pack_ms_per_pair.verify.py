"""Host time spent packing pairs into engine tensors (self time of the
program's ``ged.pack`` spans in the traced window), in ms per certified
answer of the window."""

from bench import spans


def read(run):
    s = spans.self_s(run, ("ged.pack",))
    return 1e3 * s / run.answered if s is not None and run.answered else None
