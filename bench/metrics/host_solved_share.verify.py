"""Share of the pairs the engine answered that went to the exact host
solver, in %, from the engine counters ``host_solved`` and ``pairs``."""


def read(run):
    pairs = run.delta("pairs")
    return 100.0 * run.delta("host_solved") / pairs if pairs > 0 else None
