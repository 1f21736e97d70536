"""Host time of the stage-0 corpus scan per query, in ms: the store
counters ``scan_wall_s`` over ``queries``."""


def read(run):
    q = run.delta("queries")
    return 1e3 * run.delta("scan_wall_s") / q if q > 0 else None
