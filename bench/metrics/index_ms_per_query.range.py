"""Host time in the stage -1 candidate index per query, in ms: the
store counters ``index_wall_s`` over ``queries``."""


def read(run):
    q = run.delta("queries")
    return 1e3 * run.delta("index_wall_s") / q if q > 0 else None
