"""Share of the rows that engine programs ran which were batch padding,
in %: 1 - real pairs / padded rows, from the executor counters
``executor_pairs`` and ``executor_rows`` over the window."""


def read(run):
    rows = run.delta("executor_rows")
    if rows <= 0:
        return None
    return 100.0 * (1.0 - run.delta("executor_pairs") / rows)
