"""Completed queries with every hit certified, over the window."""


def read(run):
    return run.answered / run.window_s if run.window_s > 0 else None
