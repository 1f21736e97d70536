"""Certified answers returned in the window, over the window."""


def read(run):
    return run.answered / run.window_s if run.window_s > 0 else None
