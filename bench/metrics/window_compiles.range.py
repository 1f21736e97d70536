"""Programs compiled or loaded inside the window (should be 0)."""


def read(run):
    return run.window_compiles
