"""Programs compiled or loaded from the persistent compile cache before
the window, from JAX's monitoring events."""


def read(run):
    return run.setup_programs
