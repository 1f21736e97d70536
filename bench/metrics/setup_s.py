"""Set-up time: from process start to the first timed request
(TPU start-up, corpus generation and ingest, compiles or cache loads,
warm-up)."""


def read(run):
    return run.setup_s
