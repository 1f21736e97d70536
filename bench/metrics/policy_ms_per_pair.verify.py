"""Host time of the service, the engine facade and the ``auto`` policy
outside packing, dispatch and waiting (self time of the program's spans
below in the traced window), in ms per certified answer of the window."""

from bench import spans

NAMES = ("ged.service.verify", "ged.engine.run", "ged.plan", "ged.auto.run",
         "ged.auto.rebucket", "ged.auto.unpack", "ged.auto.host_solve")


def read(run):
    s = spans.self_s(run, NAMES)
    return 1e3 * s / run.answered if s is not None and run.answered else None
