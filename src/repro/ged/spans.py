"""Named host spans on the verify path, on the profiler's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: while a profiler
trace runs (``jax.profiler.start_trace`` with ``host_tracer_level`` of 1 or
more) it lands in the same ``.xplane.pb`` as the device's events, on the
host thread that opened it, so that the time between device programs can
be split by what the host was doing.  With no trace running a span costs
well under a microsecond.  A span's parent is the span that encloses it on
the same thread; everything on the verify path runs on one thread, and the
request ID is ``call`` on ``ged.service.verify``.

Metadata are small ints, recorded as the event's stats::

    with span("ged.exec.dispatch", rung=0, slots=32, batch=256, real=147):
        ...

:data:`SPANS` lists every name the program opens, with its meaning.
"""

from __future__ import annotations

from typing import Optional

from jax.profiler import TraceAnnotation

SPANS = (
    ("ged.service.verify",
     "GedVerificationService.verify: admission and routing of one call"),
    ("ged.engine.run",
     "GedEngine._run: cache keys, result-cache lookups and puts, planning"),
    ("ged.plan", "build_plan: graph ingestion, label vocabulary, bucketing"),
    ("ged.pack", "pack_bucket: tensor packing and matching order"),
    ("ged.auto.run",
     "AutoBackend.run: difficulty, LPT packing, the rung scheduler loop"),
    ("ged.auto.rebucket",
     "AutoBackend.run: a scheduler batch regrouped into slot buckets"),
    ("ged.exec.dispatch",
     "Executor.run_packed_async: kernel plan, host-to-device transfer, "
     "the executable's call"),
    ("ged.exec.compile",
     "Executor._compile: an engine program lowered and compiled at dispatch"),
    ("ged.auto.wait", "AutoBackend.run: the host blocked on a batch's result"),
    ("ged.auto.unpack",
     "AutoBackend.run: a landed batch turned into outcomes and survivors"),
    ("ged.auto.host_solve",
     "AutoBackend.run: one pair solved exactly on the host"),
)


def span(name: str, **meta: Optional[int]) -> TraceAnnotation:
    """The context manager of the host span ``name`` (one of
    :data:`SPANS`), with ``meta`` as its stats; a value of None is left
    out.

    >>> with span("ged.exec.dispatch", rung=None, slots=8, batch=4):
    ...     pass
    """
    return TraceAnnotation(
        name, **{k: v for k, v in meta.items() if v is not None})
