"""The system under test, behind the few calls the benchmark makes.

This is the only module of the benchmark that imports the program
(``src/repro``).  It builds the service a configuration names, sends it
the generated requests, and hands back plain answers, so that the checks
in ``checks.py`` read nothing of the program's types.

A configuration's ``"system"`` entry names the service and its settings:
``{"service": "similarity", "options": {...}}`` builds
``GedSimilarityService(graphs, **options)``; ``{"service":
"verification", "options": {...}}`` builds
``GedVerificationService(**options)``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

# Environment variables that would steer the program off its chip path:
# the pure-jnp reference kernels, injected faults, a kernel-dispatch table.
REFUSED_ENV = ("REPRO_DISABLE_PALLAS", "REPRO_GED_FAULT_INJECT",
               "REPRO_GED_AUTOTUNE_DIR")
# Counter names (any part) that mean the program fell back or gave up.
FALLBACK_MARKS = ("degraded", "fault_", "retries", "timed_out", "shed")


class NoChip(RuntimeError):
    """JAX found no accelerator that the cell can be measured on."""


def import_program():
    """Put the program on the path; raise ``NoChip`` when it is absent."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise NoChip(f"the program is not in this checkout ({SRC})")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def require_chip(chips: int):
    """The devices, or ``NoChip``: a TPU, Pallas kernels compiled rather
    than interpreted, at least ``chips`` devices, and no variable that
    steers the program off its chip path."""
    refused = [v for v in REFUSED_ENV if os.environ.get(v)]
    if refused:
        raise NoChip(f"{', '.join(refused)} set: it steers the program off "
                     "its chip path")
    import_program()
    import jax

    from repro.kernels.ops import pallas_interpret
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if pallas_interpret():
        raise NoChip("Pallas kernels would run in interpret mode")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """The program's own persistent compile cache: the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names, else a fixed one in the
    checkout."""
    import_program()
    from repro.ged.exec import enable_compile_cache as enable
    return enable()


def _graph(g):
    from repro.core.exact.graph import Graph
    return Graph(g[0], g[1])


def fallbacks(stats: Dict[str, float]) -> Dict[str, float]:
    """The counters that say the program retried, degraded, faulted,
    timed out or shed load, where they are not zero."""
    return {k: v for k, v in stats.items()
            if any(m in k for m in FALLBACK_MARKS) and v
            and isinstance(v, (int, float))}


class _Service:
    """The counters both services report, and their release."""

    service = None

    def stats(self) -> Dict[str, float]:
        """The service's numeric counters and its ``health()`` snapshot."""
        out = {k: v for k, v in self.service.stats.items()
               if isinstance(v, (int, float))}
        out.update({f"health_{k}": v for k, v in self.service.health().items()})
        return out

    def close(self) -> None:
        self.service = None


class Similarity(_Service):
    """``GedSimilarityService`` over a generated corpus."""

    def __init__(self, options: dict, graphs: Sequence):
        import_program()
        from repro.serving.ged_service import GedSimilarityService
        self.service = GedSimilarityService(
            [_graph(g) for g in graphs], **options)

    def range(self, query, tau: float) -> List[Tuple[int, Optional[float],
                                                      bool]]:
        """``(graph_id, distance or None, certified)`` per hit."""
        hits = self.service.range_search(_graph(query), tau)
        return [(h.graph_id, h.ged, bool(h.outcome.certified)) for h in hits]

    def topk(self, query, k: int) -> List[Tuple[int, Optional[float], bool]]:
        hits = self.service.top_k(_graph(query), k)
        return [(h.graph_id, h.ged, bool(h.outcome.certified)) for h in hits]


class Verification(_Service):
    """``GedVerificationService`` with no registered corpus."""

    def __init__(self, options: dict, graphs=None):
        import_program()
        from repro.serving.ged_service import GedVerificationService
        self.service = GedVerificationService(**options)

    def verify(self, pairs: Sequence) -> List[Tuple[Optional[bool], bool]]:
        """``(similar, certified)`` per ``(q, g, tau)`` request."""
        from repro.serving.ged_service import GedRequest
        outs = self.service.verify(
            [GedRequest(_graph(q), _graph(g), tau=tau) for q, g, tau in pairs])
        return [(o.similar, bool(o.certified)) for o in outs]

    def rungs(self) -> int:
        """How many engine rungs the escalation ladder has."""
        return len(self.service.scheduler.rungs)

    def shape(self, vertices: int, pairs: int) -> Tuple[int, int]:
        """The slot bucket of a pair of ``vertices`` vertices, and the batch
        that ``pairs`` such pairs are padded to: the engine program's
        shape, by the program's own planner."""
        from repro.ged.plan import padded_batch, slot_bucket
        slots = self.service.engine.slots
        return (slots or slot_bucket(vertices),
                padded_batch(pairs, self.service.engine.batch_multiple))

    def verify_at_rung(self, rung: int, pairs: Sequence):
        """``verify`` with the ladder cut to its rung ``rung`` alone (pairs
        it leaves open go to the host solver), so that set-up can build
        the programs that only escalated pairs reach."""
        sched = self.service.scheduler
        saved = sched.rungs
        sched.rungs = (saved[rung],)
        try:
            return self.verify(pairs)
        finally:
            sched.rungs = saved


SERVICES = {"similarity": Similarity, "verification": Verification}


def build(config: dict, graphs=None):
    spec = config["system"]
    return SERVICES[spec["service"]](dict(spec.get("options", {})), graphs)
