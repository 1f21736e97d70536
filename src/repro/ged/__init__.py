"""``repro.ged`` — the public GED API.

One facade (:class:`GedEngine` / :func:`compute` / :func:`verify`) over
pluggable policy backends (``exact`` host solver, ``jax`` vmap engine,
``pallas`` kernel engine, ``sharded`` mesh-parallel engine, ``auto``
escalation pipeline), with bucketed planning for mixed-size workloads and
a single :class:`GedOutcome` result schema.

Corpus-scale similarity search goes through the same door:
:class:`GraphStore` ingests a graph database once (shared label vocab,
resident stage-0 feature arrays, canonical-digest dedup, a sublinear
:class:`CandidateIndex` — banded WL-sketch LSH plus distance-reuse pivot
pruning) and answers ``range_search`` / ``top_k`` / ``search_batch``
queries via a staged filter-verify pipeline, returning ranked
:class:`SearchHit` results.

Policies ride on the executor layer (:mod:`repro.ged.exec`): an
:class:`Executor` owns device placement, compile caching, packing and
unpacking; :class:`ShardedExecutor` ``shard_map``-s the search over the
device mesh; :class:`PendingBatch` is the async-dispatch future the
overlapped ``auto`` escalation scheduler rides; and an engine-level
:class:`ResultCache` answers duplicate pairs without re-execution (keyed
on exact or Weisfeiler-Leman canonical digests — see :func:`wl_digest`).

Robustness primitives live in :mod:`repro.ged.faults`: the anytime
:class:`Deadline` contract (``GedEngine(deadline_s=...)`` — every pair
answers with admissible best-so-far bounds when the budget expires), the
:class:`RetryPolicy`/degradation ladder under faults, and the
deterministic :class:`FaultInjector` chaos hook — see
``docs/robustness.md``.

The layers underneath (``repro.core.exact``, ``repro.core.engine``,
``repro.serving``) remain importable, but new code — and all future
sharding/caching/async work — should come through this door.

>>> from repro import ged
>>> [o.ged for o in ged.compute([(([0], []), ([1], []))],
...                             backend="exact")]
[1.0]
"""

from repro.ged.api import GedEngine, compute, verify
from repro.ged.backends import (available_backends, make_backend,
                                register_backend)
from repro.ged.exec import (Executor, PendingBatch, ResultCache,
                            ShardedExecutor, SketchSpec, batch_signatures,
                            graph_digest, wl_digest, wl_signature)
from repro.ged.faults import (CompileRefusal, Deadline, FaultInjector,
                              InjectedFault, Overloaded, RetryPolicy)
from repro.ged.index import CandidateIndex, sketch_damage
from repro.ged.plan import as_graph, build_plan, slot_bucket
from repro.ged.results import GedOutcome, SearchHit
from repro.ged.store import GraphStore

__all__ = [
    "GedEngine",
    "GedOutcome",
    "GraphStore",
    "CandidateIndex",
    "SearchHit",
    "SketchSpec",
    "sketch_damage",
    "wl_signature",
    "batch_signatures",
    "compute",
    "verify",
    "register_backend",
    "available_backends",
    "make_backend",
    "as_graph",
    "build_plan",
    "slot_bucket",
    "Executor",
    "ShardedExecutor",
    "PendingBatch",
    "ResultCache",
    "graph_digest",
    "wl_digest",
    "Deadline",
    "RetryPolicy",
    "FaultInjector",
    "InjectedFault",
    "CompileRefusal",
    "Overloaded",
]
