"""Pluggable *policy* backends behind the ``repro.ged`` facade.

Every backend implements one protocol — ``run(plan, taus, verification,
cfg) -> List[GedOutcome]`` — over the bucketed :class:`repro.ged.plan.Plan`.
Backends decide *what* runs (which rungs, which bounds, when to escalate);
*how* a bucket reaches a device — placement, jit/compile caching, packing,
unpacking — is the executor layer's job (:mod:`repro.ged.exec`), so a
policy composes with any placement:

* ``"exact"``   — the paper-faithful host solver (AStar+/DFS+ with BMa),
  one pair at a time.  Always certified; produces mappings.
* ``"jax"``     — the batched vmap engine, one jit call per shape bucket,
  compile-cache aware.  Pure-jnp bound math (``use_kernel=False``).
* ``"pallas"``  — same engine with the Pallas kernels enabled on the hot
  path (interpret mode on CPU, real kernels on TPU).
* ``"sharded"`` — same policy as ``"jax"`` on a
  :class:`~repro.ged.exec.ShardedExecutor`: the pair batch ``shard_map``-ed
  over the device mesh, buckets padded to shard multiples.
* ``"auto"``    — the production pipeline: difficulty prediction, LPT
  batch packing, escalation through growing engine rungs, host-solver
  final rung.  Every answer it returns is certified.  Rungs execute
  *overlapped* by default (async dispatch; while rung *k* is in flight,
  decided pairs drain into results, survivors re-bucket for rung *k+1*,
  and host-solver pairs run behind the device work), and the policy rides
  any executor — ``GedEngine(backend="auto", mesh=...)`` runs every rung
  ``shard_map``-ed over the mesh.

New backends (remote, multi-host, ...) register with
:func:`register_backend` and become constructible via
``GedEngine(backend="name")`` with no facade changes.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Protocol

import numpy as np

from repro.core.engine.search import EngineConfig
from repro.core.exact.search import ged as exact_ged
from repro.core.exact.search import ged_verify
from repro.ged.exec import (Executor, PendingBatch, ShardedExecutor,
                            engine_outcome)
from repro.ged.plan import Bucket, Plan
from repro.ged.results import GedOutcome
from repro.ged.spans import span
from repro.runtime.scheduler import Batch, GedScheduler, difficulty


class Backend(Protocol):
    """What the facade requires of an execution-policy backend.

    A minimal conforming implementation (see
    :func:`register_backend` to plug one in)::

        class EchoBackend:
            name = "echo"
            kernel_default = None

            def run(self, plan, taus, verification, cfg):
                return [some_outcome(q, g) for q, g in plan.pairs]
    """

    name: str
    # What ``EngineConfig.use_kernel`` must be for this backend; ``None``
    # means the backend honors whatever the config says.  ``GedEngine``
    # applies the default and rejects contradicting user settings.
    kernel_default: Optional[bool]

    def run(self, plan: Plan, taus: np.ndarray, verification: bool,
            cfg: EngineConfig) -> List[GedOutcome]:
        """Answer every pair in ``plan`` (in order).  ``taus`` is aligned
        with ``plan.pairs`` (zeros in computation mode).

        A backend may additionally accept ``ctx`` (keyword,
        :class:`repro.ged.faults.RunContext`) to honor deadlines and the
        fault-injection/retry machinery; the facade only passes it when
        the signature names it, so third-party backends registered before
        the robustness layer keep working unchanged.
        """
        ...


# ----------------------------------------------------------- host solver

class ExactBackend:
    """Paper-faithful host solver: always certified, yields mappings.

    >>> import numpy as np
    >>> from repro.core.engine.search import EngineConfig
    >>> from repro.ged.plan import build_plan
    >>> plan = build_plan([(([0], []), ([1], []))])   # 1-vertex relabel
    >>> out, = ExactBackend().run(plan, np.zeros(1, np.float32), False,
    ...                           EngineConfig())
    >>> out.ged, out.certified
    (1.0, True)
    """

    name = "exact"
    kernel_default = None  # host solver: kernels irrelevant
    batch_multiple = 1     # host solver: no device batch shape to satisfy

    def run(self, plan: Plan, taus: np.ndarray, verification: bool,
            cfg: EngineConfig, ctx=None) -> List[GedOutcome]:
        from repro.ged import faults as _faults
        outcomes: List[GedOutcome] = []
        for i, (q, g) in enumerate(plan.pairs):
            tau = float(taus[i]) if verification else None
            if ctx is not None and ctx.expired():
                # budget already spent: cheap admissible floor, no search
                ctx.bump("timed_out_pairs")
                outcomes.append(_faults.fallback_outcome(
                    q, g, verification, tau, self.name,
                    stats={"rung": 0}))
                continue
            outcomes.append(_robust_host_solve(
                q, g, tau, verification, cfg, self.name, 0, ctx))
        return outcomes


def _host_compute_outcome(res, backend: str, wall_s: float,
                          rung: int = 0) -> GedOutcome:
    ged = float(res.ged)
    return GedOutcome(ged=ged, similar=None, certified=True,
                      lower_bound=ged, upper_bound=ged,
                      mapping=res.best_mapping, backend=backend,
                      wall_s=wall_s, stats={"rung": rung,
                                            "expanded": res.stats.expanded})


def _host_verify_outcome(res, tau: float, backend: str, wall_s: float,
                         rung: int = 0) -> GedOutcome:
    similar = bool(res.similar)
    return GedOutcome(
        ged=None, similar=similar, certified=True,
        lower_bound=0.0 if similar else float(np.nextafter(tau, np.inf)),
        upper_bound=float(res.upper_bound) if similar else float("inf"),
        mapping=res.best_mapping if similar else None,
        backend=backend, wall_s=wall_s, tau=tau,
        stats={"rung": rung, "expanded": res.stats.expanded})


def _robust_host_solve(q, g, tau: Optional[float], verification: bool,
                       cfg: EngineConfig, backend: str, rung: int,
                       ctx=None) -> GedOutcome:
    """One host-solver pair under the robustness context.

    ``ctx=None`` is exactly the legacy certified path.  With a context:
    the pair runs under :meth:`RunContext.pair_deadline` (cooperative
    check inside the search loop); a timed-out search becomes a sound
    uncertified best-so-far outcome; the ``host`` fault-injection site
    simulates a solver failure, which — since the host solver is the
    ladder's last step — degrades to the cheap admissible floor.
    """
    from repro.ged import faults as _faults

    t0 = time.perf_counter()
    inj = _faults.get_injector(ctx)
    if inj is not None:
        try:
            inj.check("host", rung)
        except Exception:
            if ctx is not None:
                ctx.bump("fault_host")
            _faults.warn_once(
                "host-fault",
                "host solver failed (injected or real); answering from "
                "the cheap admissible floor, uncertified")
            out = _faults.fallback_outcome(
                q, g, verification, tau, backend, timed_out=False,
                stats={"rung": rung, "degraded": True})
            out.wall_s = time.perf_counter() - t0
            return out
    deadline = None
    if ctx is not None and (ctx.has_deadline
                            or ctx.per_pair_deadline_s is not None):
        deadline = ctx.pair_deadline()
    if verification:
        res = ged_verify(q, g, float(tau), bound="BMa",
                         strategy=cfg.strategy, deadline=deadline)
    else:
        res = exact_ged(q, g, bound="BMa", strategy=cfg.strategy,
                        deadline=deadline)
    wall = time.perf_counter() - t0
    if getattr(res, "timed_out", False):
        if ctx is not None:
            ctx.bump("timed_out_pairs")
        out = _faults.fallback_outcome(
            q, g, verification, tau, backend,
            lower_bound=res.lower_bound, upper_bound=res.upper_bound,
            stats={"rung": rung, "expanded": res.stats.expanded})
        out.wall_s = wall
        return out
    if verification:
        return _host_verify_outcome(res, float(tau), backend, wall,
                                    rung=rung)
    return _host_compute_outcome(res, backend, wall, rung=rung)


# --------------------------------------------------------- batched engine

class EngineBackend:
    """Bucket-at-a-time policy over an :class:`~repro.ged.exec.Executor`.

    ``cfg.use_kernel`` is taken as-is — ``GedEngine`` defaults it per
    backend name (``jax``/``sharded`` -> False, ``pallas`` -> True) and
    rejects contradictions, so the flag always matches what the user asked
    for.

    Example (normally reached through the facade)::

        eng = ged.GedEngine("jax", pool=512)
        outs = eng.compute(pairs)       # one jit call per shape bucket
    """

    name = "jax"
    kernel_default = False

    def __init__(self, executor: Optional[Executor] = None) -> None:
        self.executor = executor or Executor()

    @property
    def cache(self):
        return self.executor.cache

    @property
    def batch_multiple(self) -> int:
        return self.executor.batch_multiple

    def run(self, plan: Plan, taus: np.ndarray, verification: bool,
            cfg: EngineConfig, ctx=None) -> List[GedOutcome]:
        from repro.ged import faults as _faults
        results: List[Optional[GedOutcome]] = [None] * len(plan.pairs)
        for bucket in plan.buckets:
            t0 = time.perf_counter()
            if ctx is not None and ctx.expired():
                # deadline gone: remaining buckets answer from the cheap
                # admissible floor (bucket granularity — one dispatch is
                # the engine's unit of work)
                for gi in bucket.indices:
                    ctx.bump("timed_out_pairs")
                    q, g = plan.pairs[gi]
                    results[gi] = _faults.fallback_outcome(
                        q, g, verification,
                        float(taus[gi]) if verification else None,
                        self.name, stats={"rung": 0})
                continue
            try:
                pending = self.executor.run_bucket_async(
                    bucket, taus, cfg, verification, ctx=ctx, rung=0)
                out = pending.result()
            except _faults.CompileRefusal:
                raise
            except Exception as exc:
                # the engine rung is permanently gone for this bucket
                # (kernel AND unfused dispatch failed): the degradation
                # ladder's last step is the certified host solver
                _faults.warn_once(
                    f"degrade-host-{self.name}",
                    f"{self.name} backend: engine bucket failed "
                    f"({exc!r}); degrading its pairs to the host solver")
                for gi in bucket.indices:
                    if ctx is not None:
                        ctx.bump("degraded_host")
                    q, g = plan.pairs[gi]
                    o = _robust_host_solve(
                        q, g, float(taus[gi]) if verification else None,
                        verification, cfg, self.name, 0, ctx)
                    o.stats["degraded"] = True
                    results[gi] = o
                continue
            wall = time.perf_counter() - t0
            for bi, gi in enumerate(bucket.indices):
                o = engine_outcome(
                    out, bucket.packed, bi, verification,
                    float(taus[gi]) if verification else None,
                    self.name, wall, rung=0)
                if pending.flags:
                    o.stats.update(pending.flags)
                results[gi] = o
        return results  # type: ignore[return-value]


class PallasBackend(EngineBackend):
    """Engine policy with Pallas kernels on the hot path.

    Interpret mode on CPU, real kernels on TPU — same policy, same
    outcomes as ``"jax"``::

        outs = ged.GedEngine("pallas").compute(pairs)
    """

    name = "pallas"
    kernel_default = True


class ShardedBackend(EngineBackend):
    """Engine policy on a mesh-sharded executor (``shard_map`` over pairs).

    Identical policy (and therefore identical outcomes) to ``"jax"``; only
    the placement differs.  ``mesh`` defaults to a 1-D mesh over every
    local device.  Example::

        mesh = jax.make_mesh((8,), ("data",))
        outs = ged.GedEngine("sharded", mesh=mesh).verify(pairs, 4.0)
    """

    name = "sharded"
    kernel_default = False

    def __init__(self, mesh=None) -> None:
        super().__init__(ShardedExecutor(mesh))


# ------------------------------------------------------------ escalation

@dataclasses.dataclass
class _InFlight:
    """One dispatched rung bucket awaiting its device results."""
    bucket: Bucket
    rung: int
    pending: PendingBatch
    t_dispatch: float


class AutoBackend:
    """Difficulty-scheduled escalation: engine rungs, then the host solver.

    This is the serving pipeline (previously private to
    ``GedVerificationService``): predict per-pair difficulty, LPT-pack
    equalised batches, run the batched engine, and re-queue uncertified
    pairs through bigger-pool rungs down to the exact host solver — so
    every answer is certified.

    Rung execution is *overlapped* by default: batches are dispatched
    asynchronously (JAX async dispatch, up to ``max_in_flight`` at once),
    and while rung *k* is still crunching on the device the scheduler
    drains rung *k-1*'s finished batches — decided pairs become outcomes,
    survivors are re-bucketed (:meth:`repro.ged.plan.Plan.subset_buckets`)
    and queued for rung *k+1* — and chews final-rung host-solver pairs,
    which run on the Python side and therefore hide entirely behind
    in-flight device work.  ``overlap=False`` restores the strictly
    sequential rung loop (the benchmark baseline).

    The policy composes with any executor: the default is a local
    single-device :class:`~repro.ged.exec.Executor`; pass ``mesh=`` (or an
    explicit ``executor=``) to run every rung's batches ``shard_map``-ed
    over the device mesh via :class:`~repro.ged.exec.ShardedExecutor` —
    that is what ``GedEngine(backend="auto", mesh=...)`` constructs.
    Outcomes are identical whatever the placement or overlap setting; only
    the wall-clock changes.

    Example::

        eng = ged.GedEngine("auto", mesh=jax.make_mesh((8,), ("data",)),
                            max_in_flight=4)
        outs = eng.verify(pairs, tau=4.0)       # certified, mesh-sharded
        eng.stats["dispatches"]                 # rung batches dispatched

    Each step of the loop opens a host span (``ged.auto.*``,
    :mod:`repro.ged.spans`), so a profiler trace splits the host time
    between device programs into packing, waiting and unpacking.
    """

    name = "auto"
    kernel_default = None  # honors cfg.use_kernel on the engine rungs

    def __init__(self, batch_size: int = 256,
                 executor: Optional[Executor] = None,
                 mesh=None, overlap: bool = True, max_in_flight: int = 4):
        if executor is None:
            executor = ShardedExecutor(mesh) if mesh is not None \
                else Executor()
        self.scheduler = GedScheduler(batch_size)
        self.executor = executor
        self.overlap = bool(overlap)
        self.max_in_flight = max(1, int(max_in_flight))
        self.stats: Dict[str, float] = {"pairs": 0, "escalated": 0,
                                        "host_solved": 0, "batches": 0,
                                        "dispatches": 0}

    @property
    def cache(self):
        return self.executor.cache

    @property
    def batch_multiple(self) -> int:
        return self.executor.batch_multiple

    def run(self, plan: Plan, taus: np.ndarray, verification: bool,
            cfg: EngineConfig, ctx=None) -> List[GedOutcome]:
        with span("ged.auto.run"):
            return self._schedule(plan, taus, verification, cfg, ctx)

    def _schedule(self, plan: Plan, taus: np.ndarray, verification: bool,
                  cfg: EngineConfig, ctx) -> List[GedOutcome]:
        from repro.ged import faults as _faults
        results: List[Optional[GedOutcome]] = [None] * len(plan.pairs)
        diffs = [difficulty(q.n, g.n, q.m, g.m, q.vlabels, g.vlabels,
                            tau=float(taus[i]) if verification else None)
                 for i, (q, g) in enumerate(plan.pairs)]
        queue = self.scheduler.pack(diffs, rung=0)
        self.stats["pairs"] += len(plan.pairs)
        host_queue: List[int] = []          # pairs awaiting the final rung
        dispatchable: "collections.deque" = collections.deque()  # (bucket, rung)
        inflight: "collections.deque[_InFlight]" = collections.deque()
        has_deadline = ctx is not None and ctx.has_deadline
        # Best-so-far admissible bounds per surviving pair, merged across
        # rungs (anytime contract) — maintained only under a deadline so
        # the no-deadline path does zero extra work.
        best: Dict[int, tuple] = {}
        degraded: set = set()               # pairs routed around a fault

        def merge_best(gi: int, lb: float, ub: float) -> None:
            plb, pub = best.get(gi, (0.0, float("inf")))
            best[gi] = (max(plb, lb), min(pub, ub))

        def solve_host(gi: int) -> None:
            # final rung: exact host solver (paper-faithful AStar+-BMa)
            q, g = plan.pairs[gi]
            self.stats["host_solved"] += 1
            with span("ged.auto.host_solve"):
                o = _robust_host_solve(
                    q, g, float(taus[gi]) if verification else None,
                    verification, cfg, f"{self.name}/exact", -1, ctx)
            if gi in degraded:
                o.stats["degraded"] = True
            if not o.certified and gi in best:
                # fold the engine rungs' best-so-far bounds into an
                # uncertified answer (both sides admissible -> still sound)
                lb, ub = best[gi]
                o.lower_bound = max(o.lower_bound, lb)
                o.upper_bound = min(o.upper_bound, ub)
                o.lower_bound = min(o.lower_bound, o.upper_bound)
                if verification and o.similar is None:
                    if o.lower_bound > float(taus[gi]):
                        o.similar = False
                    elif o.upper_bound <= float(taus[gi]):
                        o.similar = True
            results[gi] = o

        def degrade_bucket(bucket: Bucket, exc: Exception) -> None:
            # the engine rung is gone for these pairs (kernel AND unfused
            # dispatch failed): route them to the ladder's last step, the
            # host solver, instead of failing the whole run
            fresh = [gi for gi in bucket.indices if results[gi] is None]
            degraded.update(fresh)
            host_queue.extend(fresh)
            self.stats["degraded_host"] = \
                self.stats.get("degraded_host", 0) + len(fresh)
            if ctx is not None:
                ctx.bump("degraded_host", len(fresh))
            _faults.warn_once(
                "degrade-host-auto",
                f"auto backend: engine rung failed ({exc!r}); routing "
                f"{len(fresh)} pairs to the host solver")

        def refill() -> None:
            # turn scheduler batches into dispatchable rung buckets:
            # shard-aware re-bucketing groups each batch by slot bucket
            # and pads to the executor's shard multiple, so the
            # max_in_flight cap applies to what actually hits the device
            while not dispatchable and queue:
                batch = queue.pop(0)
                self.stats["batches"] += 1
                if self.scheduler.engine_params(batch.rung) is None:
                    host_queue.extend(batch.indices)
                    continue
                with span("ged.auto.rebucket", rung=batch.rung):
                    buckets = plan.subset_buckets(batch.indices,
                                                  self.executor.pack)
                dispatchable.extend((b, batch.rung) for b in buckets)

        def dispatch(bucket: Bucket, rung: int) -> None:
            pool, expand, max_iters = self.scheduler.engine_params(rung)
            rcfg = dataclasses.replace(cfg, pool=pool, expand=expand,
                                       max_iters=max_iters)
            self.stats["dispatches"] += 1
            try:
                pending = self.executor.run_packed_async(
                    bucket.packed, bucket.pad_values(taus), rcfg,
                    verification, real=bucket.real, ctx=ctx, rung=rung)
            except _faults.CompileRefusal:
                raise
            except Exception as exc:
                degrade_bucket(bucket, exc)
                return
            item = _InFlight(bucket, rung, pending, time.perf_counter())
            if self.overlap:
                inflight.append(item)
            else:
                drain(item)             # sequential baseline: block now

        def drain(item: _InFlight) -> None:
            # Raises only a compile refusal (of the unfused re-dispatch): a
            # batch that fails at materialisation is degraded to the host
            # solver, so callers (including the cleanup path below) can
            # always drain in-flight work.
            try:
                with span("ged.auto.wait", rung=item.rung):
                    out = item.pending.result()  # blocks until it lands
            except _faults.CompileRefusal:
                raise
            except Exception as exc:
                degrade_bucket(item.bucket, exc)
                return
            with span("ged.auto.unpack", rung=item.rung,
                      rows=len(item.bucket.indices)):
                unpack(item, out)

        def unpack(item: _InFlight, out: dict) -> None:
            # per-batch wall, not cumulative-since-run-start: a pair's
            # reported wall_s is the cost of the batch that answered it.
            wall = time.perf_counter() - item.t_dispatch
            survivors = []
            for bi, gi in enumerate(item.bucket.indices):
                if bool(out["exact"][bi]):
                    o = engine_outcome(
                        out, item.bucket.packed, bi, verification,
                        float(taus[gi]) if verification else None,
                        self.name, wall, rung=item.rung)
                    if item.pending.flags:
                        o.stats.update(item.pending.flags)
                    results[gi] = o
                else:
                    survivors.append(bi)
                    if has_deadline:
                        # pool floor is admissible; the compute-mode raw
                        # ged is the engine's incumbent full mapping
                        merge_best(
                            gi, float(out["lower_bound"][bi]),
                            float("inf") if verification
                            else float(out["ged"][bi]))
            skey = f"survivors_rung_{item.rung}"
            self.stats[skey] = self.stats.get(skey, 0) + len(survivors)
            if survivors:
                self.stats["escalated"] += len(survivors)
                nxt = self.scheduler.escalate(
                    Batch(list(item.bucket.indices), 0.0, item.rung),
                    survivors)
                if nxt is not None:
                    queue.append(nxt)

        expired = False
        try:
            while queue or dispatchable or inflight or host_queue:
                if ctx is not None and ctx.expired():
                    expired = True
                    break
                refill()
                # keep the device fed: dispatch while there's work & room
                while dispatchable and len(inflight) < self.max_in_flight:
                    dispatch(*dispatchable.popleft())
                    refill()
                if inflight:
                    # overlap: host-solve while oldest batch is in flight
                    while host_queue and not inflight[0].pending.ready():
                        if ctx is not None and ctx.expired():
                            break
                        solve_host(host_queue.pop(0))
                    drain(inflight.popleft())
                elif host_queue:
                    solve_host(host_queue.pop(0))
        finally:
            # Never strand dispatched device work or lose its survivor
            # bounds — on deadline expiry or a mid-flight error, drain
            # everything still in flight (drain() raises only a compile
            # refusal, which fails the run anyway).
            while inflight:
                drain(inflight.popleft())
        if expired or any(r is None for r in results):
            # Anytime tail: every pair the budget never reached answers
            # with its best-so-far admissible bounds, uncertified.
            for gi, r in enumerate(results):
                if r is not None:
                    continue
                q, g = plan.pairs[gi]
                lb, ub = best.get(gi, (0.0, float("inf")))
                o = _faults.fallback_outcome(
                    q, g, verification,
                    float(taus[gi]) if verification else None,
                    self.name, lower_bound=lb, upper_bound=ub)
                if gi in degraded:
                    o.stats["degraded"] = True
                results[gi] = o
                self.stats["timed_out_pairs"] = \
                    self.stats.get("timed_out_pairs", 0) + 1
                if ctx is not None:
                    ctx.bump("timed_out_pairs")
        return results  # type: ignore[return-value]


# -------------------------------------------------------------- registry

_REGISTRY: Dict[str, Callable[..., Backend]] = {}


def register_backend(name: str, factory: Callable[..., Backend]) -> None:
    """Make ``GedEngine(backend=name)`` constructible.

    ``factory`` is called with keyword options the backend understands
    (unknown ones are not passed — see :func:`make_backend`).

    >>> class NullBackend:
    ...     name = "null"
    ...     kernel_default = None
    ...     def run(self, plan, taus, verification, cfg): return []
    >>> register_backend("null", NullBackend)
    >>> "null" in available_backends()
    True
    >>> del _REGISTRY["null"]                  # tidy up the example
    """
    _REGISTRY[name] = factory


def available_backends() -> tuple:
    """Sorted names ``GedEngine(backend=...)`` accepts right now.

    >>> available_backends()
    ('auto', 'exact', 'jax', 'pallas', 'sharded')
    """
    return tuple(sorted(_REGISTRY))


def make_backend(name: str, **options) -> Backend:
    """Construct a registered backend, dropping options it doesn't take.

    This is what lets ``GedEngine`` pass every knob (``batch_size``,
    ``mesh``, ``overlap``, ...) to every backend: factories only receive
    the keywords their signature names (unless they take ``**kwargs``).

    >>> make_backend("exact").name
    'exact'
    >>> make_backend("exact", batch_size=64).name   # ignored, not an error
    'exact'
    """
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None
    import inspect
    params = inspect.signature(factory).parameters
    if not any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in params.values()):
        options = {k: v for k, v in options.items() if k in params}
    return factory(**options)


register_backend("exact", ExactBackend)
register_backend("jax", EngineBackend)
register_backend("pallas", PallasBackend)
register_backend("sharded", ShardedBackend)
register_backend("auto", AutoBackend)
