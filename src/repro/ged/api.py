"""The one front door for GED: ``repro.ged.GedEngine``.

    from repro import ged

    outcomes = ged.compute([(q, g), ...])                 # module-level
    engine = ged.GedEngine(backend="jax", pool=512)
    outcomes = engine.verify(pairs, tau=4.0)              # batch
    engine.submit(q, g); engine.submit(q2, g2, tau=3.0)
    outcomes = engine.flush()                             # streaming

Inputs are anything :func:`repro.ged.plan.as_graph` understands (``Graph``
objects, ``(vlabels, edges)`` tuples, adjacency dicts); every entry point
returns :class:`repro.ged.results.GedOutcome` per pair, whichever backend
ran.  Mixed-size workloads are bucketed to power-of-two shapes so the
jitted engine compiles once per bucket, not once per odd batch.

In front of every backend sits an engine-level result cache
(:class:`repro.ged.exec.ResultCache`): queries are keyed on canonical pair
digests (label-vocab-independent; tau-aware for verification), so
duplicate pairs — within one batch or across calls — are answered without
re-planning, re-compiling, or re-executing.  ``GedEngine(cache=False)``
opts out (benchmarks do, to time real work).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.engine.search import EngineConfig
from repro.kernels.autotune import autotune_stats, enable_autotune
from repro.ged.backends import Backend, make_backend
from repro.ged.exec import (DIGESTS, ResultCache, detached,
                            enable_compile_cache, pair_key,
                            pair_key_from_digests, persistent_cache_stats)
from repro.ged.faults import (Deadline, FaultInjector, RetryPolicy,
                              RunContext)
from repro.ged.plan import Vocab, as_graph, as_pairs, build_plan
from repro.ged.results import GedOutcome
from repro.ged.spans import span

Taus = Union[float, Sequence[float]]

_CONFIG_FIELDS = {f.name for f in dataclasses.fields(EngineConfig)}


class GedEngine:
    """Facade over the pluggable GED backends.

    Parameters
    ----------
    backend : ``"auto"`` (default) | ``"exact"`` | ``"jax"`` | ``"pallas"``
        | ``"sharded"`` or any name registered via
        :func:`repro.ged.register_backend`.
    slots : pin every batch to this slot count instead of per-pair
        power-of-two bucketing (bucketing is the default).
    vocab : optional ``(vertex_labels, edge_labels)`` universe.  Pin it when
        issuing many calls over the same label alphabet so the engine's
        static shapes — and hence its compilations — are stable across
        calls.
    batch_size : scheduler batch size (``auto`` backend only).
    mesh : device mesh for the ``"sharded"`` and ``"auto"`` backends
        (``"sharded"`` defaults to a 1-D mesh over every local device;
        ``"auto"`` runs single-device unless a mesh is given, in which
        case every escalation rung's batches are ``shard_map``-ed over
        it).  Ignored by the other backends.
    overlap : overlapped rung execution (``auto`` backend only, default
        True): batches dispatch asynchronously, decided pairs drain while
        the next rung is in flight, and host-solver pairs run behind
        device work.  ``overlap=False`` is the strictly sequential rung
        loop.  Outcomes are identical either way.
    max_in_flight : how many rung buckets may be dispatched but not yet
        drained at once (``auto`` backend, overlap mode only).
    cache : keep an engine-level result cache (default True): duplicate
        pairs — within one batch or across calls — are answered from the
        cache instead of re-executing.  ``cache_size`` bounds it (LRU).
    shared_cache_dir : directory for the *cross-process* result-cache
        tier (default: the ``REPRO_GED_SHARED_CACHE_DIR`` environment
        variable; unset means off).  An on-disk, file-locked LRU of
        certified scalars (:class:`repro.store_io.SharedResultCache`)
        layered *behind* the in-memory cache: probed on in-memory
        misses (hits are promoted back into memory), written through
        with every certified outcome, shared safely between concurrent
        processes.  Counters appear in :attr:`stats` as
        ``shared_cache_*``.
    compile_cache_dir : turn on jax's *persistent* compilation cache in
        this directory (default ``None``: the engine leaves the cache as
        the process set it up, see
        :func:`repro.ged.exec.enable_compile_cache`).  A
        ``JAX_COMPILATION_CACHE_DIR`` set by the caller takes precedence.
        Compiled engine executables are serialised there and re-loaded by
        later processes, so the multi-second first-call compile is paid
        once per machine rather than once per process.  Process-global
        (jax has one cache); hit/miss/entry counters appear in
        :attr:`stats` as ``persistent_cache_*``.
    autotune_dir : directory for the measured kernel-tuning table
        (default: the ``REPRO_GED_AUTOTUNE_DIR`` environment variable;
        unset means in-memory only).  ``use_kernel="auto"`` resolves each
        bucket's ``(slots, batch)`` shape to fused/unfused kernels plus
        tuned tile sizes through the table — pre-warm it with
        :func:`repro.kernels.autotune.tune`.  Process-global like the
        compile cache; counters appear in :attr:`stats` as
        ``autotune_*`` alongside ``pallas_interpret`` (True when Pallas
        kernels would run in interpret mode, i.e. timings here are not
        accelerator numbers).
    digest : graph-hash family for the result-cache keys.  ``"exact"``
        (default) keys on byte-identical graphs, so cached mappings stay
        index-compatible; ``"wl"`` keys on Weisfeiler-Leman canonical
        digests, so *isomorphic* duplicates also hit.  ``"wl"`` is a
        deliberate precision trade for duplicate-heavy graph-DB traffic:
        WL refinement is an incomplete isomorphism test, so WL-equivalent
        non-isomorphic pairs (rare outside uniform-label regular graphs)
        alias to one cache entry and the second pair is answered with the
        first pair's distance.  Cache copies also drop their vertex
        mappings.  :class:`repro.ged.GraphStore` gets the same hit-rate
        win soundly instead — WL dedup confirmed by certified
        zero-distance checks at ingest — and keeps its engine on
        ``"exact"``.
    deadline_s : wall-clock budget per ``compute``/``verify``/``flush``
        call (default ``None`` = unbounded, bit-identical to an engine
        without the robustness layer).  On expiry, in-flight device work
        drains, remaining rungs are skipped, and *every* pair still
        returns a :class:`GedOutcome` carrying best-so-far admissible
        ``lower_bound``/``upper_bound`` with ``certified=False`` and
        ``timed_out`` set — never an exception, never a missing result.
        Each entry point takes a per-call override.  See
        ``docs/robustness.md``.
    per_pair_deadline_s : additional per-pair budget for host-solver
        searches (cooperative check inside the search loop), capped by
        whatever remains of ``deadline_s``.
    fault_inject : deterministic fault spec (string for
        :class:`repro.ged.faults.FaultInjector`, or an injector
        instance) scoped to this engine; the ``REPRO_GED_FAULT_INJECT``
        environment variable injects process-wide instead.
    retry : :class:`repro.ged.faults.RetryPolicy` for transient dispatch
        failures (default: 2 retries, exponential backoff + jitter).
    Remaining keyword arguments (``pool``, ``expand``, ``max_iters``,
    ``sweeps``, ``bound``, ``strategy``, ``use_kernel``) override
    :class:`EngineConfig` defaults.  ``use_kernel`` is implied by the
    ``"jax"``/``"sharded"`` (False) and ``"pallas"`` (True) backend names —
    passing a contradicting boolean there raises, while
    ``use_kernel="auto"`` is accepted on *every* backend: it defers the
    fused/unfused choice to the measured per-bucket dispatch
    (:mod:`repro.kernels.autotune`), which never changes outcomes, only
    which bit-identical implementation runs.

    Examples
    --------
    >>> from repro import ged
    >>> q = ([0, 1], [(0, 1, 1)])           # (vlabels, edges) adapter form
    >>> g = ([0, 2], [(0, 1, 1)])
    >>> eng = ged.GedEngine("exact")
    >>> [o.ged for o in eng.compute([(q, g)])]
    [1.0]
    >>> [o.similar for o in eng.verify([(q, g)], tau=1.0)]
    [True]

    The anytime deadline contract — an exhausted budget still answers
    every pair, with sound (here: cheap stage-0-style) bounds:

    >>> eng = ged.GedEngine("exact", deadline_s=0.0)    # expires on arrival
    >>> out, = eng.compute([(q, g)])
    >>> out.timed_out, out.certified, out.lower_bound, out.upper_bound
    (True, False, 1.0, inf)
    >>> out, = eng.compute([(q, g)], deadline_s=60.0)   # per-call override
    >>> out.ged, out.certified
    (1.0, True)

    Deterministic fault injection — an injected host-solver failure
    degrades (uncertified, admissible bounds), never errors:

    >>> eng = ged.GedEngine("exact", fault_inject="host@times=1")
    >>> out, = eng.compute([(q, g)])
    >>> out.degraded, out.certified, out.lower_bound <= 1.0
    (True, False, True)
    """

    def __init__(self, backend: str = "auto", *,
                 slots: Optional[int] = None,
                 vocab: Optional[Vocab] = None,
                 batch_size: int = 256,
                 mesh=None,
                 overlap: bool = True,
                 max_in_flight: int = 4,
                 cache: bool = True,
                 cache_size: int = 4096,
                 shared_cache_dir: Optional[str] = None,
                 compile_cache_dir: Optional[str] = None,
                 autotune_dir: Optional[str] = None,
                 digest: str = "exact",
                 deadline_s: Optional[float] = None,
                 per_pair_deadline_s: Optional[float] = None,
                 fault_inject: Union[None, str, FaultInjector] = None,
                 retry: Optional[RetryPolicy] = None,
                 config: Optional[EngineConfig] = None,
                 **config_overrides):
        unknown = set(config_overrides) - _CONFIG_FIELDS
        if unknown:
            raise TypeError(f"unknown GedEngine options: {sorted(unknown)}")
        self.deadline_s = deadline_s
        self.per_pair_deadline_s = per_pair_deadline_s
        self._injector = (FaultInjector(fault_inject)
                          if isinstance(fault_inject, str)
                          else fault_inject)
        self._retry = retry if retry is not None else RetryPolicy()
        self._fault_stats: Dict[str, float] = {}
        if digest not in DIGESTS:
            raise ValueError(f"unknown digest {digest!r}; "
                             f"expected one of {sorted(DIGESTS)}")
        self.digest = digest
        self.compile_cache_dir = (enable_compile_cache(compile_cache_dir)
                                  if compile_cache_dir is not None else None)
        self.autotune_dir = enable_autotune(autotune_dir)
        if config is None:
            config = EngineConfig(**{"use_kernel": False, **config_overrides})
        elif config_overrides:
            config = dataclasses.replace(config, **config_overrides)
        self.slots = slots
        self.vocab = vocab
        self._cache = ResultCache(cache_size) if cache else None
        self._shared = None
        if shared_cache_dir is None:
            # repro.store_io.shared_cache.SHARED_CACHE_ENV; lazily
            # imported below so the leaf modules stay cycle-free
            shared_cache_dir = os.environ.get(
                "REPRO_GED_SHARED_CACHE_DIR") or None
        if shared_cache_dir:
            from repro.store_io.shared_cache import SharedResultCache
            self._shared = SharedResultCache(str(shared_cache_dir))
        self.shared_cache_dir = shared_cache_dir
        self._backend: Backend = make_backend(
            backend, batch_size=batch_size, mesh=mesh, overlap=overlap,
            max_in_flight=max_in_flight)
        self.backend = self._backend.name
        # "jax" means pure-jnp and "pallas" means kernels; default the flag
        # from the backend name and refuse a contradicting boolean.
        # "auto" is welcome everywhere: measured dispatch picks among
        # bit-identical implementations, so it cannot contradict what a
        # backend name promises about outcomes.
        self._kernel_default = getattr(self._backend, "kernel_default", None)
        if self._kernel_default is not None:
            asked = config_overrides.get("use_kernel")
            if asked == "auto":
                pass
            elif asked is not None and asked != self._kernel_default:
                raise ValueError(
                    f"backend {backend!r} implies use_kernel="
                    f"{self._kernel_default}; use the "
                    f"{'pallas' if asked else 'jax'!r} backend instead")
            else:
                config = dataclasses.replace(config,
                                             use_kernel=self._kernel_default)
        self.config = config
        # backends registered before the robustness layer may not take
        # ``ctx``; only pass it when the run() signature names it
        import inspect
        try:
            self._backend_takes_ctx = "ctx" in inspect.signature(
                self._backend.run).parameters
        except (TypeError, ValueError):            # pragma: no cover
            self._backend_takes_ctx = False
        self._pending: List[Tuple[object, object, Optional[float]]] = []

    # ------------------------------------------------------------ batch

    def compute(self, pairs, vocab: Optional[Vocab] = None,
                deadline_s: Union[None, float, Deadline] = None,
                per_pair_deadline_s: Optional[float] = None,
                **config_overrides) -> List[GedOutcome]:
        """Exact-with-certificate GED for every pair.

        ``vocab`` overrides the engine's label universe for this call
        only (callers with a known corpus vocabulary — e.g.
        :class:`repro.ged.GraphStore` — keep compile keys stable without
        mutating shared engine state).  ``deadline_s`` /
        ``per_pair_deadline_s`` override the engine-level budgets for
        this call (anytime contract: an expired budget yields
        uncertified best-so-far bounds, never an exception).

        >>> from repro import ged
        >>> outs = ged.GedEngine("exact").compute(
        ...     [(([0], []), ([0], []))])           # identical graphs
        >>> outs[0].ged, outs[0].certified
        (0.0, True)
        """
        return self._run(pairs, None, verification=False,
                         overrides=config_overrides, vocab=vocab,
                         deadline_s=deadline_s,
                         per_pair_deadline_s=per_pair_deadline_s)

    def verify(self, pairs, tau: Taus, vocab: Optional[Vocab] = None,
               deadline_s: Union[None, float, Deadline] = None,
               per_pair_deadline_s: Optional[float] = None,
               **config_overrides) -> List[GedOutcome]:
        """Certified ``delta(q, g) <= tau``? for every pair.

        ``tau`` is a scalar (broadcast) or one threshold per pair;
        ``vocab`` is a per-call label-universe override (see
        :meth:`compute`); ``deadline_s`` / ``per_pair_deadline_s`` are
        the per-call anytime budgets (see :meth:`compute`).

        >>> from repro import ged
        >>> pair = (([0], []), ([1], []))           # distance 1
        >>> [o.similar for o in ged.GedEngine("exact").verify(
        ...     [pair, pair], tau=[0.5, 1.5])]
        [False, True]
        """
        return self._run(pairs, tau, verification=True,
                         overrides=config_overrides, vocab=vocab,
                         deadline_s=deadline_s,
                         per_pair_deadline_s=per_pair_deadline_s)

    # -------------------------------------------------------- streaming

    def submit(self, q, g, tau: Optional[float] = None) -> int:
        """Enqueue one pair (verification when ``tau`` is given, otherwise
        computation); returns its ticket — the index into ``flush()``'s
        result list.

        >>> from repro import ged
        >>> eng = ged.GedEngine("exact")
        >>> eng.submit(([0], []), ([1], []))        # computation
        0
        >>> eng.submit(([0], []), ([0], []), tau=0.5)   # verification
        1
        >>> [(o.ged, o.similar) for o in eng.flush()]
        [(1.0, None), (None, True)]
        """
        self._pending.append((q, g, None if tau is None else float(tau)))
        return len(self._pending) - 1

    def flush(self, deadline_s: Union[None, float, Deadline] = None,
              per_pair_deadline_s: Optional[float] = None
              ) -> List[GedOutcome]:
        """Answer every submitted pair, in submission order.

        Mixed computation/verification submissions come back as one list
        aligned with the tickets :meth:`submit` returned (see the example
        there); a drained engine flushes to ``[]``.  ``deadline_s`` is
        one shared budget for the whole flush (the computation and
        verification sub-batches draw from the same clock).
        """
        pending, self._pending = self._pending, []
        if not pending:
            return []
        dl = deadline_s if deadline_s is not None else self.deadline_s
        # one Deadline for both sub-batches, so a flush-level budget is a
        # single clock, not one-per-mode
        shared = dl if isinstance(dl, Deadline) or dl is None \
            else Deadline(dl)
        results: List[Optional[GedOutcome]] = [None] * len(pending)
        comp = [i for i, (_, _, tau) in enumerate(pending) if tau is None]
        veri = [i for i, (_, _, tau) in enumerate(pending) if tau is not None]
        if comp:
            outs = self.compute([(pending[i][0], pending[i][1])
                                 for i in comp], deadline_s=shared,
                                per_pair_deadline_s=per_pair_deadline_s)
            for i, o in zip(comp, outs):
                results[i] = o
        if veri:
            outs = self.verify([(pending[i][0], pending[i][1])
                                for i in veri],
                               [pending[i][2] for i in veri],
                               deadline_s=shared,
                               per_pair_deadline_s=per_pair_deadline_s)
            for i, o in zip(veri, outs):
                results[i] = o
        return results  # type: ignore[return-value]

    # ------------------------------------------------------------ stats

    @property
    def batch_multiple(self) -> int:
        """Shard count every batch is padded to (1 on a single device).

        >>> from repro import ged
        >>> ged.GedEngine("jax").batch_multiple
        1
        """
        return getattr(self._backend, "batch_multiple", 1)

    @property
    def stats(self) -> Dict[str, float]:
        """Backend + executor counters plus cache hit/miss totals.

        Per backend: the ``auto`` pipeline reports ``pairs`` /
        ``escalated`` / ``host_solved`` / ``batches`` / ``dispatches`` and
        per-rung survivor counts (``survivors_rung_0``, ...).  Every
        engine adds ``executor_*`` (``executor_pairs`` real pairs
        dispatched, ``executor_rows`` the padded batches they rode in),
        ``compile_cache_*`` and ``result_cache_*`` counters where
        applicable, plus the kernel-dispatch telemetry:
        ``autotune_hits`` / ``autotune_misses`` / ``autotune_sweep_s`` /
        ``autotune_entries`` and ``pallas_interpret`` (True when Pallas
        kernels fall back to interpret mode — CPU — so bench rows cannot
        masquerade as accelerator numbers).  Robustness counters
        (``retries``, ``degraded_kernel``, ``degraded_host``,
        ``fault_*``, ``timed_out_pairs``,
        ``shared_cache_lock_timeouts``) appear once the corresponding
        event has happened — see ``docs/robustness.md``.

        >>> from repro import ged
        >>> eng = ged.GedEngine("exact")
        >>> _ = eng.compute([(([0], []), ([1], []))])
        >>> eng.stats["result_cache_misses"]
        1
        """
        out: Dict[str, float] = dict(getattr(self._backend, "stats", {}))
        executor = getattr(self._backend, "executor", None)
        if executor is not None:
            out.update({f"executor_{k}": v
                        for k, v in executor.stats.items()})
        cache = getattr(self._backend, "cache", None)
        if cache is not None:
            out["compile_cache_hits"] = cache.stats.hits
            out["compile_cache_misses"] = cache.stats.misses
        if self._cache is not None:
            out["result_cache_hits"] = self._cache.hits
            out["result_cache_misses"] = self._cache.misses
            out["result_cache_entries"] = len(self._cache)
            out["index_pivot_hits"] = self._cache.pivot_hits
            out["index_pivot_misses"] = self._cache.pivot_misses
        if self._shared is not None:
            out["shared_cache_hits"] = self._shared.hits
            out["shared_cache_misses"] = self._shared.misses
            out["shared_cache_evictions"] = self._shared.evictions
            out["shared_cache_entries"] = self._shared.entries()
            out["shared_cache_lock_timeouts"] = self._shared.lock_timeouts
        # robustness counters accumulated across runs (retries, degraded_*,
        # fault_*, timed_out_pairs) — absent keys mean nothing happened
        out.update(self._fault_stats)
        out.update(persistent_cache_stats())
        out.update(autotune_stats())
        return out

    def cached_distance(self, q=None, g=None, *,
                        digests: Optional[Tuple[bytes, bytes]] = None
                        ) -> Optional[float]:
        """A certified exact distance for one pair straight from the result
        cache — no planning, no execution, ``None`` on a miss.

        This is the distance-reuse hook :class:`repro.ged.CandidateIndex`
        prunes through: DB–DB distances that earlier traffic (top-k walks,
        pivot probes, ingest seeding) left in the cache are read back by
        digest and fed into the triangle bound
        ``|d(q,p) - d(p,y)| <= d(q,y)``.  Pass ``digests=(dq, dg)`` when
        the graphs are already hashed (the index pre-digests its corpus);
        both orientations of the pair are probed.  Only *certified
        computation* entries answer — verification entries carry no exact
        distance, uncertified ones no guarantee — and only the scalar
        comes back, never the cached outcome (so a WL-aliased entry's
        dropped mapping stays dropped).  Lookups count into
        ``stats["index_pivot_hits"]`` / ``["index_pivot_misses"]``, not
        the query-path ``result_cache_*`` totals.

        >>> from repro import ged
        >>> eng = ged.GedEngine("exact")
        >>> a, b = ([0], []), ([1], [])
        >>> eng.cached_distance(a, b) is None       # nothing cached yet
        True
        >>> _ = eng.compute([(a, b)])
        >>> eng.cached_distance(b, a)               # either orientation
        1.0
        """
        if self._cache is None:
            return None
        if digests is None:
            fn = DIGESTS[self.digest]
            digests = (fn(as_graph(q)), fn(as_graph(g)))
        for dq, dg in (digests, digests[::-1]):
            key = pair_key_from_digests(dq, dg, False, None, self.config,
                                        self.backend, digest=self.digest)
            out = self._cache.peek(key)
            if out is not None and out.certified and out.ged is not None:
                self._cache.pivot_hits += 1
                return float(out.ged)
        self._cache.pivot_misses += 1
        return None

    # --------------------------------------------------------- internal

    def _run(self, pairs, tau: Optional[Taus], verification: bool,
             overrides: dict,
             vocab: Optional[Vocab] = None,
             deadline_s: Union[None, float, Deadline] = None,
             per_pair_deadline_s: Optional[float] = None
             ) -> List[GedOutcome]:
        unknown = set(overrides) - _CONFIG_FIELDS
        if unknown:
            raise TypeError(f"unknown engine options: {sorted(unknown)}")
        asked = overrides.get("use_kernel")
        if (asked is not None and asked != "auto"
                and self._kernel_default is not None
                and asked != self._kernel_default):
            raise ValueError(
                f"backend {self.backend!r} implies use_kernel="
                f"{self._kernel_default}")
        cfg = dataclasses.replace(self.config, **overrides) \
            if overrides else self.config
        with span("ged.engine.run") as run_span:
            pairs = as_pairs(pairs)
            n = len(pairs)
            if verification:
                taus = np.broadcast_to(
                    np.asarray(tau, dtype=np.float32), (n,)).copy()
            else:
                taus = np.zeros((n,), dtype=np.float32)

            results: List[Optional[GedOutcome]] = [None] * n
            run_idx = list(range(n))
            keys: List[Optional[tuple]] = [None] * n
            dup_of: Dict[int, int] = {}
            if self._cache is not None or self._shared is not None:
                run_idx, seen = [], {}
                for i, (q, g) in enumerate(pairs):
                    keys[i] = pair_key(
                        q, g, verification,
                        float(taus[i]) if verification else None, cfg,
                        self.backend, digest=self.digest)
                    if keys[i] in seen:
                        # duplicate within this batch: runs once, answers twice
                        dup_of[i] = seen[keys[i]]
                        if self._cache is not None:
                            self._cache.hits += 1
                        continue
                    hit = self._cache.get(keys[i]) \
                        if self._cache is not None else None
                    if hit is None and self._shared is not None:
                        # the cross-process tier answers in-memory misses;
                        # promote hits so this process stops paying disk
                        hit = self._shared.get(keys[i])
                        if hit is not None and self._cache is not None:
                            self._cache.put(keys[i], self._cache_view(hit))
                    if hit is not None:
                        results[i] = hit
                    else:
                        seen[keys[i]] = i
                        run_idx.append(i)

            run_span.set_metadata(pairs=n, run=len(run_idx))
            if run_idx:
                plan = build_plan(
                    [pairs[i] for i in run_idx], slots=self.slots,
                    vocab=vocab if vocab is not None else self.vocab,
                    batch_multiple=self.batch_multiple)
                dl = deadline_s if deadline_s is not None else self.deadline_s
                pp = (per_pair_deadline_s if per_pair_deadline_s is not None
                      else self.per_pair_deadline_s)
                ctx = RunContext(
                    deadline=dl if isinstance(dl, Deadline) else Deadline(dl),
                    per_pair_deadline_s=pp,
                    injector=self._injector, retry=self._retry)
                if self._backend_takes_ctx:
                    outs = self._backend.run(plan, taus[run_idx], verification,
                                             cfg, ctx=ctx)
                else:
                    outs = self._backend.run(plan, taus[run_idx], verification,
                                             cfg)
                for k, v in ctx.stats.items():
                    self._fault_stats[k] = self._fault_stats.get(k, 0) + v
                for i, o in zip(run_idx, outs):
                    results[i] = o
                    # never cache a timed-out or fault-degraded *uncertified*
                    # answer: a later, unconstrained run must not be poisoned
                    # by this run's budget or faults (degraded-but-certified
                    # answers are bit-identical, so they stay cacheable)
                    if o.timed_out or (not o.certified
                                       and o.stats.get("degraded")):
                        continue
                    if self._cache is not None:
                        self._cache.put(keys[i], self._cache_view(o))
                    if self._shared is not None:
                        self._shared.put(keys[i], o)   # certified-only inside
            for i, j in dup_of.items():
                # a distinct outcome per position, so mutating one entry
                # cannot leak into its duplicates (or the cache)
                results[i] = detached(self._cache_view(results[j]),
                                      {**results[j].stats, "cached": True})
            return results  # type: ignore[return-value]

    def _cache_view(self, outcome: GedOutcome) -> GedOutcome:
        """What a cache (or in-batch duplicate) may reuse of ``outcome``.

        Exact digests key byte-identical graphs, so everything is
        reusable; WL digests key isomorphism classes, so the vertex
        mapping — index-valid only for the graph that produced it — is
        dropped from what duplicates see.
        """
        if self.digest == "exact" or outcome.mapping is None:
            return outcome
        return dataclasses.replace(outcome, mapping=None)


# ------------------------------------------------- module-level helpers

def compute(pairs, backend: str = "auto", **options) -> List[GedOutcome]:
    """One-shot :meth:`GedEngine.compute` with a throwaway engine.

    Compiled executables persist in the process-wide jit cache, so repeated
    module-level calls stay cheap; hold a :class:`GedEngine` to accumulate
    stats or stream with ``submit``/``flush``.

    >>> from repro import ged
    >>> [o.ged for o in ged.compute([(([0], []), ([1], []))],
    ...                             backend="exact")]
    [1.0]
    """
    return GedEngine(backend, **options).compute(pairs)


def verify(pairs, tau: Taus, backend: str = "auto",
           **options) -> List[GedOutcome]:
    """One-shot :meth:`GedEngine.verify` with a throwaway engine.

    >>> from repro import ged
    >>> [o.similar for o in ged.verify([(([0], []), ([1], []))], tau=2.0,
    ...                                backend="exact")]
    [True]
    """
    return GedEngine(backend, **options).verify(pairs, tau)
