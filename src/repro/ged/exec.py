"""The execution layer under the ``repro.ged`` facade.

Backends (:mod:`repro.ged.backends`) are pure *policies* — which pairs run
at which rung, with which bounds, when to escalate.  Everything about *how*
a packed bucket actually reaches silicon lives here:

* :class:`Executor` — default placement: one jit call per shape bucket on
  the default device, compile-cache bookkeeping, bucket packing and result
  unpacking.  Every backend drives one of these.
* :class:`ShardedExecutor` — ``shard_map`` the vmapped search over the
  device mesh's batch axes (``pod`` x ``data`` per
  :func:`repro.parallel.sharding.default_rules`), with bucket batches
  padded to shard multiples by :mod:`repro.ged.plan`.  The search's
  sorted-pool loop is built from batch-partitionable HLO — ``lax.sort``
  over the child keys, binary-search rank merges, gathers with explicit
  batch dims — so the pair batch stays sharded (a ``lax.top_k``
  custom-call would all-gather it — see ``repro/parallel/ops.py``).
  One-shard meshes skip ``shard_map`` entirely (the single-device fast
  path).
* :class:`PendingBatch` — the future returned by
  :meth:`Executor.run_packed_async`: a dispatched-but-not-yet-drained
  engine invocation, riding JAX's async dispatch.  The overlapped ``auto``
  escalation scheduler keeps several in flight and drains them as their
  device work lands.
* :class:`ResultCache` — engine-level outcome cache keyed on canonical
  pair digests (label-vocab-independent, tau-aware for verification) that
  :class:`repro.ged.GedEngine` consults before any executor runs.

Policy and placement compose freely: any backend policy runs unchanged on
any executor, which is what async / remote / multi-host work hangs off.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
from typing import Dict, Optional, Sequence

import numpy as np

from repro.core.engine import api as engine_api
from repro.core.engine.search import EngineConfig
from repro.core.exact.graph import Graph
from repro.kernels import autotune
from repro.ged.plan import Bucket, CompileCache, Vocab, pack_bucket
from repro.ged.results import GedOutcome, engine_mapping
from repro.ged.spans import span


# ------------------------------------------------- persistent compile cache

# Where the persistent compile cache lives when the caller did not place it
# with ``JAX_COMPILATION_CACHE_DIR``: one fixed directory inside the
# checkout.  The path is never built from a temporary name, a process id or
# the time — a cache that moves is never hit again.
DEFAULT_COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))

# Process-wide persistent-cache state: the enabled directory plus hit/miss
# counters fed by jax's monitoring events.  jax's compilation cache is a
# process-global switch, so this is module state rather than per-executor —
# every engine in the process shares the one cache (that is the point: the
# multi-second engine compile is paid once per *machine*, not per process).
# ``listener`` tracks the (unremovable) monitoring-listener registration
# separately from ``dir`` so disabling and re-enabling the cache can never
# register a second listener and double-count events.
_PERSISTENT_CACHE: Dict[str, object] = {"dir": None, "hits": 0, "misses": 0,
                                        "listener": False}


def _cache_event_listener(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _PERSISTENT_CACHE["hits"] += 1          # type: ignore[operator]
    elif event == "/jax/compilation_cache/cache_misses":
        _PERSISTENT_CACHE["misses"] += 1        # type: ignore[operator]


def enable_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: jax reads
    it itself, and nothing here sets another one.  Otherwise the cache
    goes to ``cache_dir``, or by default to :data:`DEFAULT_COMPILE_CACHE_DIR`
    (``.jax_cache/`` at the root of the checkout).  Compiled engine
    executables are serialised there and re-loaded by *later processes*,
    so the multi-second first-call compile is paid once per machine.
    Entry points call this at start-up; importing the library enables
    nothing.  Idempotent — repeat calls (every
    ``GedEngine(compile_cache_dir=...)``) just re-point the directory.
    Hit/miss counts land in :func:`persistent_cache_stats` (and therefore
    ``engine.stats``).

    >>> os.path.basename(DEFAULT_COMPILE_CACHE_DIR)
    '.jax_cache'
    """
    import jax
    from jax import monitoring
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = str(outside or cache_dir or DEFAULT_COMPILE_CACHE_DIR)
    if not _PERSISTENT_CACHE["listener"]:
        monitoring.register_event_listener(_cache_event_listener)
        _PERSISTENT_CACHE["listener"] = True
    os.makedirs(path, exist_ok=True)
    if not outside:
        jax.config.update("jax_compilation_cache_dir", path)
    # the engine's jit is exactly the compile worth persisting — don't let
    # the default 1s threshold skip mid-sized bucket shapes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if _PERSISTENT_CACHE["dir"] != path:
        # jax latches its cache-enabled check at the first compile of the
        # process; (re-)pointing the directory afterwards needs an explicit
        # reset or the new setting is silently ignored
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    _PERSISTENT_CACHE["dir"] = path
    return path


def persistent_cache_stats() -> Dict[str, float]:
    """Process-wide persistent compile-cache counters (empty when off).

    ``persistent_cache_hits`` / ``persistent_cache_misses`` count jax's
    disk-cache lookups this process; ``persistent_cache_entries`` is the
    number of serialised executables currently in the directory.
    """
    d = _PERSISTENT_CACHE["dir"]
    if d is None:
        return {}
    try:
        entries = len(os.listdir(str(d)))
    except OSError:
        entries = 0
    return {"persistent_cache_hits": float(_PERSISTENT_CACHE["hits"]),
            "persistent_cache_misses": float(_PERSISTENT_CACHE["misses"]),
            "persistent_cache_entries": float(entries)}


# ---------------------------------------------------------------- executors

class PendingBatch:
    """One dispatched-but-not-yet-drained engine invocation.

    Wraps the dict of ``jax.Array`` futures an executor's dispatch step
    produced.  Because JAX dispatches asynchronously, the device may still
    be crunching when a ``PendingBatch`` is handed out — :meth:`ready`
    polls without blocking, :meth:`result` blocks once and caches the
    numpy conversion.  The overlapped ``auto`` scheduler keeps a small
    queue of these in flight and does host-solver work while they cook.

    Plain numpy inputs (no ``is_ready`` method) count as always ready:

    >>> import numpy as np
    >>> p = PendingBatch({"ged": np.zeros(2)})
    >>> p.ready()
    True
    >>> p.result()["ged"]
    array([0., 0.])

    ``recover`` (optional) is the executor's degraded re-dispatch: JAX
    surfaces some runtime failures only at materialisation, so
    :meth:`result` catches them, runs ``recover()`` synchronously (the
    bit-identical unfused path) and marks ``flags["degraded"]``.
    ``check`` is the deterministic fault-injection hook for that same
    window (the ``result`` site).  ``flags`` records what the robust
    dispatch path did (``retries`` / ``degraded``) so backends can fold
    it into outcome stats.
    """

    def __init__(self, arrays, recover=None, check=None,
                 flags: Optional[Dict[str, float]] = None):
        self._arrays = arrays
        self._result: Optional[Dict[str, np.ndarray]] = None
        self._recover = recover
        self._check = check
        self.flags: Dict[str, float] = {} if flags is None else flags

    def ready(self) -> bool:
        """True when every output has landed (never blocks)."""
        if self._result is not None:
            return True
        for v in self._arrays.values():
            is_ready = getattr(v, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def result(self) -> Dict[str, np.ndarray]:
        """Block until the batch lands; numpy result dict (cached).

        A materialisation failure with a ``recover`` path re-runs the
        batch on the degraded config instead of raising; without one the
        failure propagates to the backend (which host-solves the pairs).
        """
        if self._result is None:
            try:
                if self._check is not None:
                    self._check()
                self._result = {k: np.asarray(v)
                                for k, v in self._arrays.items()}
            except Exception:
                if self._recover is None:
                    raise
                self.flags["degraded"] = True
                self._result = {k: np.asarray(v)
                                for k, v in self._recover().items()}
            self._arrays = None
        return self._result


class Executor:
    """Runs packed buckets on the default device.

    Owns the things backends used to hand-roll: the compile-cache mirror,
    batch-shape policy (``batch_multiple``), packing, and invocation
    counters (``stats``) — so a policy layer above never touches jit, jax
    arrays, or device placement.  Subclasses override :meth:`_dispatch`
    (and usually ``batch_multiple``) only; the sync/async entry points and
    the bookkeeping are shared.

    >>> ex = Executor()
    >>> ex.batch_multiple
    1
    >>> sorted(ex.stats)
    ['calls', 'pairs', 'rows']
    """

    name = "local"

    def __init__(self) -> None:
        self.cache = CompileCache()
        # pairs: real pairs dispatched; rows: the padded batches they rode in
        self.stats: Dict[str, float] = {"calls": 0, "pairs": 0, "rows": 0}
        self._executables: Dict[tuple, object] = {}

    @property
    def batch_multiple(self) -> int:
        """Every bucket batch must be a multiple of this (shard count)."""
        return 1

    def pack(self, pairs, slots: int, vocab: Optional[Vocab]):
        """Pack ``pairs`` with this executor's batch-shape policy.

        Returns ``(tensors, real_count)`` with the batch dimension padded
        to a power of two rounded up to :attr:`batch_multiple`::

            packed, real = executor.pack(pairs, slots=8, vocab=plan.vocab)
        """
        return pack_bucket(pairs, slots, vocab, self.batch_multiple)

    def run_packed_async(self, packed, taus: np.ndarray, cfg: EngineConfig,
                         verification: bool,
                         real: Optional[int] = None,
                         ctx=None, rung: Optional[int] = None
                         ) -> PendingBatch:
        """Dispatch one engine invocation without waiting for the result.

        Returns a :class:`PendingBatch` immediately — JAX queues the device
        work and hands back array futures — so callers can dispatch rung
        *k+1* or solve host pairs while rung *k* is in flight.  ``real`` —
        pairs before batch padding, for the ``pairs`` counter (defaults to
        the padded batch when the caller doesn't know).  ``ctx`` — the
        engine's :class:`repro.ged.faults.RunContext` (retry policy, fault
        injector, counters); ``rung`` labels the dispatch for rung-scoped
        fault specs.  Both default to off, which is the bit-identical
        legacy path.

        Example (the overlapped ``auto`` scheduler's inner loop)::

            pending = executor.run_packed_async(packed, taus, cfg, False)
            do_host_work_while(not pending.ready())
            out = pending.result()          # numpy dict, blocks if needed
        """
        self._check_batch(packed)
        real = packed.batch if real is None else int(real)
        with span("ged.exec.dispatch", rung=rung, slots=packed.slots,
                  batch=packed.batch, real=real):
            # ``use_kernel="auto"`` resolves to a concrete per-bucket
            # kernel plan *here*, before anything jit-keyed sees the
            # config: the resolved dispatch (tuning-table lookup or static
            # heuristic for unmeasured shapes) is pinned on the config, so
            # the jit cache, the CompileCache ledger and the sharded
            # executor's fn cache all key on the actual decision.
            # Outcomes are bit-identical across dispatch choices, so
            # result caching upstream stays sound.
            cfg = autotune.resolve_config(cfg, packed.slots, packed.batch)
            self.cache.record(packed, cfg, verification)
            self.stats["calls"] += 1
            self.stats["pairs"] += real
            self.stats["rows"] += packed.batch
            return self._robust_dispatch(packed, taus, cfg, verification,
                                         ctx, rung)

    def _robust_dispatch(self, packed, taus, cfg, verification, ctx,
                         rung) -> PendingBatch:
        """Dispatch with the retry policy and kernel-degradation ladder.

        A program the Pallas lowering or the XLA compiler refuses raises
        :class:`repro.ged.faults.CompileRefusal` and propagates: that is a
        fault in the program, and degrading would hide it.  Runtime and
        injected faults take the ladder: transient ones retry with
        exponential backoff + jitter
        (:class:`repro.ged.faults.RetryPolicy`); permanent kernel failures
        fall back to the bit-identical unfused config
        (``use_kernel=False``); a failure of the unfused path too
        propagates, and the backend above degrades the bucket to the host
        solver.  On a clean dispatch this is exactly the legacy path —
        the try/except costs nothing unless something raises.
        """
        import time as _time

        from repro.ged import faults as _faults

        inj = _faults.get_injector(ctx)
        retry = ctx.retry if ctx is not None else _faults.RetryPolicy()

        def bump(key: str, by: float = 1) -> None:
            self.stats[key] = self.stats.get(key, 0) + by
            if ctx is not None:
                ctx.bump(key, by)

        ladder = [cfg]
        if bool(cfg.use_kernel):
            ladder.append(dataclasses.replace(cfg, use_kernel=False,
                                              dispatch=None))
        flags: Dict[str, float] = {}
        last_exc: Optional[Exception] = None
        for step, step_cfg in enumerate(ladder):
            if step > 0:
                bump("degraded_kernel")
                flags["degraded"] = True
                _faults.warn_once(
                    f"degrade-kernel-{self.name}",
                    f"{self.name} executor: kernel path failed "
                    f"({last_exc!r}); degrading to the bit-identical "
                    "unfused config for this and retried dispatches")
            attempt = 0
            while True:
                try:
                    if inj is not None:
                        inj.check("dispatch", rung)
                        if bool(step_cfg.use_kernel):
                            inj.check("kernel", rung)
                    arrays = self._dispatch(packed, taus, step_cfg,
                                            verification)
                    recover = None
                    if step + 1 < len(ladder):
                        nxt = ladder[step + 1]

                        def recover(_nxt=nxt):
                            bump("degraded_kernel")
                            _faults.warn_once(
                                f"degrade-kernel-{self.name}",
                                f"{self.name} executor: kernel batch "
                                "failed at materialisation; re-running "
                                "unfused")
                            return self._dispatch(packed, taus, _nxt,
                                                  verification)
                    check = None
                    if inj is not None:
                        check = (lambda: inj.check("result", rung))
                    return PendingBatch(arrays, recover=recover,
                                        check=check, flags=flags)
                except _faults.CompileRefusal:
                    raise
                except Exception as exc:
                    last_exc = exc
                    if (_faults.classify_transient(exc)
                            and attempt < retry.max_retries):
                        bump("retries")
                        flags["retries"] = flags.get("retries", 0) + 1
                        _time.sleep(retry.backoff_s(attempt))
                        attempt += 1
                        continue
                    bump("fault_dispatch")
                    break               # next ladder step (or give up)
        raise last_exc

    def run_packed(self, packed, taus: np.ndarray, cfg: EngineConfig,
                   verification: bool,
                   real: Optional[int] = None,
                   ctx=None, rung: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """One blocking engine invocation over a packed bucket; numpy dict.

        Sugar for :meth:`run_packed_async` + :meth:`PendingBatch.result`::

            out = executor.run_packed(packed, taus, cfg, verification)
            out["ged"], out["exact"]        # per-row engine results
        """
        return self.run_packed_async(packed, taus, cfg, verification,
                                     real=real, ctx=ctx, rung=rung).result()

    def run_bucket(self, bucket: Bucket, taus: np.ndarray, cfg: EngineConfig,
                   verification: bool) -> Dict[str, np.ndarray]:
        """Run one plan bucket; ``taus`` is the plan-global per-pair array.

        Example::

            for bucket in plan.buckets:
                out = executor.run_bucket(bucket, taus, cfg, verification)
        """
        return self.run_packed(bucket.packed, bucket.pad_values(taus), cfg,
                               verification, real=bucket.real)

    def run_bucket_async(self, bucket: Bucket, taus: np.ndarray,
                         cfg: EngineConfig, verification: bool,
                         ctx=None, rung: Optional[int] = None
                         ) -> PendingBatch:
        """Async :meth:`run_bucket` with the robustness context threaded
        through — the entry point fault-aware backends use (the returned
        batch's ``flags`` record retries/degradation for outcome stats)."""
        return self.run_packed_async(bucket.packed, bucket.pad_values(taus),
                                     cfg, verification, real=bucket.real,
                                     ctx=ctx, rung=rung)

    # ------------------------------------------------------------ internal

    def _check_batch(self, packed) -> None:
        mult = self.batch_multiple
        if packed.batch % mult:
            raise ValueError(
                f"batch {packed.batch} is not a multiple of the executor's "
                f"{mult} shards; pack with batch_multiple={mult} "
                "(GedEngine does this automatically)")

    def _dispatch(self, packed, taus, cfg, verification):
        """Enqueue the device work; dict of un-materialised jax arrays.

        Each program is lowered and compiled ahead of its first call
        (:meth:`_compile`), so a refusal by the Pallas lowering or the XLA
        compiler surfaces as :class:`repro.ged.faults.CompileRefusal`,
        apart from the runtime faults the degradation ladder handles.
        JAX dispatches asynchronously: this returns as soon as the
        computation is queued, with every value still a ``jax.Array``
        future.
        """
        import jax.numpy as jnp
        args = engine_api.pair_tuple(packed) + (
            jnp.asarray(np.asarray(taus, dtype=np.float32)),)
        key = (cfg, bool(verification), packed.n_vlabels, packed.n_elabels,
               packed.slots, packed.batch)
        exe = self._executables.get(key)
        if exe is None:
            exe = self._compile(key[:4], args)
            self._executables[key] = exe
        return exe(*args)

    def _compile(self, static: tuple, args: tuple):
        """Lower and compile the engine program for ``args``' shapes."""
        from repro.ged.faults import CompileRefusal
        try:
            with span("ged.exec.compile", slots=args[0].shape[-1],
                      batch=args[0].shape[0]):
                return self._lower(static, args).compile()
        except Exception as exc:
            raise CompileRefusal(
                f"{self.name} executor: the engine program for "
                f"{tuple(a.shape for a in args)} was refused by the "
                f"compiler: {exc}") from exc

    def _lower(self, static: tuple, args: tuple):
        return engine_api._run_batch.lower(*args, *static)


class ShardedExecutor(Executor):
    """``shard_map`` the vmapped search over the mesh's batch axes.

    ``mesh`` defaults to a 1-D ``("data",)`` mesh over every local device;
    production meshes from :mod:`repro.launch.mesh` work as-is — the shard
    axes come from the ``"pairs"`` row of
    :func:`repro.parallel.sharding.default_rules` (``pod`` + ``data``),
    matching how the serving dry-run places pair batches.

    Any policy backend composes with it — ``GedEngine(backend="sharded")``
    is the vmap policy on this executor, ``GedEngine(backend="auto",
    mesh=...)`` the escalation policy.  Example::

        mesh = jax.make_mesh((8,), ("data",))
        eng = ged.GedEngine("sharded", mesh=mesh)   # batches padded to 8

    On a one-shard mesh (one local device) the ``shard_map`` wrapper and
    shard-multiple batch padding are pure overhead — there is nothing to
    partition — so dispatch falls through to the plain single-device path
    (``stats["single_device_fastpath"]`` counts those dispatches) and
    ``batch_multiple`` stays 1.  Outcomes are identical either way.

    >>> ShardedExecutor().batch_multiple >= 1      # local device count
    True
    """

    name = "sharded"

    def __init__(self, mesh=None, axes: Optional[Sequence[str]] = None):
        super().__init__()
        import jax
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        self.mesh = mesh
        if axes is None:
            from repro.parallel.sharding import pairs_axes
            axes = pairs_axes(mesh)
        self.axes = tuple(axes)
        self.stats["single_device_fastpath"] = 0

    @property
    def batch_multiple(self) -> int:
        from repro.parallel.sharding import default_rules
        return default_rules(self.mesh).mesh_size(self.axes)

    def _dispatch(self, packed, taus, cfg, verification):
        if self.batch_multiple == 1:
            self.stats["single_device_fastpath"] += 1
        return super()._dispatch(packed, taus, cfg, verification)

    def _lower(self, static: tuple, args: tuple):
        if self.batch_multiple == 1:
            # one shard = nothing to partition: the plain single-device
            # program, no shard_map wrapper (or its trace/lowering cost)
            return super()._lower(static, args)
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.parallel.ops import shard_map
        spec = P(self.axes)  # leading (batch) dim sharded, rest local

        def local_shard(qv, gv, qa, ga, order, n, t):
            return engine_api._run_batch(qv, gv, qa, ga, order, n, t,
                                         *static)

        fn = jax.jit(shard_map(local_shard, mesh=self.mesh,
                               in_specs=(spec,) * 7, out_specs=spec,
                               check=False))
        return fn.lower(*args)


# ----------------------------------------------------------- result unpack

def engine_outcome(out: Dict[str, np.ndarray], packed, bi: int,
                   verification: bool, tau: Optional[float], backend: str,
                   wall_s: float, rung: int) -> GedOutcome:
    """One :class:`GedOutcome` from row ``bi`` of an executor result dict.

    The unpack half of the executor contract — backends call it once per
    answered pair::

        out = executor.run_bucket(bucket, taus, cfg, verification)
        for bi, gi in enumerate(bucket.indices):
            results[gi] = engine_outcome(out, bucket.packed, bi,
                                         verification, tau, "jax",
                                         wall_s, rung=0)
    """
    certified = bool(out["exact"][bi])
    n = int(packed.n[bi])
    mapping = engine_mapping(packed.order[bi], out["best_img"][bi], n)
    stats = {"rung": rung,
             "iterations": float(out["iterations"][bi]),
             "expanded": float(out["expanded"][bi])}
    lb = float(out["lower_bound"][bi])
    if verification:
        similar = bool(out["similar"][bi])
        ub = float(out["upper_bound"][bi])
        return GedOutcome(
            ged=None, similar=similar, certified=certified,
            lower_bound=lb, upper_bound=ub if similar else float("inf"),
            mapping=mapping if similar else None,
            backend=backend, wall_s=wall_s, tau=tau, stats=stats)
    raw = float(out["ged"][bi])
    ged = float(np.rint(raw)) if certified else raw
    return GedOutcome(
        ged=ged, similar=None, certified=certified,
        lower_bound=min(lb, ged), upper_bound=ged,
        mapping=mapping, backend=backend, wall_s=wall_s, stats=stats)


# ------------------------------------------------------------ result cache

def graph_digest(g: Graph) -> bytes:
    """Canonical digest of one graph, independent of any batch label vocab.

    Hashes the concrete representation (raw int64 labels + adjacency), so
    equality means *identical* graphs — mappings in cached outcomes stay
    index-compatible — and the digest never changes with whichever other
    pairs happened to share a batch.

    >>> from repro.ged.plan import as_graph
    >>> g = as_graph(([0, 1], [(0, 1, 1)]))
    >>> len(graph_digest(g))
    16
    >>> graph_digest(g) == graph_digest(as_graph(([0, 1], [(0, 1, 1)])))
    True
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(np.int64(g.n).tobytes())
    h.update(np.ascontiguousarray(g.vlabels, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(g.adj, dtype=np.int64).tobytes())
    return h.digest()


def wl_digest(g: Graph, iters: int = 3) -> bytes:
    """Isomorphism-invariant digest: Weisfeiler-Leman color refinement.

    Vertex colors start from vertex labels and are refined ``iters`` times
    with the sorted multiset of ``(edge_label, neighbor_color)`` pairs; the
    digest hashes the *sorted* final color multiset plus an edge summary
    (sorted ``(color, color, edge_label)`` triples) and the graph sizes —
    every ingredient is permutation-invariant, so isomorphic graphs always
    collide, which is exactly what a graph-DB result cache wants.

    Caveat (why the exact digest stays the default for :func:`pair_key`):
    WL refinement is not a complete isomorphism test — WL-equivalent
    non-isomorphic graphs (a 6-cycle vs two triangles, regular graphs
    with uniform labels) share a digest.  A consumer must therefore
    either *confirm* a collision before trusting it
    (:class:`repro.ged.GraphStore` runs a certified GED == 0 check per
    candidate merge at ingest) or accept that an unconfirmed collision
    aliases two different pairs — ``GedEngine(digest="wl")`` is that
    opt-in trade: on WL-equivalent non-isomorphic pairs the cache can
    return the *other* pair's distance.  Cached mappings are dropped
    either way (index-valid only for the graph that produced them).

    >>> from repro.ged.plan import as_graph
    >>> g = as_graph(([0, 1, 2], [(0, 1, 1), (1, 2, 2)]))
    >>> p = as_graph(([2, 1, 0], [(1, 0, 2), (2, 1, 1)]))   # relabelled copy
    >>> wl_digest(g) == wl_digest(p)
    True
    >>> graph_digest(g) == graph_digest(p)
    False
    """
    def h8(*parts: bytes) -> bytes:
        hh = hashlib.blake2b(digest_size=8)
        for p in parts:
            hh.update(p)
        return hh.digest()

    adj = g.adj
    colors = [h8(np.int64(int(a)).tobytes()) for a in g.vlabels]
    for _ in range(iters):
        colors = [
            h8(colors[v], *(np.int64(int(adj[v, u])).tobytes() + colors[u]
                            for u in sorted(np.nonzero(adj[v])[0].tolist(),
                                            key=lambda u: (adj[v, u],
                                                           colors[u]))))
            for v in range(g.n)
        ]
    out = hashlib.blake2b(digest_size=16)
    out.update(np.int64(g.n).tobytes())
    out.update(np.int64(g.m).tobytes())
    for c in sorted(colors):
        out.update(c)
    ii, jj = np.nonzero(np.triu(adj, k=1))
    for t in sorted(
        h8(*sorted((colors[i], colors[j])),
           np.int64(int(adj[i, j])).tobytes())
        for i, j in zip(ii.tolist(), jj.tolist())
    ):
        out.update(t)
    return out.digest()


DIGESTS = {"exact": graph_digest, "wl": wl_digest}


# ------------------------------------------------------- sketch signatures

# Multiplicative uint32 hash constants (Knuth / murmur-style finalisers).
# The *same* wraparound arithmetic runs in numpy on the host (one query
# graph) and in jnp on device (the packed corpus), so signatures agree
# bit-for-bit whichever path produced them — CandidateIndex probes depend
# on that.
_H_VMUL = 2654435761        # vertex-label hash multiplier
_H_VADD = 0x9E3779B9
_H_EMUL = 0xC2B2AE35        # edge label inside the neighbor combine
_H_NMUL = 0x27D4EB2F        # per-neighbor contribution
_H_CMUL = 0x85EBCA6B        # self color between WL rounds
_H_CADD = 0x165667B1
_H_BMUL = 0x9E3779B1        # edge-label histogram bin
_H_BADD = 0x85EBCA77


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Shape of a WL-sketch signature (see :func:`wl_signature`).

    ``dims_v`` / ``dims_e`` are the hashed vertex- and edge-histogram
    widths; ``wl_iters`` rounds of Weisfeiler-Leman color refinement run
    before the vertex part is binned (0 = plain label histogram, the
    default — deeper sketches discriminate more but carry a larger
    admissible damage factor, see :func:`repro.ged.index.sketch_damage`).

    >>> SketchSpec().dims        # 64 vertex + 16 edge bins + (n, m)
    82
    """

    dims_v: int = 64
    dims_e: int = 16
    wl_iters: int = 0

    @property
    def dims(self) -> int:
        return self.dims_v + self.dims_e + 2


def wl_signature(g: Graph, spec: SketchSpec = SketchSpec()) -> np.ndarray:
    """Integer sketch of one graph: hashed WL-color histogram (``dims_v``
    bins) ⊕ hashed edge-label histogram (``dims_e`` bins) ⊕ ``(n, m)``.

    The sketch is built so one unit edit operation moves its L1 norm by a
    *bounded* amount (the damage factor — 2 at ``wl_iters=0``): a vertex
    relabel moves one unit between two vertex bins, an edge edit touches
    one edge bin plus the ``m`` entry, a vertex insert/delete one vertex
    bin plus ``n``.  Hashing labels into bins only ever *merges* histogram
    mass, which shrinks L1 — so ``ceil(L1 / damage)`` stays an admissible
    GED lower bound at any width.  Host path of the pair whose batched
    twin is :func:`batch_signatures`.

    >>> from repro.ged.plan import as_graph
    >>> s = wl_signature(as_graph(([0, 1], [(0, 1, 1)])))
    >>> int(s.sum() - s[-2] - s[-1]), int(s[-2]), int(s[-1])   # 2 vertices, 1 edge
    (3, 2, 1)
    """
    u32 = np.uint32
    c = np.asarray(g.vlabels, dtype=np.int64).astype(u32) * u32(_H_VMUL) \
        + u32(_H_VADD)
    adj = np.ascontiguousarray(g.adj, dtype=np.int64).astype(u32)
    present = g.adj > 0
    for _ in range(spec.wl_iters):
        h = (adj * u32(_H_EMUL) + c[None, :]) * u32(_H_NMUL)
        nsum = np.where(present, h, u32(0)).sum(axis=1, dtype=u32)
        c = c * u32(_H_CMUL) + nsum + u32(_H_CADD)
    sig = np.zeros(spec.dims, dtype=np.int32)
    sig[:spec.dims_v] = np.bincount(
        (c % u32(spec.dims_v)).astype(np.int64), minlength=spec.dims_v)
    iu, ju = np.nonzero(np.triu(g.adj, k=1))
    elabs = np.asarray(g.adj, dtype=np.int64)[iu, ju].astype(u32)
    ebin = ((elabs * u32(_H_BMUL) + u32(_H_BADD))
            % u32(spec.dims_e)).astype(np.int64)
    sig[spec.dims_v:spec.dims_v + spec.dims_e] = np.bincount(
        ebin, minlength=spec.dims_e)
    sig[-2] = g.n
    sig[-1] = g.m
    return sig


def _signature_fn(spec: SketchSpec, slots: int):
    """Pure-jnp single-graph signature over padded ``slots`` tensors,
    bit-identical to :func:`wl_signature` (same uint32 wraparound ops in
    the same order)."""
    import jax.numpy as jnp
    u32 = jnp.uint32

    def one(vlab, mask, adj):
        c = vlab.astype(u32) * u32(_H_VMUL) + u32(_H_VADD)
        present = adj > 0
        for _ in range(spec.wl_iters):
            h = (adj.astype(u32) * u32(_H_EMUL) + c[None, :]) * u32(_H_NMUL)
            nsum = jnp.sum(jnp.where(present, h, u32(0)), axis=1,
                           dtype=jnp.uint32)
            c = c * u32(_H_CMUL) + nsum + u32(_H_CADD)
        vbin = (c % u32(spec.dims_v)).astype(jnp.int32)
        vhist = jnp.zeros(spec.dims_v, jnp.int32).at[vbin].add(mask)
        tri = jnp.triu(jnp.ones((slots, slots), jnp.int32), k=1)
        w = present.astype(jnp.int32) * tri
        ebin = ((adj.astype(u32) * u32(_H_BMUL) + u32(_H_BADD))
                % u32(spec.dims_e)).astype(jnp.int32)
        ehist = jnp.zeros(spec.dims_e, jnp.int32) \
            .at[ebin.reshape(-1)].add(w.reshape(-1))
        return jnp.concatenate(
            [vhist, ehist, jnp.stack([jnp.sum(mask), jnp.sum(w)])])

    return one


def batch_signatures(graphs: Sequence[Graph],
                     spec: SketchSpec = SketchSpec(),
                     executor: Optional[Executor] = None,
                     fns: Optional[Dict[tuple, object]] = None,
                     chunk: int = 2048) -> np.ndarray:
    """:func:`wl_signature` for a whole corpus, batched on device.

    Graphs are grouped into power-of-two slot buckets (the planner's
    shapes, so compilations are shared with everything else at that
    width), packed into ``(batch, slots)`` label/mask and
    ``(batch, slots, slots)`` adjacency tensors in chunks of ``chunk``
    rows, and pushed through one vmapped jit per shape.  On a
    :class:`ShardedExecutor` the chunk's batch axis is ``shard_map``-ed
    over the executor's mesh axes — ingest-time signature builds ride
    whatever placement the store runs on.  ``fns`` is the caller's
    compiled-fn cache (keyed on shape), so repeated builds recompile
    nothing.  Returns ``(len(graphs), spec.dims)`` int32, row order =
    input order, bit-identical to the host path:

    >>> from repro.ged.plan import as_graph
    >>> g = as_graph(([0, 1, 0], [(0, 1, 1), (1, 2, 2)]))
    >>> bool((batch_signatures([g])[0] == wl_signature(g)).all())
    True
    """
    from repro.ged.plan import padded_batch, slot_bucket
    sigs = np.zeros((len(graphs), spec.dims), dtype=np.int32)
    if not len(graphs):
        return sigs
    import jax
    import jax.numpy as jnp
    executor = executor or Executor()
    fns = {} if fns is None else fns
    mult = executor.batch_multiple
    by_slots: Dict[int, list] = {}
    for i, g in enumerate(graphs):
        by_slots.setdefault(slot_bucket(g.n), []).append(i)
    for slots in sorted(by_slots):
        idxs = by_slots[slots]
        for lo in range(0, len(idxs), chunk):
            part = idxs[lo:lo + chunk]
            batch = padded_batch(len(part), mult)
            vlab = np.zeros((batch, slots), dtype=np.int32)
            mask = np.zeros((batch, slots), dtype=np.int32)
            adj = np.zeros((batch, slots, slots), dtype=np.int32)
            for r, gi in enumerate(part):
                g = graphs[gi]
                vlab[r, :g.n] = g.vlabels
                mask[r, :g.n] = 1
                adj[r, :g.n, :g.n] = g.adj
            key = (spec, slots, batch)
            fn = fns.get(key)
            if fn is None:
                one = _signature_fn(spec, slots)

                def batched(v, mk, a, _one=one):
                    return jax.vmap(_one)(v, mk, a)

                if isinstance(executor, ShardedExecutor) and mult > 1:
                    from jax.sharding import PartitionSpec as P

                    from repro.parallel.ops import shard_map
                    axes = executor.axes
                    fn = jax.jit(shard_map(
                        batched, mesh=executor.mesh,
                        in_specs=(P(axes),) * 3, out_specs=P(axes),
                        check=False))
                else:
                    fn = jax.jit(batched)
                fns[key] = fn
            out = np.asarray(fn(jnp.asarray(vlab), jnp.asarray(mask),
                                jnp.asarray(adj)))
            sigs[np.asarray(part, dtype=np.int64)] = out[:len(part)]
    return sigs


def pair_key_from_digests(dq: bytes, dg: bytes, verification: bool,
                          tau: Optional[float], cfg: EngineConfig,
                          backend: str, digest: str = "exact") -> tuple:
    """:func:`pair_key` when the graph digests are already in hand — the
    form :meth:`repro.ged.GedEngine.cached_distance` uses for pivot
    lookups over pre-digested corpus members (no re-hashing per probe)."""
    return (digest, dq, dg, bool(verification),
            None if tau is None else float(tau), cfg, backend)


def pair_key(q: Graph, g: Graph, verification: bool, tau: Optional[float],
             cfg: EngineConfig, backend: str, digest: str = "exact") -> tuple:
    """Cache key for one query: pair digests + mode (tau-aware) + config.

    The same pair in a different mode (or at a different tau) keys
    differently, so a verification answer can never shadow a computation:

    >>> from repro.ged.plan import as_graph
    >>> q = as_graph(([0], [])); g = as_graph(([1], []))
    >>> pair_key(q, g, True, 2.0, None, "jax") == \\
    ...     pair_key(q, g, False, None, None, "jax")
    False

    ``digest`` selects the graph-hash family: ``"exact"`` (default; equal
    keys mean byte-identical graphs, mappings stay index-compatible) or
    ``"wl"`` (:func:`wl_digest`; isomorphic duplicates share keys, raising
    hit rates on graph-DB workloads — cache copies drop their mappings):

    >>> p = as_graph(([1], []))                 # same graph, new object
    >>> pair_key(q, p, False, None, None, "jax", digest="wl") == \\
    ...     pair_key(q, g, False, None, None, "jax", digest="wl")
    True
    """
    fn = DIGESTS[digest]
    return pair_key_from_digests(fn(q), fn(g), verification, tau, cfg,
                                 backend, digest=digest)


def detached(outcome: GedOutcome, stats: Dict[str, float]) -> GedOutcome:
    """An independent copy of ``outcome`` — own stats dict, own mapping
    array — with ``stats`` swapped in.  Callers may mutate what they are
    handed without corrupting a cached entry (or a duplicate's answer).

    >>> from repro.ged.results import GedOutcome
    >>> a = GedOutcome(ged=1.0, similar=None, certified=True,
    ...                lower_bound=1.0, upper_bound=1.0, mapping=None,
    ...                backend="exact", wall_s=0.0, stats={"rung": 0})
    >>> b = detached(a, {**a.stats, "cached": True})
    >>> b.stats["cached"], "cached" in a.stats
    (True, False)
    """
    mapping = None if outcome.mapping is None else np.array(outcome.mapping)
    return dataclasses.replace(outcome, mapping=mapping, stats=stats)


class ResultCache:
    """LRU cache of :class:`GedOutcome` keyed by :func:`pair_key`.

    Sits in front of every executor (``GedEngine`` consults it before
    planning), so duplicate pairs — across calls or within one batch —
    never re-execute, whatever the backend.

    >>> from repro.ged.results import GedOutcome
    >>> cache = ResultCache(maxsize=2)
    >>> cache.get(("some", "key")) is None     # miss
    True
    >>> out = GedOutcome(ged=2.0, similar=None, certified=True,
    ...                  lower_bound=2.0, upper_bound=2.0, mapping=None,
    ...                  backend="jax", wall_s=0.01)
    >>> cache.put(("some", "key"), out)
    >>> hit = cache.get(("some", "key"))
    >>> hit.ged, hit.stats["cached"], (cache.hits, cache.misses)
    (2.0, True, (1, 1))
    """

    def __init__(self, maxsize: int = 4096):
        self.maxsize = int(maxsize)
        self._entries: "collections.OrderedDict[tuple, GedOutcome]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        # pivot-lookup traffic (CandidateIndex distance reuse) is counted
        # separately from query hits/misses: a pivot miss is expected and
        # must not skew the result-cache hit rate the serving layer reads.
        self.pivot_hits = 0
        self.pivot_misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def peek(self, key: tuple) -> Optional[GedOutcome]:
        """Read-only probe: no LRU bump, no hit/miss counting, and — unlike
        :meth:`get` — no detached copy.  Callers must treat the entry as
        frozen and may only read *scalars* off it (``ged``, ``certified``);
        in particular a peeked entry's ``mapping`` must never be handed
        out, because under WL digests the stored copy already dropped it
        and resurrecting one from a different orientation's entry would
        pair vertices of the wrong graph.  This is the lookup
        :meth:`repro.ged.GedEngine.cached_distance` builds pivot pruning
        on — thousands of probes per query, most missing, none of which
        should churn the LRU order."""
        return self._entries.get(key)

    def get(self, key: tuple) -> Optional[GedOutcome]:
        out = self._entries.get(key)
        if out is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        # wall_s stays the cost of the run that produced the entry
        return detached(out, {**out.stats, "cached": True})

    def put(self, key: tuple, outcome: GedOutcome) -> None:
        self._entries[key] = detached(outcome, dict(outcome.stats))
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
