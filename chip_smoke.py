#!/usr/bin/env python3
"""Smoke run of the certified GED service on a TPU, at AIDS corpus scale.

    python chip_smoke.py                # one chip: phases 1-3
    python chip_smoke.py --four-chips   # the corpus sharded over 4 chips

Phase 1 ingests a corpus the size of the AIDS antiviral screen (the corpus
of the GED similarity-search literature) through ``GedSimilarityService``
and answers range and top-k queries.  Phase 2 answers a few hundred
``GedVerificationService`` requests and runs ``GedEngine("auto").compute``
on a few dozen pairs.  Phase 3 repeats that computation with the Pallas
kernels on (``use_kernel=True``), which must reproduce phase 2 field for
field.  Every answer is checked against the plain host reference
(``repro.core.exact.search``), and a phase in which anything retried,
degraded, faulted or timed out fails the run.  ``--four-chips`` runs only
the corpus search on a 4-device mesh, against a one-device store in the
same process and against the host reference.

The run refuses anything but the chip: it exits non-zero, printing no
result, when JAX finds no TPU, when the Pallas kernels would run in
interpret mode, or when an environment variable that steers the engine
off its chip path is set.  The lines before the last report the run —
smoke timings, not metrics.  The last line is the verdict as JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))

# The AIDS antiviral-screen corpus: 42,687 molecules averaging ~25.6
# vertices, with 62 vertex labels and 3 edge labels.
AIDS_GRAPHS = 42_687
AIDS_MEAN_VERTICES = 25.6
# Vertex counts are capped at 64 (AIDS's tail reaches ~220 vertices), so
# every graph fits the engine's largest slot bucket used here.
MAX_VERTICES = 64
N_VLABELS, N_ELABELS = 62, 3

# Variables that would run a path that is not the chip's: the pure-jnp
# reference kernels, injected faults, and a kernel-dispatch table.
REFUSED_ENV = ("REPRO_DISABLE_PALLAS", "REPRO_GED_FAULT_INJECT",
               "REPRO_GED_AUTOTUNE_DIR")
# Counter names (any prefix) that mean a phase fell back or gave up.
FALLBACK_MARKS = ("degraded", "fault_", "retries", "timed_out")

RANGE_TAUS = (1, 2, 3, 4, 5)
TOPK_QUERIES, TOPK_K = 3, 10


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, missing or fallen-back answer."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------- data

def vertex_counts(rng, size: int, mean: float = AIDS_MEAN_VERTICES,
                  cap: int = MAX_VERTICES):
    """Seeded molecule sizes: gamma-distributed (shape 6) around ``mean``,
    rounded and clipped to ``[2, cap]``."""
    import numpy as np
    x = rng.gamma(6.0, mean / 6.0, size=size)
    return np.clip(np.rint(x), 2, cap).astype(int)


def make_corpus(n_graphs: int, seed: int, mean: float = AIDS_MEAN_VERTICES,
                cap: int = MAX_VERTICES):
    """An AIDS-like corpus of analog series.

    Each series is a base molecule (``aids_like_graph``) followed by
    analogs one edit away from it; series sizes are geometric (mean 4,
    capped at 32), since screening libraries hold congeneric series.
    Returns ``(graphs, base_of)`` with ``base_of[i]`` the id of graph
    ``i``'s series base.
    """
    import numpy as np

    from repro.data.graphs import aids_like_graph, perturb
    rng = np.random.default_rng(seed)
    sizes = vertex_counts(rng, n_graphs, mean, cap)
    graphs, base_of = [], []
    while len(graphs) < n_graphs:
        base_id = len(graphs)
        base = aids_like_graph(rng, int(sizes[base_id]), N_VLABELS,
                               N_ELABELS)
        series = min(int(rng.geometric(0.25)), 32, n_graphs - base_id)
        graphs.append(base)
        for _ in range(series - 1):
            graphs.append(perturb(rng, base, 1, N_VLABELS, N_ELABELS))
        base_of.extend([base_id] * series)
    return graphs, base_of


def make_queries(graphs, base_of, seed: int,
                 taus: Sequence[int] = RANGE_TAUS,
                 topk: int = TOPK_QUERIES, k: int = TOPK_K):
    """Range queries at each ``tau`` and ``topk`` top-``k`` queries, each
    a 1-4-edit perturbation of a corpus member (its *source*).

    A range query at ``tau`` makes ``min(tau, 4)`` edits, so its source is
    within ``tau``.  A top-k query perturbs (1-2 edits) the base of a
    series with more members than the store verifies as top-k seeds
    (``max(2k, 8)``), so its nearest neighbours are close analogs.
    Returns dicts with ``request`` (a ``SearchRequest``) and ``source``.
    """
    import numpy as np

    from repro.data.graphs import perturb
    from repro.serving.ged_service import SearchRequest
    rng = np.random.default_rng(seed + 1)
    out = []
    for tau in taus:
        src = int(rng.integers(len(graphs)))
        q = perturb(rng, graphs[src], min(int(tau), 4), N_VLABELS, N_ELABELS)
        out.append({"request": SearchRequest(q, tau=float(tau)),
                    "source": src})
    counts = np.bincount(np.asarray(base_of), minlength=len(graphs))
    seeds = max(2 * k, 8)
    big = np.flatnonzero(counts > seeds)
    _require(len(big) > 0, f"no analog series has more than {seeds} members")
    for _ in range(topk):
        src = int(rng.choice(big))
        q = perturb(rng, graphs[src], int(rng.integers(1, 3)), N_VLABELS,
                    N_ELABELS)
        out.append({"request": SearchRequest(q, k=k), "source": src})
    return out


def make_pairs(count: int, seed: int, n_min: int = 16, n_max: int = 24,
               max_edits: int = 6):
    """AIDS-like pairs of ``n_min``-``n_max`` vertices, 1-``max_edits``
    edits apart."""
    import numpy as np

    from repro.data.graphs import aids_like_graph, perturb
    rng = np.random.default_rng(seed + 2)
    pairs = []
    for _ in range(count):
        q = aids_like_graph(rng, int(rng.integers(n_min, n_max + 1)),
                            N_VLABELS, N_ELABELS)
        g = perturb(rng, q, int(rng.integers(1, max_edits + 1)), N_VLABELS,
                    N_ELABELS)
        pairs.append((q, g))
    return pairs


# ------------------------------------------------------------- reporting

class CompileLog:
    """Programs compiled and seconds spent tracing, lowering and compiling
    them, from JAX's monitoring events."""

    def __init__(self) -> None:
        import jax
        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.programs += 1

    def snapshot(self):
        from repro.ged.exec import persistent_cache_stats
        s = persistent_cache_stats()
        return (self.programs, self.seconds,
                s.get("persistent_cache_hits", 0.0),
                s.get("persistent_cache_misses", 0.0))


def report(phase: str, log: Optional[CompileLog], since, wall_s: float,
           **fields) -> None:
    """One ``smoke`` line: wall time, compile set-up, cache traffic, the
    device's peak memory and the phase's own fields."""
    import jax
    parts = [f"smoke {phase}:", f"wall_s={wall_s:.3f}"]
    if log is not None:
        p, s, h, m = log.snapshot()
        parts += [f"compile_s={s - since[1]:.3f}",
                  f"programs={p - since[0]}",
                  f"persistent_cache_hits={h - since[2]:g}",
                  f"persistent_cache_misses={m - since[3]:g}"]
    stats = jax.devices()[0].memory_stats() or {}
    parts.append(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    parts += [f"{k}={v}" for k, v in fields.items()]
    print(" ".join(parts), flush=True)


def check_no_fallback(phase: str, *stat_dicts: Dict[str, float]) -> None:
    """Fail unless every retry/degrade/fault/timeout counter is zero."""
    bad = {k: v for d in stat_dicts for k, v in d.items()
           if any(mark in k for mark in FALLBACK_MARKS) and v}
    _require(not bad, f"{phase}: the run fell back: {bad}")


# ------------------------------------------------------ reference checks

def _sample_ids(n: int, must: Sequence[int], size: int, rng) -> List[int]:
    import numpy as np
    rest = np.setdiff1d(np.arange(n), np.asarray(must, dtype=np.int64))
    extra = rng.choice(rest, size=max(0, min(size - len(must), len(rest))),
                       replace=False)
    return sorted(set(int(i) for i in must) | set(int(i) for i in extra))


def check_range(graphs, base_of, query, tau: float, source: int, hits,
                sample: int, rng) -> int:
    """Every hit within ``tau`` (its distance confirmed by the host
    solver), the planted source found, and every sampled corpus graph the
    reference puts within ``tau`` a hit.  Returns reference calls made."""
    from repro.core.exact.search import ged, ged_verify
    hit_ids = {h.graph_id for h in hits}
    _require(source in hit_ids, f"range tau={tau}: source {source} missed")
    calls = 0
    for h in hits:
        ref = ged(query, graphs[h.graph_id]).ged
        calls += 1
        _require(ref is not None and ref <= tau,
                 f"range tau={tau}: hit {h.graph_id} at distance {ref}")
        _require(h.outcome.certified
                 and h.outcome.lower_bound <= ref <= h.outcome.upper_bound,
                 f"range tau={tau}: hit {h.graph_id} bounds "
                 f"[{h.outcome.lower_bound}, {h.outcome.upper_bound}] "
                 f"miss {ref}")
        if h.ged is not None:
            _require(h.ged == ref, f"range tau={tau}: hit {h.graph_id} "
                                   f"ged {h.ged} != {ref}")
    series = [i for i, b in enumerate(base_of) if b == base_of[source]]
    for gid in _sample_ids(len(graphs), [source] + series, sample, rng):
        within = ged_verify(query, graphs[gid], tau).similar
        calls += 1
        _require(within == (gid in hit_ids),
                 f"range tau={tau}: graph {gid} within={within} but "
                 f"hit={gid in hit_ids}")
    return calls


def check_topk(graphs, base_of, query, k: int, source: int, hits,
               sample: int, rng) -> int:
    """The hits are the ``k`` nearest by (distance, id): each distance
    equals the host solver's, the planted source is found unless ``k``
    graphs are at least as close, and no sampled graph outside the hits
    is closer than the ``k``-th."""
    from repro.core.exact.search import ged, ged_verify
    _require(len(hits) == k, f"top-k: {len(hits)} hits, want {k}")
    calls = 0
    for h in hits:
        ref = ged(query, graphs[h.graph_id]).ged
        calls += 1
        _require(h.outcome.certified and h.ged == ref,
                 f"top-k: hit {h.graph_id} ged {h.ged} != {ref}")
    order = [(h.ged, h.graph_id) for h in hits]
    _require(order == sorted(order), f"top-k: hits out of order {order}")
    kth = hits[-1].ged
    hit_ids = {h.graph_id for h in hits}
    last_tie = max(gid for d, gid in order if d == kth)
    series = [i for i, b in enumerate(base_of) if b == base_of[source]]
    for gid in _sample_ids(len(graphs), [source] + series, sample, rng):
        if gid in hit_ids:
            continue
        calls += 1
        _require(not ged_verify(query, graphs[gid], kth - 0.5).similar,
                 f"top-k: graph {gid} is closer than the k-th hit ({kth})")
        if ged_verify(query, graphs[gid], kth).similar:
            calls += 1
            _require(gid > last_tie, f"top-k: graph {gid} ties the k-th "
                                     f"hit ({kth}) with a smaller id")
    return calls


# ---------------------------------------------------------------- phases

def phase_search(graphs, base_of, queries, seed: int, sample: int = 200,
                 log: Optional[CompileLog] = None,
                 name: str = "phase1 search"):
    """Ingest ``graphs`` through ``GedSimilarityService`` and answer
    ``queries``; check every answer against the host reference.  Returns
    ``(service, answers)``."""
    import numpy as np

    from repro.serving.ged_service import GedSimilarityService
    since = log.snapshot() if log is not None else None
    t0 = time.perf_counter()
    svc = GedSimilarityService(graphs, index="auto")
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    answers = svc.search([q["request"] for q in queries])
    search_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 3)
    calls = 0
    for q, hits in zip(queries, answers):
        r = q["request"]
        if r.tau is not None:
            calls += check_range(graphs, base_of, r.query, r.tau,
                                 q["source"], hits, sample, rng)
        else:
            calls += check_topk(graphs, base_of, r.query, r.k, q["source"],
                                hits, sample, rng)
    ref_s = time.perf_counter() - t0
    check_no_fallback(name, svc.stats, svc.health())
    store = svc.store
    feature_bytes = sum(
        a.nbytes for b in store._index.buckets
        for a in (b.features.vhist, b.features.ehist, b.features.degs,
                  b.features.n, b.features.m))
    report(name, log, since, ingest_s + search_s, ingest_s=f"{ingest_s:.3f}",
           search_s=f"{search_s:.3f}", reference_s=f"{ref_s:.3f}",
           graphs=len(graphs),
           corpus_bytes=sum(g.adj.nbytes + g.vlabels.nbytes for g in graphs),
           stage0_feature_bytes=feature_bytes, queries=len(queries),
           hits=sum(len(h) for h in answers), reference_calls=calls,
           stage2_verified=store.stats["stage2_verified"],
           filter_ratio=f"{store.stats['filter_ratio']:.6f}")
    return svc, answers


def phase_verify(requests, pairs, log: Optional[CompileLog] = None):
    """``GedVerificationService.verify`` on ``requests`` and
    ``GedEngine("auto").compute`` on ``pairs``, both checked against the
    host reference.  Returns the compute outcomes."""
    from repro import ged
    from repro.core.exact.search import ged as host_ged
    from repro.core.exact.search import ged_verify
    from repro.serving.ged_service import GedVerificationService
    since = log.snapshot() if log is not None else None
    t0 = time.perf_counter()
    svc = GedVerificationService()
    verdicts = svc.verify(requests)
    verify_s = time.perf_counter() - t0
    for i, (r, o) in enumerate(zip(requests, verdicts)):
        ref = ged_verify(r.q, r.g, r.tau).similar
        _require(o.certified and o.similar == ref,
                 f"verify request {i}: similar={o.similar} "
                 f"(certified={o.certified}), reference {ref}")
    t1 = time.perf_counter()
    eng = ged.GedEngine("auto")
    outs = eng.compute(pairs)
    compute_s = time.perf_counter() - t1
    for i, ((q, g), o) in enumerate(zip(pairs, outs)):
        ref = host_ged(q, g).ged
        _require(o.certified and o.ged == ref,
                 f"compute pair {i}: ged={o.ged} (certified={o.certified}),"
                 f" reference {ref}")
    check_no_fallback("phase2 verify", svc.stats, svc.health(), eng.stats)
    report("phase2 verify", log, since, verify_s + compute_s,
           verify_s=f"{verify_s:.3f}", compute_s=f"{compute_s:.3f}",
           requests=len(requests),
           accepted=sum(bool(o.similar) for o in verdicts),
           pairs=len(pairs),
           host_solved=eng.stats.get("host_solved", 0))
    return outs


def phase_kernels(pairs, unfused, log: Optional[CompileLog] = None):
    """Phase 2's computation with every Pallas kernel on — LSa, BMa, the
    auction's top-2 and the frontier merge ranks: the outcomes must equal
    the unfused ones field for field."""
    from repro import ged
    from repro.core.engine.search import EngineConfig
    from repro.kernels.autotune import KernelDispatch
    since = log.snapshot() if log is not None else None
    t0 = time.perf_counter()
    eng = ged.GedEngine("auto", config=EngineConfig(
        use_kernel=True, dispatch=KernelDispatch(
            lsa_fused=True, bma_fused=True, merge_fused=True)))
    outs = eng.compute(pairs)
    wall = time.perf_counter() - t0
    fields = ("ged", "lower_bound", "upper_bound", "certified")
    for i, (a, b) in enumerate(zip(unfused, outs)):
        got = tuple(getattr(b, f) for f in fields)
        want = tuple(getattr(a, f) for f in fields)
        _require(got == want, f"kernel pair {i}: {got} != unfused {want}")
    check_no_fallback("phase3 kernels", eng.stats)
    report("phase3 kernels", log, since, wall, pairs=len(pairs),
           use_kernel=eng.config.use_kernel)
    return outs


def phase_four_chips(graphs, base_of, queries, seed: int, sample: int,
                     mesh, log: Optional[CompileLog] = None):
    """The corpus search sharded over ``mesh``.

    The corpus is ingested once, on one device, and its answers checked
    against the host reference (:func:`phase_search`); the store is then
    saved and reopened through ``GedSimilarityService(store_dir=...,
    mesh=mesh)``, which restores the ingested corpus without repeating
    the host-side ingest.  The sharded hits must equal the one-device
    hits; the stage-0 scan output and the engine batches must be sharded
    over every device of the mesh, and no dispatch may take the
    single-device path.
    """
    import tempfile

    import numpy as np

    from repro.core.engine.corpus import graph_features
    from repro.ged.plan import build_plan, slot_bucket
    from repro.serving.ged_service import GedSimilarityService
    n_dev = mesh.devices.size
    one, want = phase_search(graphs, base_of, queries, seed, sample,
                             log=log, name="four-chips reference "
                                            "(one device)")
    since = log.snapshot() if log is not None else None
    with tempfile.TemporaryDirectory() as store_dir:
        one.store.save(store_dir)
        t0 = time.perf_counter()
        svc = GedSimilarityService(store_dir=store_dir, mesh=mesh)
        open_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = svc.search([q["request"] for q in queries])
        search_s = time.perf_counter() - t0
    for qi, (a, b) in enumerate(zip(got, want)):
        sharded = [(h.graph_id, h.ged, h.outcome.similar) for h in a]
        single = [(h.graph_id, h.ged, h.outcome.similar) for h in b]
        _require(sharded == single, f"query {qi}: sharded hits {sharded} "
                                    f"!= one-device hits {single}")
    check_no_fallback(f"four-chips search ({n_dev} devices)", svc.stats,
                      svc.health())
    store = svc.store
    _require(store.stats["engine_executor_single_device_fastpath"] == 0,
             "a sharded dispatch took the single-device path")
    q = queries[0]["request"].query
    b = store._index.buckets[-1]
    width = max(b.slots, slot_bucket(q.n))
    qf = graph_features([q], store._index.vocab, width=width)
    scan = store._index._dispatch(qf, b.features, b.slots, width)
    _require(len(scan.sharding.device_set) == n_dev,
             f"stage-0 scan output on {len(scan.sharding.device_set)} "
             f"devices, want {n_dev}")
    pairs = [(q, graphs[i]) for i in range(n_dev)]
    bucket = build_plan(pairs, batch_multiple=n_dev).buckets[0]
    arrays = store.executor._dispatch(
        bucket.packed, bucket.pad_values(np.ones(len(pairs), np.float32)),
        store.engine.config, True)
    _require(len(arrays["similar"].sharding.device_set) == n_dev,
             "engine batch outputs are not sharded over the mesh")
    report(f"four-chips search ({n_dev} devices)", log, since,
           open_s + search_s, open_s=f"{open_s:.3f}",
           search_s=f"{search_s:.3f}", queries=len(queries),
           hits=sum(len(h) for h in got), hits_equal_one_device=True,
           single_device_fastpath=0, sharded_outputs=n_dev)


# ------------------------------------------------------------------ main

def _chip_or_exit(want: int):
    """The devices, or exit non-zero: a TPU, compiled (not interpreted)
    kernels, and ``want`` devices."""
    import jax

    from repro.kernels.ops import pallas_interpret
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this run needs the chip")
    if pallas_interpret():
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")
    if len(devices) < want:
        sys.exit(f"chip_smoke: {want} devices needed, JAX found "
                 f"{len(devices)}")
    return devices


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=20171)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the corpus search sharded over 4 chips")
    args = ap.parse_args(argv)
    refused = [v for v in REFUSED_ENV if os.environ.get(v)]
    if refused:
        sys.exit(f"chip_smoke: refusing to run with {', '.join(refused)} "
                 "set: it steers the engine off its chip path")
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: the repro package is not at {src}")
    sys.path.insert(0, src)

    import jax

    from repro.ged.exec import enable_compile_cache
    devices = _chip_or_exit(4 if args.four_chips else 1)
    cache_dir = enable_compile_cache()
    log = CompileLog()
    print(f"smoke device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind!r} count={len(devices)} "
          f"compile_cache={cache_dir}", flush=True)

    t0 = time.perf_counter()
    graphs, base_of = make_corpus(AIDS_GRAPHS, args.seed)
    sizes = [g.n for g in graphs]
    print(f"smoke assumptions: corpus={len(graphs)} graphs in "
          f"{len(set(base_of))} analog series (geometric sizes, mean 4, "
          f"cap 32; analogs one edit from their base), vertices gamma(6) "
          f"around {AIDS_MEAN_VERTICES} clipped to [2, {MAX_VERTICES}] "
          f"(realised mean {sum(sizes) / len(sizes):.2f}, max {max(sizes)}), "
          f"edges {sum(g.m for g in graphs) / len(graphs):.2f} per graph, "
          f"{N_VLABELS} vertex / {N_ELABELS} edge labels; "
          f"generated in {time.perf_counter() - t0:.3f}s", flush=True)
    queries = make_queries(graphs, base_of, args.seed)

    if args.four_chips:
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        phase_four_chips(graphs, base_of, queries, args.seed, 200, mesh,
                         log)
        count = 4
    else:
        phase_search(graphs, base_of, queries, args.seed, 200, log=log)
        from repro.serving.ged_service import GedRequest
        import numpy as np
        rng = np.random.default_rng(args.seed + 4)
        requests = [GedRequest(q, g, tau=float(rng.integers(1, 6)))
                    for q, g in make_pairs(256, args.seed + 5)]
        pairs = make_pairs(32, args.seed + 6)
        unfused = phase_verify(requests, pairs, log)
        phase_kernels(pairs, unfused, log)
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
