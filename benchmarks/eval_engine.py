"""Batched JAX engine benchmarks: agreement with the exact reference,
throughput, strategy/bound ablations, and Pallas-kernel validation.

This is the beyond-paper half of the harness: the paper's AStar+ is a
sequential heap algorithm; the engine runs thousands of pairs in lockstep
on one device (and data-parallel across the mesh at scale — see the
``ged-verify`` dry-run rows).  Everything here goes through the public
``repro.ged`` facade — the same door serving traffic uses.
"""

from __future__ import annotations

import itertools
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import (groups, print_table, record, record_section,
                               timed, timed_best)
from repro.core.exact.search import ged as exact_ged
from repro.ged import GedEngine


def _flat_pairs(gs, max_pairs=60):
    pairs = list(itertools.chain.from_iterable(gs.values()))
    return pairs[:max_pairs]


def _engine(**overrides) -> GedEngine:
    # cache=False: benchmarks re-run identical pair sets to measure
    # steady-state throughput — the result cache would answer the repeat
    # from memory and time nothing.
    opts = dict(slots=16, pool=512, expand=8, max_iters=512,
                bound="hybrid", strategy="astar", cache=False)
    opts.update(overrides)
    return GedEngine(opts.pop("backend", "jax"), **opts)


def _mean_stat(outs, key) -> float:
    return float(np.mean([o.stats[key] for o in outs]))


def engine_agreement_and_throughput(quick=True) -> List[Dict]:
    """Certified-exact agreement with the reference + pairs/s."""
    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs)
    truth = [exact_ged(q, g, bound="BMa").ged for q, g in pairs]

    rows = []
    for strategy in ("astar", "dfs"):
        eng = _engine(strategy=strategy)
        outs, dt_warm = timed(eng.compute, pairs)          # includes compile
        outs, dt = timed(eng.compute, pairs)               # steady state
        certified = np.array([o.certified for o in outs])
        agree = [int(round(o.ged)) == t
                 for o, t in zip(outs, truth) if o.certified]
        rows.append({
            "strategy": strategy,
            "pairs": len(pairs),
            "certified_frac": float(np.mean(certified)),
            "agree_frac_of_certified": float(np.mean(agree)) if agree else 0.0,
            "pairs_per_s": len(pairs) / dt,
            "compile_s": dt_warm - dt,
            "mean_iters": _mean_stat(outs, "iterations"),
        })
        assert all(agree), "certified engine answers must match the oracle"
    print_table("Engine vs exact (computation)", rows,
                ["strategy", "pairs", "certified_frac",
                 "agree_frac_of_certified", "pairs_per_s", "mean_iters"])
    record("engine_agreement", rows)
    return rows


def engine_verification(quick=True) -> List[Dict]:
    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs)
    truth = [exact_ged(q, g, bound="BMa").ged for q, g in pairs]
    rows = []
    for tau in (3.0, 6.0, 9.0):
        eng = _engine()
        outs, _ = timed(eng.verify, pairs, tau)
        outs, dt = timed(eng.verify, pairs, tau)
        cert = np.array([o.certified for o in outs])
        ok = [o.similar == (t <= tau)
              for o, t in zip(outs, truth) if o.certified]
        rows.append({"tau": tau, "pairs_per_s": len(pairs) / dt,
                     "certified_frac": float(np.mean(cert)),
                     "agree": float(np.mean(ok)) if ok else 0.0,
                     "mean_iters": _mean_stat(outs, "iterations")})
        assert all(ok)
    print_table("Engine verification (vary tau)", rows,
                ["tau", "pairs_per_s", "certified_frac", "agree",
                 "mean_iters"])
    record("engine_verification", rows)
    return rows


def engine_bound_ablation(quick=True) -> List[Dict]:
    """LSa vs BMa-dual vs hybrid inside the batched engine: iterations =
    the tensor analogue of the paper's search-space metric."""
    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs, max_pairs=36)
    rows = []
    for bound in ("lsa", "bma", "hybrid"):
        eng = _engine(bound=bound)
        outs, _ = timed(eng.compute, pairs)
        outs, dt = timed(eng.compute, pairs)
        rows.append({"bound": bound,
                     "mean_iters": _mean_stat(outs, "iterations"),
                     "mean_expanded": _mean_stat(outs, "expanded"),
                     "pairs_per_s": len(pairs) / dt,
                     "certified_frac":
                         float(np.mean([o.certified for o in outs]))})
    by = {r["bound"]: r["mean_expanded"] for r in rows}
    assert by["hybrid"] <= by["lsa"] * 1.05, \
        "tighter bound must not expand more states"
    print_table("Engine bound ablation", rows,
                ["bound", "mean_iters", "mean_expanded", "pairs_per_s",
                 "certified_frac"])
    record("engine_bounds", rows)
    return rows


def engine_sweeps_ablation(quick=True) -> List[Dict]:
    """Auction sweeps: the bound-tightness dial.

    Finding (recorded in EXPERIMENTS.md §Perf as a refuted hypothesis):
    MORE sweeps does NOT monotonically shrink the search on paper-scale
    graphs — higher post-auction prices degrade the greedy-primal
    *incumbent* faster than the dual bound tightens, and the incumbent
    dominates pruning at these sizes.  What IS guaranteed (weak duality)
    and asserted here: every certified answer stays exact at any sweep
    count, and answers agree across sweep counts.
    """
    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs, max_pairs=36)
    truth = [exact_ged(q, g, bound="BMa").ged for q, g in pairs]
    rows = []
    for sweeps in (2, 6, 12):
        eng = _engine(bound="bma", sweeps=sweeps)
        outs, _ = timed(eng.compute, pairs)
        outs, dt = timed(eng.compute, pairs)
        agree = [int(round(o.ged)) == t
                 for o, t in zip(outs, truth) if o.certified]
        assert all(agree), f"sweeps={sweeps}: certified answer wrong"
        rows.append({"sweeps": sweeps,
                     "mean_expanded": _mean_stat(outs, "expanded"),
                     "pairs_per_s": len(pairs) / dt,
                     "certified_frac":
                         float(np.mean([o.certified for o in outs]))})
    print_table("Engine auction-sweeps ablation (admissible at every "
                "sweep count)", rows,
                ["sweeps", "mean_expanded", "pairs_per_s",
                 "certified_frac"])
    record("engine_sweeps", rows)
    return rows


def kernel_validation(quick=True) -> List[Dict]:
    """Pallas kernels vs pure-jnp oracles (interpret mode on CPU)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.bma_cost_matrix import bma_cost_matrix_pallas
    from repro.kernels.reduced_top2 import reduced_top2_pallas

    rng = np.random.default_rng(3)
    rows = []
    shapes = [(2, 8, 4), (3, 16, 6)] if quick else \
        [(2, 8, 4), (3, 16, 6), (2, 32, 8), (1, 64, 8)]
    for (b, n, le) in shapes:
        qv = jnp.asarray(rng.integers(0, 5, (b, n)), jnp.int32)
        gv = jnp.asarray(rng.integers(0, 5, (b, n)), jnp.int32)
        iq = jnp.asarray(rng.integers(0, 3, (b, n, le)), jnp.float32)
        ig = jnp.asarray(rng.integers(0, 3, (b, n, le)), jnp.float32)
        qa = jnp.asarray(rng.integers(0, 3, (b, n, n)), jnp.int32)
        gc = jnp.asarray(rng.integers(0, 3, (b, n, n)), jnp.int32)
        pa = jnp.asarray(rng.random((b, n)) < 0.3, jnp.float32)
        t0 = time.perf_counter()
        out_k = bma_cost_matrix_pallas(qv, gv, iq, ig, qa, gc, pa,
                                       interpret=True)
        dt_k = time.perf_counter() - t0
        out_r = ref.bma_cost_matrix_ref(qv, gv, iq, ig, qa, gc, pa)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=1e-5)
        cost = jnp.asarray(rng.random((b, n, n)), jnp.float32)
        prices = jnp.asarray(rng.random((b, n)), jnp.float32)
        m1, a1, m2 = reduced_top2_pallas(cost, prices, interpret=True)
        r1, ra, r2 = ref.reduced_top2_ref(cost, prices)
        np.testing.assert_allclose(np.asarray(m1), np.asarray(r1), atol=1e-6)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(r2), atol=1e-6)
        from repro.kernels.lsa_children import lsa_children_pallas
        lsa_args = [
            jnp.asarray(rng.integers(0, 9, (b, n)) * 0.5, jnp.float32),
            jnp.asarray(rng.integers(0, 2, (b, n)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, n, le)), jnp.float32),
            jnp.asarray(rng.integers(0, le + 1, (b, n, n)), jnp.int32),
            jnp.asarray(rng.integers(0, le + 1, (b, n)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (b, n)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, n, le)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, n, le)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, n)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, n)), jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, le)) * 0.5, jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, le)) * 0.5, jnp.float32),
            jnp.asarray(rng.integers(0, 4, (b, le)), jnp.float32),
        ]
        out_l = lsa_children_pallas(*lsa_args, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out_l), np.asarray(ref.lsa_children_ref(*lsa_args)))
        rows.append({"B": b, "N": n, "Le": le, "allclose": True,
                     "interpret_s": dt_k})
    print_table("Pallas kernels vs oracle (interpret mode)", rows,
                ["B", "N", "Le", "allclose", "interpret_s"])
    record("kernel_validation", rows)
    return rows


def engine_backend_throughput(quick=True) -> List[Dict]:
    """Single-device vs mesh-sharded executor throughput.

    Emits the ``backend_throughput`` section of
    ``results/bench/BENCH_engine.json`` — the perf-trajectory record the
    ROADMAP's scaling work is judged against.  On one CPU device the
    sharded path should roughly match ``jax`` (same compute + shard_map
    overhead); the row captures the device count so multi-chip runs are
    comparable.
    """
    import jax

    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs)
    rows = []
    for backend in ("jax", "sharded"):
        eng = _engine(backend=backend)
        outs, dt_warm = timed(eng.compute, pairs)          # includes compile
        outs, dt = timed_best(eng.compute, pairs)          # steady state
        rows.append({
            "backend": backend,
            "devices": jax.device_count(),
            "batch_multiple": int(eng.batch_multiple),
            "pairs": len(pairs),
            "pairs_per_s": len(pairs) / dt,
            "compile_s": dt_warm - dt,
            "certified_frac":
                float(np.mean([o.certified for o in outs])),
            "mean_wall_s": float(np.mean([o.wall_s for o in outs])),
        })
    a, b = (r["pairs_per_s"] for r in rows)
    assert min(a, b) > 0
    print_table("Engine backend throughput (single-device vs sharded)",
                rows, ["backend", "devices", "batch_multiple", "pairs",
                       "pairs_per_s", "compile_s", "certified_frac"])
    record_section("BENCH_engine", "backend_throughput", rows)
    return rows


def engine_escalation_overlap(quick=True) -> List[Dict]:
    """Sequential vs overlapped rung execution in the ``auto`` pipeline.

    A small first rung forces real escalation (and a host-solver tail),
    which is where overlap pays: while one batch is in flight the
    scheduler drains decided pairs, re-buckets survivors, and host-solves
    final-rung pairs behind the device work.  Outcomes must be identical
    in both modes; only the wall-clock differs.  The comparison lands in
    the ``escalation_overlap`` section of
    ``results/bench/BENCH_engine.json``.
    """
    import jax

    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs, max_pairs=36)

    def make(overlap: bool) -> GedEngine:
        eng = _engine(backend="auto", batch_size=8, overlap=overlap,
                      max_in_flight=4)
        # shrink the ladder so rung 0 leaves survivors and the host rung
        # actually engages on paper-scale pairs
        eng._backend.scheduler.rungs = ((8, 1, 4), (256, 4, 128))
        return eng

    rows, outcomes = [], {}
    for mode in ("sequential", "overlapped"):
        overlap = mode == "overlapped"
        make(overlap).compute(pairs)                   # compile warm-up
        eng = make(overlap)
        outs, dt = timed(eng.compute, pairs)
        outcomes[mode] = [(o.ged, o.certified) for o in outs]
        s = eng.stats
        rows.append({
            "mode": mode,
            "devices": jax.device_count(),
            "pairs": len(pairs),
            "pairs_per_s": len(pairs) / dt,
            "wall_s": dt,
            "escalated": s["escalated"],
            "host_solved": s["host_solved"],
            "dispatches": s["dispatches"],
            "certified_frac": float(np.mean([o.certified for o in outs])),
        })
    assert outcomes["sequential"] == outcomes["overlapped"], \
        "overlapped rung execution changed an answer"
    assert all(c for _, c in outcomes["overlapped"]), \
        "auto must certify every answer"
    print_table("Auto escalation: sequential vs overlapped rungs", rows,
                ["mode", "pairs", "pairs_per_s", "wall_s", "escalated",
                 "host_solved", "certified_frac"])
    record_section("BENCH_engine", "escalation_overlap", rows)
    return rows


def engine_similarity_search(quick=True) -> List[Dict]:
    """Corpus similarity search through ``ged.GraphStore``: the paper's
    filter-verify workload end to end.

    An AIDS-like molecule corpus (with planted near-duplicates of each
    query) is ingested once; ranged queries then run the staged pipeline
    — stage-0 resident-corpus scan, stage-1 anchor-aware engine bounds,
    stage-2 certified verification.  The row records the filter ratio,
    the per-stage candidate counts, queries/s, and the scan-vs-verify
    wall split; it lands in the ``similarity_search`` section of
    ``results/bench/BENCH_engine.json``.  ``cache=False`` keeps repeat
    timings honest (the store's result cache would answer the second
    pass from memory).
    """
    import jax

    from repro.data.graphs import aids_like_graph, perturb
    from repro.ged import GraphStore

    rng = np.random.default_rng(12)
    corpus_size = 120 if quick else 240
    n_queries = 4 if quick else 8
    tau = 4.0
    corpus = [aids_like_graph(rng, int(rng.integers(8, 15)))
              for _ in range(corpus_size)]
    queries = [corpus[int(rng.integers(0, corpus_size))]
               for _ in range(n_queries)]
    for query in queries:                      # planted near-duplicates
        for _ in range(3):
            corpus.append(perturb(rng, query, int(rng.integers(1, 4)),
                                  n_vlabels=62, n_elabels=3))

    def make() -> GraphStore:
        return GraphStore(corpus, batch_size=32, pool=512, expand=8,
                          max_iters=512, cache=False)

    make().search_batch(queries, tau)          # compile warm-up
    store = make()
    _, dt = timed(store.search_batch, queries, tau)
    s = store.stats
    row = {
        "devices": jax.device_count(),
        "corpus": len(corpus),
        "queries": len(queries),
        "tau": tau,
        "candidates": s["candidates"],
        "stage0_pruned": s["stage0_pruned"],
        "stage1_decided": s["stage1_decided"],
        "stage2_verified": s["stage2_verified"],
        "filter_ratio": s["filter_ratio"],
        "hits": s["hits"],
        "queries_per_s": len(queries) / dt,
        "scan_wall_s": s["scan_wall_s"] + s["bound_wall_s"],
        "verify_wall_s": s["verify_wall_s"],
        "wall_s": dt,
    }
    assert s["index_pruned"] + s["stage0_pruned"] > 0.5 * s["candidates"], \
        "the cheap stages must prune most of the corpus"
    assert row["hits"] >= len(queries), "planted duplicates must be found"
    print_table("Corpus similarity search (filter-verify pipeline)", [row],
                ["corpus", "queries", "tau", "candidates", "stage0_pruned",
                 "stage1_decided", "stage2_verified", "filter_ratio",
                 "hits", "queries_per_s", "scan_wall_s", "verify_wall_s"])
    record_section("BENCH_engine", "similarity_search", [row])
    # the corpus-size sweep for the stage −1 candidate index rides along:
    # it emits its own ``candidate_index`` section (and, in full mode,
    # validates the >=100k-corpus selectivity acceptance bar)
    engine_candidate_index(quick=quick)
    return [row]


def engine_candidate_index(quick=True) -> List[Dict]:
    """Corpus-size sweep for the stage −1 ``CandidateIndex``.

    For each corpus size an AIDS-like database (with planted
    near-duplicates of every query) is ingested twice — once with
    ``index=None`` (the previous full-scan pipeline, which doubles as the
    recall oracle) and once with the banded WL-sketch index — and the
    same ranged queries run through both.  Each row records the ingest
    wall, the stage funnel, ``examined_frac`` (the corpus fraction stage
    −1 leaves for the linear stages — smaller is better, and
    ``tools/bench_diff.py`` flags it when it rises), measured recall
    against the oracle, and steady-state queries/s; rows land in the
    ``candidate_index`` section of ``results/bench/BENCH_engine.json``.

    In full mode the sweep reaches a >=100k-graph corpus and enforces
    the acceptance bar: exact mode examines <=10% of the database per
    query at *zero* recall loss.  A probabilistic row (``recall=0.9``)
    shows the explicit exactness opt-out at the smallest size.
    ``digest="exact"`` keeps ingest about hashing, not WL dedup probes;
    ``cache=False`` keeps the repeat timings honest.
    """
    import jax

    from repro.data.graphs import aids_like_graph, perturb
    from repro.ged import GraphStore

    sizes = [1_500] if quick else [20_000, 100_000]
    tau, n_queries = 2.0, 3
    opts = dict(batch_size=32, pool=512, expand=8, max_iters=512,
                cache=False, digest="exact")
    rows = []
    for size in sizes:
        rng = np.random.default_rng(13)
        corpus = [aids_like_graph(rng, int(rng.integers(8, 15)))
                  for _ in range(size)]
        queries = [corpus[int(rng.integers(0, size))]
                   for _ in range(n_queries)]
        for query in queries:              # planted near-duplicates
            for _ in range(2):
                corpus.append(perturb(rng, query, int(rng.integers(1, 3)),
                                      n_vlabels=62, n_elabels=3))
        flat = GraphStore(corpus, index=None, **opts)
        truth = [sorted(h.graph_id for h in flat.range_search(q, tau))
                 for q in queries]
        assert all(truth), "every query must have planted hits"

        modes = [("exact", "auto")]
        if size == sizes[0]:
            modes.append(("recall90", {"recall": 0.9}))
        for mode, index in modes:
            store, ingest_s = timed(GraphStore, corpus, index=index, **opts)
            per_q, _ = timed(store.search_batch, queries, tau)  # + compile
            s = dict(store.stats)          # funnel of exactly one pass
            got = [sorted(h.graph_id for h in qhits) for qhits in per_q]
            want = sum(len(t) for t in truth)
            found = sum(len(set(g) & set(t)) for g, t in zip(got, truth))
            _, dt = timed_best(store.search_batch, queries, tau)
            examined = (s["candidates"] - s["index_pruned"]) \
                / max(s["candidates"], 1)
            row = {
                "case": f"{mode}/{len(corpus)}",
                "mode": mode,
                "devices": jax.device_count(),
                "corpus": len(corpus),
                "queries": n_queries,
                "tau": tau,
                "ingest_s": ingest_s,
                "examined_frac": examined,
                "index_pruned": s["index_pruned"],
                "stage0_pruned": s["stage0_pruned"],
                "stage1_decided": s["stage1_decided"],
                "stage2_verified": s["stage2_verified"],
                "hits": s["hits"],
                "recall": found / want,
                "queries_per_s": n_queries / dt,
                "index_wall_s": s["index_wall_s"],
            }
            if mode == "exact":
                assert got == truth, \
                    f"exact index changed a result set at |DB|={len(corpus)}"
                if len(corpus) >= 100_000:
                    assert examined <= 0.10, \
                        f"stage -1 examined {examined:.2%} of the corpus"
            rows.append(row)
    print_table("Candidate index corpus-size sweep (stage -1)", rows,
                ["case", "corpus", "queries", "tau", "examined_frac",
                 "index_pruned", "stage0_pruned", "stage1_decided",
                 "stage2_verified", "recall", "queries_per_s", "ingest_s"])
    record_section("BENCH_engine", "candidate_index", rows)
    return rows


def engine_store_persistence(quick=True) -> List[Dict]:
    """Warm-start serving economics: cold ingest vs ``GraphStore.save``
    vs warm ``GraphStore.open`` vs incremental ``add``.

    One AIDS-like corpus is ingested from scratch (the cold path every
    process pays without persistence), persisted, and reopened from the
    snapshot; a small batch is then journal-appended to the open store.
    Result parity between the fresh and the reopened store is a
    *blocking* assertion — a persisted store that answers differently is
    a bug, not a slow path — and the warm open must not re-pack
    (``filter_packed_rows`` / ``index_signatures_built`` stay zero).
    The timings themselves are informational; ``warm_open_speedup``
    (bigger is better — ``tools/bench_diff.py`` treats the ``_speedup``
    suffix as such) lands in the ``store_persistence`` section of
    ``results/bench/BENCH_engine.json``.
    """
    import shutil
    import tempfile

    import jax

    from repro.data.graphs import aids_like_graph, perturb
    from repro.ged import GraphStore

    rng = np.random.default_rng(23)
    corpus_size = 120 if quick else 240
    n_queries = 4 if quick else 8
    n_append = 8 if quick else 16
    tau = 4.0
    corpus = [aids_like_graph(rng, int(rng.integers(8, 15)))
              for _ in range(corpus_size)]
    queries = [corpus[int(rng.integers(0, corpus_size))]
               for _ in range(n_queries)]
    extra = [perturb(rng, queries[i % n_queries], int(rng.integers(1, 4)),
                     n_vlabels=62, n_elabels=3) for i in range(n_append)]

    def make() -> GraphStore:
        return GraphStore(corpus, batch_size=32, pool=512, expand=8,
                          max_iters=512, cache=False)

    make().search_batch(queries, tau)          # compile warm-up
    fresh, cold_s = timed(make)
    truth = fresh.search_batch(queries, tau)

    store_dir = tempfile.mkdtemp(prefix="bench-graphstore-")
    try:
        _, save_s = timed(fresh.save, store_dir)
        warm, open_s = timed(
            GraphStore.open, store_dir, batch_size=32, pool=512,
            expand=8, max_iters=512, cache=False)
        got = warm.search_batch(queries, tau)
        assert [[(h.graph_id, h.ged) for h in hs] for hs in got] \
            == [[(h.graph_id, h.ged) for h in hs] for hs in truth], \
            "reopened store changed a result set"
        s = warm.stats
        assert s["filter_packed_rows"] == 0, "warm open re-packed features"
        assert s["index_signatures_built"] == 0, "warm open re-sketched"
        _, append_s = timed(warm.add, extra)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    row = {
        "devices": jax.device_count(),
        "corpus": len(corpus),
        "appended": n_append,
        "queries": n_queries,
        "tau": tau,
        "cold_ingest_s": cold_s,
        "save_s": save_s,
        "warm_open_s": open_s,
        "append_s": append_s,
        "warm_open_speedup": cold_s / max(open_s, 1e-9),
    }
    print_table("GraphStore persistence (cold vs warm vs append)", [row],
                ["corpus", "appended", "cold_ingest_s", "save_s",
                 "warm_open_s", "append_s", "warm_open_speedup"])
    record_section("BENCH_engine", "store_persistence", [row])
    return [row]


def engine_deadline(quick=True) -> List[Dict]:
    """Anytime bound quality vs wall-clock budget (``docs/robustness.md``).

    Runs the ``auto`` pipeline over one fixed pair set at several
    deadline budgets (warm — compiles are paid before the clock starts)
    and reports, per budget: certified fraction, timed-out fraction,
    measured overshoot, and the bound-quality curve ``lb_quality`` =
    mean(lower_bound / true GED) with certified pairs counting 1.0 — the
    number that should climb monotonically toward 1.0 as the budget
    grows.  Soundness (``lb <= true GED <= ub``) is asserted at every
    budget; overshoot must stay within 20% of budgets >= 0.25s.
    """
    gs = groups(quick, pairs_per_group=3)
    pairs = _flat_pairs(gs, max_pairs=24 if quick else 48)
    truth = [exact_ged(q, g, bound="BMa").ged for q, g in pairs]

    def make() -> GedEngine:
        eng = _engine(backend="auto", batch_size=8, max_in_flight=4)
        # small first rung: forces escalation + a host tail, so budgets
        # actually bite on paper-scale pairs
        eng._backend.scheduler.rungs = ((8, 1, 4), (256, 4, 128))
        return eng

    make().compute(pairs)                              # compile warm-up
    budgets = ([0.001, 0.005, 0.02, 0.25] if quick
               else [0.001, 0.005, 0.02, 0.05, 0.25, 1.0])
    rows = []
    for budget in budgets + [None]:
        eng = make()
        outs, dt = timed(eng.compute, pairs, deadline_s=budget)
        lbq = []
        for o, t in zip(outs, truth):
            if not o.certified:
                assert o.lower_bound <= t + 1e-9, (budget, o.lower_bound, t)
                assert o.upper_bound >= t - 1e-9, (budget, o.upper_bound, t)
            lbq.append(1.0 if o.certified
                       else min(o.lower_bound / t, 1.0) if t else 1.0)
        overshoot = 0.0 if budget is None else max(dt - budget, 0.0) / budget
        if budget is not None and budget >= 0.25:
            assert overshoot <= 0.20, \
                f"deadline overshoot {overshoot:.0%} at budget {budget}s"
        rows.append({
            "case": "no-deadline" if budget is None else f"{budget:g}s",
            "budget_s": 0.0 if budget is None else budget,
            "pairs": len(pairs),
            "wall_s": dt,
            "overshoot_frac": overshoot,
            "certified_frac": float(np.mean([o.certified for o in outs])),
            "timed_out_frac": float(np.mean([o.timed_out for o in outs])),
            "lb_quality": float(np.mean(lbq)),
        })
    assert rows[-1]["certified_frac"] == 1.0, \
        "no-deadline run must certify everything"
    print_table("Anytime contract: bound quality vs deadline budget", rows,
                ["case", "pairs", "wall_s", "overshoot_frac",
                 "certified_frac", "timed_out_frac", "lb_quality"])
    record_section("BENCH_engine", "deadline", rows)
    return rows


ALL = (engine_agreement_and_throughput, engine_verification,
       engine_bound_ablation, engine_sweeps_ablation,
       engine_backend_throughput, engine_escalation_overlap,
       engine_similarity_search, engine_deadline, kernel_validation)


def scheduler_cost_model(quick=True) -> List[Dict]:
    """Does the straggler scheduler's difficulty model predict real work?

    Rank correlation between ``runtime.scheduler.difficulty`` and the
    engine's measured per-pair iteration count, plus the wall-time
    balance of LPT-packed batches vs naive contiguous batches under a
    work-proportional cost model.
    """
    from repro.runtime.scheduler import GedScheduler, difficulty

    gs = groups(quick, pairs_per_group=4)
    pairs = _flat_pairs(gs, max_pairs=48)
    eng = _engine()
    outs, _ = timed(eng.compute, pairs)
    iters = np.asarray([o.stats["iterations"] for o in outs], np.float64)

    diffs = [difficulty(q.n, g.n, q.m, g.m, q.vlabels, g.vlabels)
             for q, g in pairs]
    # Spearman rank correlation (no scipy in this image)
    def ranks(v):
        order = np.argsort(v)
        r = np.empty_like(order, dtype=np.float64)
        r[order] = np.arange(len(v))
        return r
    rd, ri = ranks(np.asarray(diffs)), ranks(iters)
    rho = float(np.corrcoef(rd, ri)[0, 1])

    sched = GedScheduler(batch_size=8)
    batches = sched.pack(diffs)
    lpt_worst = max(sum(iters[i] for i in b.indices) for b in batches)
    naive_worst = max(sum(iters[k:k + 8]) for k in range(0, len(pairs), 8))
    rows = [{"pairs": len(pairs), "spearman_rho": rho,
             "lpt_worst_batch_iters": float(lpt_worst),
             "naive_worst_batch_iters": float(naive_worst),
             "straggler_gain": float(naive_worst / max(lpt_worst, 1e-9))}]
    assert rho > 0.2, f"difficulty model uncorrelated with work (rho={rho})"
    print_table("Scheduler cost model vs measured engine work", rows,
                ["pairs", "spearman_rho", "lpt_worst_batch_iters",
                 "naive_worst_batch_iters", "straggler_gain"])
    record("scheduler_cost_model", rows)
    return rows
