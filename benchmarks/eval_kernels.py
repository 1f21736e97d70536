"""Kernel micro-benchmark rail: the search inner loop's device primitives.

Four sections in ``results/bench/BENCH_engine.json`` (tracked across PRs
by ``tools/bench_diff.py``):

* ``kernel_hotpath`` — fused-vs-unfused LSa/BMa child scoring swept over
  ``N in {32, 64, 128} x B in {8, 32, 128}`` **through the autotuner**
  (``repro.kernels.autotune.tune_shape``), so every row records the
  measured winner the ``use_kernel="auto"`` dispatch would pick: the
  ``auto_*`` columns are the tuned rows, and ``auto_speedup >= 1.0`` by
  construction (dispatch can never pick a variant that measured slower
  than both alternatives).  Plus the rank-merge-vs-argsort frontier
  comparison and the fused merge-ranks kernel at pool shapes.
* ``roofline`` — bytes/FLOPs attribution for both bound kernels, the
  rank merge and a whole lowered search step, via
  ``launch/hlo_analysis.analyze_hlo`` over the compiled unfused HLO next
  to the analytic minimum traffic of the fused form — *why* a shape
  wins, not just that it does (``benchmarks/roofline.py --ged`` renders
  it).
* ``autotune`` — the CI smoke: sweep -> persist -> reload -> dispatch on
  a tuning table in a temp dir, with engine-outcome parity between
  ``use_kernel="auto"`` and the unfused baseline asserted (blocking);
  the timings are informational.
* ``compile_cache`` — warm-vs-cold first-call latency across two fresh
  subprocesses sharing one persistent compilation cache directory.

On CPU the Pallas kernels execute in interpret mode (the ``pallas`` and
``device_kind`` columns say so on every row) — fused-vs-unfused ratios
there track *lowering* regressions, not real silicon; on TPU the same
rows measure Mosaic kernels, and the tuning table keyed by
``device_kind`` keeps the two worlds from contaminating each other.
"""

from __future__ import annotations

import functools
import os
import re
import subprocess
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import print_table, record_section

_NS = {True: (32, 64), False: (32, 64, 128)}       # quick -> N sweep
_BS = {True: (8, 32), False: (8, 32, 128)}         # quick -> B sweep
_MERGE_SHAPES = {True: ((512, 256), (2048, 512)),  # (pool, children)
                 False: ((512, 256), (2048, 512), (2048, 1024))}
_BUDGET = {True: 0.08, False: 0.15}                # per-variant timing budget

# Machine balance (FLOP/byte) separating memory- from compute-bound in the
# roofline verdicts: ~TPU-class HBM (e.g. 275 TF/s / 1.2 TB/s ~= 230).
# CPU balances are far lower, so a kernel memory-bound at 240 is
# memory-bound everywhere this repo runs.
_BALANCE = 240.0


def _time(fn, *args, iters: int = 5, blocks: int = 4) -> float:
    """Steady-state seconds per call of a jitted ``fn`` (compiles first).

    ``common.timed_best`` (min over repeats — the least-interference
    estimator for one-sided shared-runner noise) over ``blocks`` timing
    blocks of ``iters`` back-to-back calls each.
    """
    import jax

    from benchmarks.common import timed_best
    jax.block_until_ready(fn(*args))               # compile + warm

    def block():
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)

    _, best = timed_best(block, repeats=blocks)
    return best / iters


def _pallas_mode() -> str:
    import jax
    if os.environ.get("REPRO_DISABLE_PALLAS", "0") == "1":
        return "disabled"
    return "mosaic" if jax.default_backend() == "tpu" else "interpret"


def _device_kind() -> str:
    from repro.kernels.autotune import device_kind
    return device_kind()


def _packed_pair(rng, n: int):
    """One dense random pair packed at ``slots == n`` (full occupancy)."""
    from repro.core.engine.tensor_graphs import pack_pairs
    from repro.data.graphs import perturb, random_graph

    q = random_graph(rng, n, density=0.3, n_vlabels=5, n_elabels=3)
    g = perturb(rng, q, 4, n_vlabels=5, n_elabels=3)
    return pack_pairs([(q, g)], slots=n)


def _states(rng, n: int, b: int):
    """A batch of ``b`` random expansion states (img, level, gcost)."""
    imgs = np.full((b, n), -1, np.int32)
    levels = rng.integers(1, max(2, n // 2), b).astype(np.int32)
    for i, lvl in enumerate(levels):
        imgs[i, :lvl] = rng.permutation(n)[:lvl]
    gcosts = (rng.integers(0, 8, b) * 0.5).astype(np.float32)
    return imgs, levels, gcosts


def kernel_bound_fusion(quick=True) -> List[Dict]:
    """Fused vs unfused LSa/BMa child scoring, measured by the autotuner.

    Every ``(kernel, N, B)`` cell runs ``autotune.tune_shape`` — the
    exact measurement ``use_kernel="auto"`` dispatches on — so the rail
    and the dispatch can never disagree.  ``fused_us`` is the fused
    kernel at its *default* tiles (the PR 5 comparison), ``auto_us`` the
    tuned winner's own time; ``auto_speedup`` compares the winner to the
    better of {fused-default, unfused} and is >= 1.0 by construction.
    """
    from repro.kernels import autotune

    rows = []
    for name in ("lsa", "bma"):
        for n in _NS[quick]:
            for b in _BS[quick]:
                ent = autotune.tune_shape(name, n, b,
                                          budget_s=_BUDGET[quick])
                fused = ent["fused_default_us"]
                unfused = ent["unfused_us"]
                auto = ent["us"]
                rows.append({
                    "case": f"{name}/N={n}/B={b}",
                    "kernel": name, "N": n, "B": b,
                    "fused_us": fused,
                    "unfused_us": unfused,
                    "fused_speedup": unfused / fused,
                    "auto_us": auto,
                    "auto_impl": ent["impl"],
                    "tile_v": ent["tile_v"], "tile_u": ent["tile_u"],
                    "auto_speedup": min(fused, unfused) / auto,
                    "tuned": True,
                    "pallas": ent["pallas"],
                    "device_kind": ent["device_kind"],
                })
    print_table("Kernel fusion: fused vs unfused child scoring (tuned)",
                rows, ["case", "fused_us", "unfused_us", "fused_speedup",
                       "auto_impl", "tile_u", "auto_speedup", "pallas"])
    return rows


def kernel_merge_fusion(quick=True) -> List[Dict]:
    """Pallas rank-count merge kernel vs the searchsorted rank passes.

    The same sorted-pool merge step the engine runs (pop-slice remainder
    + freshly sorted children, payload gather, floor), with only the two
    rank computations swapped — bit-identical outputs either way.
    """
    from repro.kernels import autotune

    rows = []
    for pool, children in _MERGE_SHAPES[quick]:
        ent = autotune.tune_shape("merge", pool, children,
                                  budget_s=_BUDGET[quick])
        fused = ent["fused_us"]
        unfused = ent["unfused_us"]
        rows.append({
            "case": f"merge_ranks/P={pool},BN={children}",
            "kernel": "merge", "pool": pool, "children": children,
            "fused_us": fused,
            "unfused_us": unfused,
            "fused_speedup": unfused / fused,
            "auto_us": ent["us"],
            "auto_impl": ent["impl"],
            "auto_speedup": min(fused, unfused) / ent["us"],
            "tuned": True,
            "pallas": ent["pallas"],
            "device_kind": ent["device_kind"],
        })
    print_table("Frontier merge: Pallas rank counts vs binary search",
                rows, ["case", "fused_us", "unfused_us", "fused_speedup",
                       "auto_impl", "auto_speedup", "pallas"])
    return rows


def kernel_merge_vs_argsort(quick=True) -> List[Dict]:
    """Sorted-pool frontier step vs the old full-pool argsort merge.

    Payload mirrors the engine's pool state (an ``(N,)`` int32 image per
    entry plus level/gcost/lb/valid); both variants are vmapped over a
    pair batch, like the real loop.
    """
    import jax
    import jax.numpy as jnp

    from repro.parallel.ops import merge_sorted_topk, sort_by_key, \
        top_k_sorted

    rng = np.random.default_rng(11)
    batch, bexp = 32, 8                            # pair batch, expand B
    rows = []
    for n in _NS[quick]:
        pool = 2048 if n >= 64 else 512
        bn = bexp * n                              # children per iteration

        def payload(rows_, keys):
            return {"img": jnp.asarray(
                        rng.integers(0, n, (batch, rows_, n)), jnp.int32),
                    "lb": keys / 256.0}

        pool_keys = jnp.asarray(
            np.sort(rng.random((batch, pool)), axis=1), jnp.float32)
        ch_keys = jnp.asarray(rng.random((batch, bn)), jnp.float32)
        pool_pl = payload(pool, pool_keys)
        ch_pl = payload(bn, ch_keys)

        @jax.jit
        def old_step(pk, pp, ck, cp):
            def one(pk, pimg, plb, ck, cimg, clb):
                _, idx = top_k_sorted(-pk, bexp)   # pop: full-pool top_k
                popped = (pimg[idx], plb[idx])
                ak = jnp.concatenate([pk, ck])
                ai = jnp.concatenate([pimg, cimg])
                al = jnp.concatenate([plb, clb])
                order = jnp.argsort(ak)            # full (P + B*N) argsort
                keep = order[:pool]
                return popped, ak[keep], ai[keep], al[keep], \
                    jnp.min(al[order[pool:]])
            return jax.vmap(one)(pk, pp["img"], pp["lb"], ck, cp["img"],
                                 cp["lb"])

        @jax.jit
        def new_step(pk, pp, ck, cp):
            def one(pk, pimg, plb, ck, cimg, clb):
                popped = (pimg[:bexp], plb[:bexp])   # pop: a slice
                rk, (rimg, rlb) = pk[bexp:], (pimg[bexp:], plb[bexp:])
                cks, co = sort_by_key(                # keys only
                    ck, jnp.arange(bn, dtype=jnp.int32))
                keys, (img, lb), dropped = merge_sorted_topk(
                    rk, cks, (rimg, rlb), (cimg, clb), pool,
                    drop_a=rlb, drop_b=clb, perm_b=co)
                return popped, keys, img, lb, dropped
            return jax.vmap(one)(pk, pp["img"], pp["lb"], ck, cp["img"],
                                 cp["lb"])

        old_s = _time(lambda: old_step(pool_keys, pool_pl, ch_keys, ch_pl))
        new_s = _time(lambda: new_step(pool_keys, pool_pl, ch_keys, ch_pl))
        rows.append({
            "case": f"merge/P={pool},BN={bn}",
            "kernel": "merge", "N": n, "pool": pool, "children": bn,
            "pairs": batch,
            "argsort_us": old_s * 1e6,
            "merge_us": new_s * 1e6,
            "merge_speedup": old_s / new_s,
            "device_kind": _device_kind(),
        })
    print_table("Frontier maintenance: rank merge vs full-pool argsort",
                rows, ["case", "pairs", "argsort_us", "merge_us",
                       "merge_speedup"])
    return rows


def kernel_hotpath(quick=True) -> List[Dict]:
    """The full rail -> ``kernel_hotpath`` section of BENCH_engine.json."""
    rows = kernel_bound_fusion(quick) + kernel_merge_vs_argsort(quick) \
        + kernel_merge_fusion(quick)
    # acceptance: dispatch never picks a loser (auto >= best alternative;
    # tiny epsilon for float division noise — the winner's us IS the min)
    for r in rows:
        if "auto_speedup" in r:
            assert r["auto_speedup"] >= 0.999, r
    record_section("BENCH_engine", "kernel_hotpath", rows)
    return rows


# ---------------------------------------------------------------- roofline

def _fused_min_bytes(kernel: str, n: int, b: int, le: int = 3) -> float:
    """Analytic minimum HBM traffic of the fused kernel: every operand
    read once + the output written once (f32/int32 = 4 bytes each).

    lsa operands (see ``kernels/lsa_children.py``): 5x (B,N) f32 + qrow
    (B,N) i32 + 3x (B,N,Le) f32 + 3x (B,Le) f32 + a_ju (B,N,N) i32,
    out (B,N) f32.  bma (``kernels/bma_cost_matrix.py``): qv/gv (B,N)
    i32 + inner hists 2x (B,N,Le) f32 + qa_ord/gcross (B,N,N) i32 +
    pos_anch (B,N) f32, out (B,N,N) f32.
    """
    if kernel == "lsa":
        words = b * (7 * n + 3 * n * le + 3 * le + n * n)
    elif kernel == "bma":
        words = b * (3 * n + 2 * n * le + 3 * n * n)
    else:
        raise ValueError(kernel)
    return 4.0 * words


def _lowered_bound_cost(kernel: str, n: int, b: int) -> Dict[str, float]:
    """flops/bytes of the *unfused* bound at (N, B) from compiled HLO.

    The unfused path is pure XLA (no interpret-mode pallas noise in the
    module), so ``analyze_hlo`` over ``.compile().as_text()`` attributes
    the real einsum-chain traffic the fused kernel replaces.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import bounds as eb
    from repro.launch.hlo_analysis import analyze_hlo

    rng = np.random.default_rng(7)
    t = _packed_pair(rng, n)
    args = tuple(jnp.asarray(x[0]) for x in
                 (t.qv, t.gv, t.qa, t.ga, t.order)) + (jnp.asarray(t.n[0]),)
    imgs, levels, gcosts = (jnp.asarray(a) for a in _states(rng, n, b))

    @functools.partial(jax.jit, static_argnames=("uk",))
    def f(qv, gv, qa, ga, order, nn, im, lv, gc, uk):
        pc = eb.make_pair_consts(qv, gv, qa, ga, order, nn,
                                 t.n_vlabels, t.n_elabels)

        def one(img, level, gcost):
            sm = eb.state_masks(pc, img, level)
            if kernel == "lsa":
                return eb.lsa_children(pc, sm, level, gcost, use_kernel=uk)
            return eb.bma_cost_matrix(pc, sm, use_kernel=uk)

        return jax.vmap(one)(im, lv, gc)

    text = f.lower(*args, imgs, levels, gcosts, uk=False) \
        .compile().as_text()
    c = analyze_hlo(text)
    return {"flops": float(c["flops"]),
            "bytes_accessed": float(c["bytes_accessed"])}


def _lowered_merge_cost(pool: int, children: int, pairs: int = 32
                        ) -> Dict[str, float]:
    """flops/bytes of one sorted-pool merge step (rank passes + payload
    gather + floor) from compiled HLO."""
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze_hlo
    from repro.parallel.ops import merge_sorted_topk, sort_by_key

    rng = np.random.default_rng(11)
    na = pool - 8
    ka = jnp.asarray(np.sort(rng.random((pairs, na)), axis=1), jnp.float32)
    kb = jnp.asarray(rng.random((pairs, children)), jnp.float32)
    pa = jnp.asarray(rng.integers(0, 64, (pairs, na, 16)), jnp.int32)
    pb = jnp.asarray(rng.integers(0, 64, (pairs, children, 16)), jnp.int32)

    @jax.jit
    def f(ka, kb, pa, pb):
        def one(ka, kb, pa, pb):
            kbs, order = sort_by_key(
                kb, jnp.arange(children, dtype=jnp.int32))
            return merge_sorted_topk(ka, kbs, (pa,), (pb,), pool,
                                     drop_a=ka, drop_b=kbs, perm_b=order)
        return jax.vmap(one)(ka, kb, pa, pb)

    text = f.lower(ka, kb, pa, pb).compile().as_text()
    c = analyze_hlo(text)
    return {"flops": float(c["flops"]),
            "bytes_accessed": float(c["bytes_accessed"])}


def _lowered_search_step_cost(n: int, batch: int = 8) -> Dict[str, float]:
    """flops/bytes of the whole jitted search (``_run_batch``) at a
    bucket shape, lowered from abstract inputs with kernels off (pure
    XLA, so the HLO walk sees everything)."""
    from repro.core.engine import api as engine_api
    from repro.core.engine.search import EngineConfig
    from repro.launch.hlo_analysis import analyze_hlo

    ab = engine_api.batch_abstract_inputs(batch, n)
    cfg = EngineConfig(pool=256, expand=4, max_iters=64, use_kernel=False)
    lowered = engine_api._run_batch.lower(
        ab["qv"], ab["gv"], ab["qa"], ab["ga"], ab["order"], ab["n"],
        ab["taus"], cfg, False, 5, 3)
    c = analyze_hlo(lowered.compile().as_text())
    return {"flops": float(c["flops"]),
            "bytes_accessed": float(c["bytes_accessed"])}


def kernel_roofline(quick=True) -> List[Dict]:
    """Bytes/FLOPs attribution -> ``roofline`` section of BENCH_engine.

    For each bound kernel at the swept N (B = 8; both costs scale ~
    linearly in B so the intensity verdict is B-independent): the
    unfused einsum chain's measured HLO traffic next to the fused form's
    analytic minimum.  ``intensity_fused_ideal < balance`` means the
    kernel stays memory-bound even with perfect fusion — the win comes
    from the traffic it deletes, which is exactly what the table shows.
    The rank-merge row is what justifies the fused merge kernel: its
    intensity sits far below any machine balance (it is a comparison
    count — almost no FLOPs per byte), i.e. memory-bound, so fusing the
    two rank passes into one VMEM-resident kernel is the only lever.
    """
    rows = []
    b = 8
    for kernel in ("lsa", "bma"):
        for n in _NS[quick]:
            c = _lowered_bound_cost(kernel, n, b)
            fused_bytes = _fused_min_bytes(kernel, n, b)
            intensity = c["flops"] / max(c["bytes_accessed"], 1.0)
            ideal = c["flops"] / fused_bytes
            rows.append({
                "case": f"{kernel}/N={n}/B={b}",
                "kernel": kernel, "N": n, "B": b,
                "flops": c["flops"],
                "bytes_unfused": c["bytes_accessed"],
                "bytes_fused_min": fused_bytes,
                "traffic_ratio": c["bytes_accessed"] / fused_bytes,
                "intensity_unfused": intensity,
                "intensity_fused_ideal": ideal,
                "memory_bound": bool(ideal < _BALANCE),
                "balance": _BALANCE,
                "device_kind": _device_kind(),
            })
    pool, children = _MERGE_SHAPES[quick][-1]
    c = _lowered_merge_cost(pool, children)
    intensity = c["flops"] / max(c["bytes_accessed"], 1.0)
    rows.append({
        "case": f"merge/P={pool},BN={children}",
        "kernel": "merge", "N": pool, "B": children,
        "flops": c["flops"],
        "bytes_unfused": c["bytes_accessed"],
        "intensity_unfused": intensity,
        "intensity_fused_ideal": intensity,   # fusion deletes no FLOPs
        "memory_bound": bool(intensity < _BALANCE),
        "balance": _BALANCE,
        "device_kind": _device_kind(),
    })
    n0 = _NS[quick][0]
    c = _lowered_search_step_cost(n0)
    rows.append({
        "case": f"search_step/N={n0}/B=8",
        "kernel": "search_step", "N": n0, "B": 8,
        "flops": c["flops"],
        "bytes_unfused": c["bytes_accessed"],
        "intensity_unfused": c["flops"] / max(c["bytes_accessed"], 1.0),
        "memory_bound": bool(
            c["flops"] / max(c["bytes_accessed"], 1.0) < _BALANCE),
        "balance": _BALANCE,
        "device_kind": _device_kind(),
    })
    print_table("GED kernel roofline (unfused HLO vs fused minimum "
                "traffic)", rows,
                ["case", "flops", "bytes_unfused", "bytes_fused_min",
                 "intensity_unfused", "intensity_fused_ideal",
                 "memory_bound"])
    record_section("BENCH_engine", "roofline", rows)
    return rows


# ---------------------------------------------------------------- autotune

def kernel_autotune(quick=True) -> List[Dict]:
    """CI smoke: sweep -> persist -> reload -> dispatch, parity-gated.

    Runs a tiny tuning sweep into a temp directory, drops the in-memory
    table, reloads it from disk, and computes a small workload with
    ``use_kernel="auto"`` against the unfused baseline.  Outcome parity
    and table round-trip are *asserted* (blocking); the recorded timings
    are informational.  Engine/global tuning state is snapshotted and
    restored, so this probe never contaminates the other rails.
    """
    from repro import ged
    from repro.data.graphs import perturb, random_graph
    from repro.kernels import autotune

    rows: List[Dict] = []
    saved = autotune.snapshot()
    try:
        with tempfile.TemporaryDirectory() as d:
            autotune.reset()
            autotune.enable_autotune(d)
            t0 = time.perf_counter()
            entries = autotune.tune(ns=(8, 16), bs=(8,),
                                    kernels=("lsa", "bma"),
                                    merge_shapes=((128, 64),),
                                    tiles=((0, 0),), budget_s=0.02)
            sweep_s = time.perf_counter() - t0
            assert len(entries) == 5, entries
            rows.append({"run": "sweep", "entries": len(entries),
                         "sweep_s": sweep_s,
                         "pallas": _pallas_mode(),
                         "device_kind": _device_kind()})

            # persist -> reload: a fresh table must serve the same rows
            autotune.reset()
            autotune.enable_autotune(d)
            reloaded = autotune.lookup("lsa", 8, 8, count=False)
            assert reloaded is not None and reloaded["impl"] in \
                ("fused", "unfused"), reloaded
            rows.append({"run": "reload",
                         "entries": len(autotune._AUTOTUNE["table"])})

            # dispatch + parity (blocking): auto must match the baseline
            rng = np.random.default_rng(5)
            pairs = [(random_graph(rng, int(rng.integers(4, 9)),
                                   density=0.4, n_vlabels=3, n_elabels=2),
                      perturb(rng, random_graph(rng, 6, density=0.4,
                                                n_vlabels=3, n_elabels=2),
                              2, n_vlabels=3, n_elabels=2))
                     for _ in range(8)]
            ea = ged.GedEngine("jax", use_kernel="auto", cache=False,
                               autotune_dir=d, pool=128, max_iters=128)
            eb_ = ged.GedEngine("jax", cache=False, pool=128,
                                max_iters=128)
            t0 = time.perf_counter()
            oa = ea.compute(pairs)
            auto_s = time.perf_counter() - t0
            ob = eb_.compute(pairs)
            for a, b in zip(oa, ob):
                assert (a.ged, a.certified, a.lower_bound, a.upper_bound) \
                    == (b.ged, b.certified, b.lower_bound, b.upper_bound), \
                    (a, b)
                assert np.array_equal(a.mapping, b.mapping)
            s = ea.stats
            assert s["autotune_hits"] >= 1, s
            rows.append({"run": "dispatch", "pairs": len(pairs),
                         "auto_s": auto_s, "parity_ok": 1.0,
                         "autotune_hits": s["autotune_hits"],
                         "autotune_misses": s["autotune_misses"],
                         "pallas_interpret": bool(s["pallas_interpret"])})
    finally:
        autotune.restore(saved)
    print_table("Autotune smoke: sweep -> persist -> reload -> dispatch",
                rows, ["run", "entries", "sweep_s", "pairs", "auto_s",
                       "parity_ok", "autotune_hits"])
    record_section("BENCH_engine", "autotune", rows)
    return rows


# ------------------------------------------------------------ compile cache

_CACHE_PROBE = """
import time
import jax
from repro import ged
from repro.ged.exec import enable_compile_cache
enable_compile_cache()          # the directory comes from the environment
pairs = [(([0, 1, 1], [(0, 1, 1), (1, 2, 2)]),
          ([0, 1, 2], [(0, 1, 1), (0, 2, 1)]))]
eng = ged.GedEngine("jax", cache=False, pool=64, max_iters=64)
t0 = time.perf_counter(); eng.compute(pairs)
first = time.perf_counter() - t0
t0 = time.perf_counter(); eng.compute(pairs)
steady = time.perf_counter() - t0
s = eng.stats
d = jax.devices()[0]
print(f"RESULT first={first} steady={steady} "
      f"hits={s['persistent_cache_hits']} "
      f"misses={s['persistent_cache_misses']} "
      f"platform={d.platform} kind={d.device_kind.replace(' ', '_')} "
      f"count={len(jax.devices())}")
"""


def kernel_compile_cache(quick=True) -> List[Dict]:
    """Warm-vs-cold first-call compile across processes.

    Two fresh subprocesses run the same tiny engine workload against one
    persistent compilation cache directory, placed through
    ``JAX_COMPILATION_CACHE_DIR``: the first pays the XLA compile and
    serialises it, the second deserialises.  The remaining warm
    first-call time is tracing + dispatch, which the persistent cache
    cannot remove.  The children run on whatever platform the
    environment gives them, and each row names it.

    A device belongs to one process at a time, so this must run before
    the calling process initialises a JAX backend (``benchmarks/run.py``
    runs it first); it refuses to run after.
    """
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "kernel_compile_cache starts processes that need the device; "
            "run it before this process initialises a JAX backend")
    rows = []
    with tempfile.TemporaryDirectory() as d:
        for run in ("cold", "warm"):
            env = dict(os.environ)
            env["JAX_COMPILATION_CACHE_DIR"] = d
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env["PYTHONPATH"] = os.path.join(root, "src") + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
            out = subprocess.run(
                [sys.executable, "-c", _CACHE_PROBE],
                capture_output=True, text=True, env=env, check=True)
            m = re.search(r"RESULT first=(\S+) steady=(\S+) hits=(\S+) "
                          r"misses=(\S+) platform=(\S+) kind=(\S+) "
                          r"count=(\S+)", out.stdout)
            assert m, out.stdout + out.stderr
            rows.append({
                "run": run,
                "first_call_s": float(m.group(1)),
                "steady_s": float(m.group(2)),
                "persistent_cache_hits": float(m.group(3)),
                "persistent_cache_misses": float(m.group(4)),
                "platform": m.group(5),
                "device_kind": m.group(6).replace("_", " "),
                "devices": int(m.group(7)),
            })
    assert rows[0]["persistent_cache_misses"] >= 1, rows
    assert rows[1]["persistent_cache_hits"] >= 1, rows
    print_table("Persistent compile cache: cold vs warm process", rows,
                ["run", "platform", "device_kind", "first_call_s",
                 "steady_s", "persistent_cache_hits",
                 "persistent_cache_misses"])
    record_section("BENCH_engine", "compile_cache", rows)
    return rows


# kernel_compile_cache is not here: it must run before the process touches
# JAX, which benchmarks/run.py arranges.
ALL = (kernel_hotpath, kernel_roofline, kernel_autotune)
