"""Measured kernel autotuning and per-bucket dispatch.

PR 5's microbench rail was honest about the fused kernels: in interpret
mode on CPU the fused LSa loses below N = 128 and the fused BMa only wins
at N = 128, so a global ``use_kernel=True`` is a pessimization for the
small buckets that dominate AIDS-like workloads.  This module makes the
choice *measured* instead of global:

* ``tune_shape(kernel, n, b)`` benchmarks fused-vs-unfused (and a small
  tile-size sweep for the fused variant) at one engine-realistic shape on
  the **current** backend — compiled Mosaic on TPU, interpret otherwise —
  and records the winner in a tuning table.
* The table is keyed by ``(device_kind, kernel, N, B)`` and persisted to
  ``<dir>/tuning.json`` when a directory is configured
  (``enable_autotune(dir)`` / ``REPRO_GED_AUTOTUNE_DIR``), mirroring the
  persistent-compile-cache contract from PR 5: idempotent enable, reset
  on re-point, corrupt files recover to an empty table, and
  ``autotune_hits`` / ``autotune_misses`` / ``autotune_sweep_s`` counters
  surface in ``GedEngine.stats``.
* ``EngineConfig.use_kernel="auto"``: ``resolve_config`` runs **pre-jit**
  (in ``ged/exec.py Executor.run_packed_async``) and pins each bucket's
  ``(slots, batch)`` shape to a concrete ``KernelDispatch`` — per-family
  fused/unfused flags plus tuned tile sizes — stored on the (hashable,
  static) config, so every jit/compile cache keys on the decision and
  outcomes stay bit-identical across all dispatch paths (the kernels are
  exact vs their oracles).  Untuned shapes fall back to a conservative
  static heuristic: everything unfused under interpret-mode Pallas (the
  CPU footgun), fused only for N >= 128 on a real accelerator.

Key schema (flat strings in ``tuning.json``)::

    "<device_kind>|<kernel>|N=<n>|B=<b>"

where ``kernel`` is ``lsa`` / ``bma`` (N = bucket slots, B = state batch
through the nested vmaps = pairs x expand) or ``merge`` (N = pool size,
B = children per iteration = expand x slots).  Lookups try the exact key
first, then the nearest tuned B (log-space) at the same
``(device_kind, kernel, N)`` — kernel cost is ~linear in B, so the
winner rarely flips with B alone — and only count a miss when no
measurement for the (kernel, N) pair exists at all.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

AUTOTUNE_ENV = "REPRO_GED_AUTOTUNE_DIR"
TABLE_FILE = "tuning.json"
_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class KernelDispatch:
    """A concrete per-bucket kernel plan: static, hashable, jit-key-safe.

    ``tile_* = 0`` means the kernel's own default tiling
    (``min(N, 128)`` for LSa's candidate axis and for BMa's
    block shape).
    """

    lsa_fused: bool = False
    lsa_tile_u: int = 0
    bma_fused: bool = False
    bma_tile_v: int = 0
    bma_tile_u: int = 0
    merge_fused: bool = False


# Module state, mirroring ``ged/exec.py``'s ``_PERSISTENT_CACHE``.
_AUTOTUNE = {
    "dir": None,        # Optional[str] — None = in-memory table only
    "table": {},        # key -> entry dict
    "hits": 0,
    "misses": 0,
    "sweep_s": 0.0,
}


# --------------------------------------------------------------------------
# table: enable / load / save / lookup
# --------------------------------------------------------------------------

def device_kind() -> str:
    """The tuning-table device key, e.g. ``"cpu"`` or ``"TPU v4"``."""
    import jax
    return jax.devices()[0].device_kind


def pallas_interpret() -> bool:
    from repro.kernels import ops as kops
    return kops.pallas_interpret()


def _table_path(path: str) -> str:
    return os.path.join(path, TABLE_FILE)


def _load(path: str) -> Dict[str, Dict]:
    """Read a tuning table; corrupt or alien files recover to empty."""
    from repro.store_io.atomic import read_json_or_none
    raw = read_json_or_none(_table_path(path))
    if not isinstance(raw, dict) or raw.get("version") != _SCHEMA_VERSION:
        return {}
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        return {}
    return {k: v for k, v in entries.items() if isinstance(v, dict)}


def _save() -> None:
    """Atomically persist the in-memory table (no-op without a dir).

    Goes through the shared atomic-IO core (:mod:`repro.store_io.atomic`)
    but keeps the raw ``{"version", "entries"}`` file format — no
    manifest envelope — so existing tables stay readable.
    """
    path = _AUTOTUNE["dir"]
    if path is None:
        return
    from repro.store_io.atomic import atomic_write_json
    payload = {"version": _SCHEMA_VERSION, "entries": _AUTOTUNE["table"]}
    atomic_write_json(_table_path(path), payload, indent=1, sort_keys=True)


def enable_autotune(path: Optional[str] = None) -> Optional[str]:
    """Point the tuning table at a directory and load any persisted rows.

    ``path=None`` falls back to ``$REPRO_GED_AUTOTUNE_DIR``; when neither
    is set the table stays purely in-memory (tuning still works, nothing
    persists).  Idempotent for a repeated path; re-pointing at a new
    directory replaces the in-memory table with that directory's rows.
    """
    path = path or os.environ.get(AUTOTUNE_ENV)
    if path is None:
        return _AUTOTUNE["dir"]
    if path == _AUTOTUNE["dir"]:
        return path
    os.makedirs(path, exist_ok=True)
    _AUTOTUNE["dir"] = path
    _AUTOTUNE["table"] = _load(path)
    return path


def reset() -> None:
    """Forget the directory, table and counters (tests / bench probes)."""
    _AUTOTUNE.update(dir=None, table={}, hits=0, misses=0, sweep_s=0.0)


def snapshot() -> Dict:
    """Copy of the module state, for save/restore around bench probes."""
    out = dict(_AUTOTUNE)
    out["table"] = dict(_AUTOTUNE["table"])
    return out


def restore(state: Dict) -> None:
    _AUTOTUNE.clear()
    _AUTOTUNE.update(state)


def autotune_stats() -> Dict[str, float]:
    """Merged into ``GedEngine.stats`` (same contract as the persistent
    compile cache counters)."""
    return {
        "autotune_hits": float(_AUTOTUNE["hits"]),
        "autotune_misses": float(_AUTOTUNE["misses"]),
        "autotune_sweep_s": float(_AUTOTUNE["sweep_s"]),
        "autotune_entries": float(len(_AUTOTUNE["table"])),
        "pallas_interpret": pallas_interpret(),
    }


def table_key(kernel: str, n: int, b: int, kind: Optional[str] = None) -> str:
    return f"{kind or device_kind()}|{kernel}|N={int(n)}|B={int(b)}"


def put(kernel: str, n: int, b: int, entry: Dict) -> Dict:
    entry = dict(entry)
    entry.update(kernel=kernel, N=int(n), B=int(b),
                 device_kind=device_kind())
    _AUTOTUNE["table"][table_key(kernel, n, b)] = entry
    _save()
    return entry


def lookup(kernel: str, n: int, b: int, count: bool = True) -> Optional[Dict]:
    """Tuned entry for ``(device_kind, kernel, n, b)``, or None.

    Falls back to the nearest tuned ``B`` (log-space) at the same
    ``(device_kind, kernel, n)`` — still a hit.  ``count=False`` probes
    without touching the hit/miss counters.
    """
    exact = _AUTOTUNE["table"].get(table_key(kernel, n, b))
    if exact is not None:
        if count:
            _AUTOTUNE["hits"] += 1
        return exact
    prefix = f"{device_kind()}|{kernel}|N={int(n)}|B="
    best, best_d = None, None
    for key, entry in _AUTOTUNE["table"].items():
        if not key.startswith(prefix):
            continue
        bb = int(key.rsplit("B=", 1)[1])
        d = abs(math.log(max(bb, 1)) - math.log(max(int(b), 1)))
        if best_d is None or d < best_d:
            best, best_d = entry, d
    if count:
        if best is not None:
            _AUTOTUNE["hits"] += 1
        else:
            _AUTOTUNE["misses"] += 1
    return best


# --------------------------------------------------------------------------
# dispatch resolution
# --------------------------------------------------------------------------

def static_heuristic(n: int) -> KernelDispatch:
    """Conservative plan for unmeasured shapes.

    Under interpret-mode Pallas (CPU) everything stays unfused — the
    measured table says fused interpret kernels lose at small N, and an
    interpret-mode "win" would be a lie about silicon anyway.  On a real
    accelerator the fused bound kernels win once tiles are full, so
    default them on from N >= 128; the merge kernel stays off until
    measured.
    """
    if pallas_interpret():
        return KernelDispatch()
    on = int(n) >= 128
    return KernelDispatch(lsa_fused=on, bma_fused=on)


def _safe_tile(tile, n: int) -> int:
    """Tile sizes from disk are untrusted: anything that doesn't divide
    the axis falls back to the kernel default (0)."""
    try:
        tile = int(tile)
    except (TypeError, ValueError):
        return 0
    if tile <= 0 or int(n) % tile != 0:
        return 0
    return tile


def resolve_config(cfg, slots: int, batch: int):
    """Pin ``use_kernel="auto"`` to a concrete ``KernelDispatch``.

    Runs once per bucket dispatch, **before** jit (``ged/exec.py``), so
    the resolved config — not the tuning table — is what every jit /
    compile cache keys on.  Non-"auto" configs pass through untouched.
    """
    if getattr(cfg, "use_kernel", None) != "auto" or cfg.dispatch is not None:
        return cfg
    n = int(slots)
    fallback = static_heuristic(n)
    b_eff = int(batch) * int(cfg.expand)

    fields = {}
    want_lsa = cfg.bound in ("lsa", "hybrid")
    want_bma = cfg.bound in ("bma", "hybrid")
    if want_lsa:
        ent = lookup("lsa", n, b_eff)
        if ent is not None:
            fields["lsa_fused"] = ent.get("impl") == "fused"
            fields["lsa_tile_u"] = _safe_tile(ent.get("tile_u"), n)
        else:
            fields["lsa_fused"] = fallback.lsa_fused
    if want_bma:
        ent = lookup("bma", n, b_eff)
        if ent is not None:
            fields["bma_fused"] = ent.get("impl") == "fused"
            fields["bma_tile_v"] = _safe_tile(ent.get("tile_v"), n)
            fields["bma_tile_u"] = _safe_tile(ent.get("tile_u"), n)
        else:
            fields["bma_fused"] = fallback.bma_fused
    ent = lookup("merge", int(cfg.pool), int(cfg.expand) * n)
    if ent is not None:
        fields["merge_fused"] = ent.get("impl") == "fused"
    else:
        fields["merge_fused"] = fallback.merge_fused
    return dataclasses.replace(cfg, dispatch=KernelDispatch(**fields))


def concrete_dispatch(cfg, n: int) -> KernelDispatch:
    """The plan the search loop follows — **pure** in ``cfg`` and ``n``.

    Called at trace time inside ``core/engine/search.py``; it must not
    consult the mutable tuning table (the jit cache keys on ``cfg``, so a
    table-dependent trace would go stale when the table changes).  An
    "auto" config that reached tracing without a resolved ``dispatch``
    (i.e. not via the executor) gets the static heuristic.
    """
    d = getattr(cfg, "dispatch", None)
    if d is not None:
        # a plan's tiles are for the bucket it was made for: on an axis a
        # tile does not divide, the kernel's default tiling stands in
        fit = {f: _safe_tile(getattr(d, f), n)
               for f in ("lsa_tile_u", "bma_tile_v", "bma_tile_u")}
        if any(getattr(d, f) != t for f, t in fit.items()):
            return dataclasses.replace(d, **fit)
        return d
    uk = cfg.use_kernel
    if uk == "auto":
        return static_heuristic(n)
    on = bool(uk)
    return KernelDispatch(lsa_fused=on, bma_fused=on)


# --------------------------------------------------------------------------
# the sweep
# --------------------------------------------------------------------------

def _timeit(fn, budget_s: float = 0.15) -> float:
    """Best-of-3 steady-state seconds per call, iteration count scaled to
    ``budget_s`` so slow interpret-mode variants don't stall the sweep."""
    import jax

    jax.block_until_ready(fn())                    # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    est = time.perf_counter() - t0
    iters = max(1, min(8, int(budget_s / (3.0 * max(est, 1e-7)))))
    best = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _bound_bench(kernel: str, n: int, b: int, seed: int = 7):
    """A jitted fused/unfused bound evaluation at engine-realistic shapes:
    one dense packed pair at ``slots == n``, ``b`` random expansion states
    through the same nested-vmap structure the search loop traces.

    Returns ``bench(uk, tv, tu) -> device array``.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core.engine import bounds as eb
    from repro.core.engine.tensor_graphs import pack_pairs
    from repro.data.graphs import perturb, random_graph

    rng = np.random.default_rng(seed)
    q = random_graph(rng, n, density=0.3, n_vlabels=5, n_elabels=3)
    g = perturb(rng, q, 4, n_vlabels=5, n_elabels=3)
    t = pack_pairs([(q, g)], slots=n)
    args = tuple(jnp.asarray(x[0]) for x in
                 (t.qv, t.gv, t.qa, t.ga, t.order)) + (jnp.asarray(t.n[0]),)

    imgs = np.full((b, n), -1, np.int32)
    levels = rng.integers(1, max(2, n // 2), b).astype(np.int32)
    for i, lvl in enumerate(levels):
        imgs[i, :lvl] = rng.permutation(n)[:lvl]
    gcosts = (rng.integers(0, 8, b) * 0.5).astype(np.float32)
    states = tuple(jnp.asarray(a) for a in (imgs, levels, gcosts))

    @functools.partial(jax.jit, static_argnames=("uk", "tv", "tu"))
    def f(qv, gv, qa, ga, order, nn, im, lv, gc, uk, tv, tu):
        pc = eb.make_pair_consts(qv, gv, qa, ga, order, nn,
                                 t.n_vlabels, t.n_elabels)

        def one(img, level, gcost):
            sm = eb.state_masks(pc, img, level)
            if kernel == "lsa":
                return eb.lsa_children(pc, sm, level, gcost,
                                       use_kernel=uk, tile_u=tu)
            return eb.bma_cost_matrix(pc, sm, use_kernel=uk,
                                      tile_v=tv, tile_u=tu)

        return jax.vmap(one)(im, lv, gc)

    return lambda uk, tv, tu: f(*args, *states, uk=uk, tv=tv, tu=tu)


def _merge_bench(pool: int, children: int, seed: int = 11, pairs: int = 8):
    """A jitted sorted-pool merge step (the engine's frontier update)
    vmapped over a small pair batch.  Returns ``bench(uk) -> arrays``."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.parallel.ops import merge_sorted_topk, sort_by_key

    rng = np.random.default_rng(seed)
    na = max(int(pool) - 8, 8)                     # pool minus the pop slice
    nb = int(children)
    ka = jnp.asarray(np.sort(rng.random((pairs, na)), axis=1), jnp.float32)
    kb = jnp.asarray(rng.random((pairs, nb)), jnp.float32)
    pa = jnp.asarray(rng.integers(0, 64, (pairs, na, 16)), jnp.int32)
    pb = jnp.asarray(rng.integers(0, 64, (pairs, nb, 16)), jnp.int32)

    @functools.partial(jax.jit, static_argnames=("uk",))
    def f(ka, kb, pa, pb, uk):
        def one(ka, kb, pa, pb):
            kbs, order = sort_by_key(kb, jnp.arange(nb, dtype=jnp.int32))
            return merge_sorted_topk(ka, kbs, (pa,), (pb,), int(pool),
                                     drop_a=ka, drop_b=kbs, perm_b=order,
                                     use_kernel=uk)
        return jax.vmap(one)(ka, kb, pa, pb)

    return lambda uk: f(ka, kb, pa, pb, uk=uk)


def _tile_candidates(kernel: str, n: int) -> List[Tuple[int, int]]:
    """(tile_v, tile_u) sweep candidates; (0, 0) = the kernel default."""
    cands = [(0, 0)]
    if kernel == "lsa":
        default = n if n <= 128 else math.gcd(n, 128)
        for t in (8, 32, 64):
            if n % t == 0 and t != default:
                cands.append((0, t))
    elif kernel == "bma":
        default = min(n, 128)
        for t in (8, 32):
            if n % t == 0 and t != default:
                cands.append((t, t))
    return cands


def tune_shape(kernel: str, n: int, b: int, *,
               tiles: Optional[Sequence[Tuple[int, int]]] = None,
               budget_s: float = 0.15) -> Dict:
    """Benchmark one ``(kernel, N, B)`` shape and record the winner.

    For ``lsa``/``bma``: times the unfused path and the fused kernel at
    each tile candidate; for ``merge``: times the searchsorted rank path
    vs the Pallas rank-count kernel.  The entry's ``us`` is the winner's
    own measured time (``impl`` names it), so dispatch-by-table can never
    pick a variant that measured slower.
    """
    t0 = time.perf_counter()
    if kernel in ("lsa", "bma"):
        bench = _bound_bench(kernel, int(n), int(b))
        unfused_s = _timeit(lambda: bench(False, 0, 0), budget_s)
        best_s, best_tv, best_tu = math.inf, 0, 0
        default_s = math.inf
        for tv, tu in (tiles if tiles is not None
                       else _tile_candidates(kernel, int(n))):
            s = _timeit(lambda: bench(True, tv, tu), budget_s)
            if (tv, tu) == (0, 0):
                default_s = s
            if s < best_s:
                best_s, best_tv, best_tu = s, tv, tu
        if not math.isfinite(default_s):
            default_s = best_s
        fused_wins = best_s < unfused_s
        entry = {
            "impl": "fused" if fused_wins else "unfused",
            "tile_v": best_tv if fused_wins else 0,
            "tile_u": best_tu if fused_wins else 0,
            "us": min(best_s, unfused_s) * 1e6,
            "fused_us": best_s * 1e6,
            "fused_default_us": default_s * 1e6,
            "unfused_us": unfused_s * 1e6,
        }
    elif kernel == "merge":
        bench = _merge_bench(int(n), int(b))
        unfused_s = _timeit(lambda: bench(False), budget_s)
        fused_s = _timeit(lambda: bench(True), budget_s)
        fused_wins = fused_s < unfused_s
        entry = {
            "impl": "fused" if fused_wins else "unfused",
            "tile_v": 0, "tile_u": 0,
            "us": min(fused_s, unfused_s) * 1e6,
            "fused_us": fused_s * 1e6,
            "fused_default_us": fused_s * 1e6,
            "unfused_us": unfused_s * 1e6,
        }
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    entry["pallas"] = "interpret" if pallas_interpret() else "mosaic"
    _AUTOTUNE["sweep_s"] += time.perf_counter() - t0
    return put(kernel, n, b, entry)


def tune(*, ns: Iterable[int] = (32, 64, 128),
         bs: Iterable[int] = (8, 32, 128),
         kernels: Iterable[str] = ("lsa", "bma"),
         merge_shapes: Iterable[Tuple[int, int]] = ((512, 256), (2048, 1024)),
         force: bool = False,
         tiles: Optional[Sequence[Tuple[int, int]]] = None,
         budget_s: float = 0.15) -> List[Dict]:
    """Pre-warm the table over a shape grid (skips already-tuned keys
    unless ``force``).  This is the "pre-warm a machine" entry point from
    docs/kernels.md."""
    entries = []
    for kernel in kernels:
        for n in ns:
            for b in bs:
                if not force and \
                        table_key(kernel, n, b) in _AUTOTUNE["table"]:
                    continue
                entries.append(tune_shape(kernel, n, b, tiles=tiles,
                                          budget_s=budget_s))
    for pool, children in merge_shapes:
        if not force and \
                table_key("merge", pool, children) in _AUTOTUNE["table"]:
            continue
        entries.append(tune_shape("merge", pool, children,
                                  budget_s=budget_s))
    return entries
