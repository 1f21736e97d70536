"""Measured kernel autotuning + per-bucket dispatch (kernels/autotune.py).

Three contracts under test:

* the tuning table: round-trip through disk, corrupt-file recovery,
  nearest-B fallback, hit/miss counters;
* dispatch resolution: ``use_kernel="auto"`` pins a concrete
  ``KernelDispatch`` pre-jit (table winners when tuned, the conservative
  static heuristic when not, hostile tile sizes neutralised);
* the invariant everything rests on: engine outcomes are bit-identical
  across every dispatch decision — fused/unfused x tuned/untuned tiles x
  astar/dfs x compute/verify — so dispatch can only ever change speed.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core.engine import EngineConfig, ged_batch, pack_pairs, \
    verify_batch
from repro.data.graphs import perturb, random_graph
from repro.kernels import autotune
from repro.kernels.autotune import KernelDispatch


@pytest.fixture(autouse=True)
def _isolated_table():
    """Every test runs on a private in-memory table and restores the
    process-global state afterwards (the table is process-global by
    design, like the persistent compile cache)."""
    saved = autotune.snapshot()
    autotune.reset()
    yield
    autotune.restore(saved)


def _make_pairs(seed, count, nmin=4, nmax=9, ops=5):
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(count):
        n = int(rng.integers(nmin, nmax))
        q = random_graph(rng, n, density=0.35, n_vlabels=3, n_elabels=2)
        if rng.random() < 0.5:
            g = perturb(rng, q, int(rng.integers(0, ops)),
                        n_vlabels=3, n_elabels=2)
        else:
            g = random_graph(rng, int(rng.integers(nmin, nmax)),
                             density=0.35, n_vlabels=3, n_elabels=2)
        pairs.append((q, g))
    return pairs


# ------------------------------------------------------------------ table

def test_table_round_trip(tmp_path):
    autotune.enable_autotune(str(tmp_path))
    autotune.put("lsa", 32, 8, {"impl": "fused", "tile_u": 8, "us": 1.0})
    autotune.put("merge", 512, 256, {"impl": "unfused", "us": 2.0})
    # a fresh process-equivalent: reset then re-enable the same dir
    autotune.reset()
    autotune.enable_autotune(str(tmp_path))
    ent = autotune.lookup("lsa", 32, 8, count=False)
    assert ent is not None and ent["impl"] == "fused" \
        and ent["tile_u"] == 8
    assert autotune.lookup("merge", 512, 256, count=False)["us"] == 2.0
    # entries carry their identity + device key
    assert ent["kernel"] == "lsa" and ent["N"] == 32 and ent["B"] == 8
    assert ent["device_kind"] == autotune.device_kind()


def test_table_corrupt_file_recovers_empty(tmp_path):
    path = tmp_path / autotune.TABLE_FILE
    path.write_text("{this is not json")
    autotune.enable_autotune(str(tmp_path))
    assert autotune.lookup("lsa", 32, 8, count=False) is None
    # and the table is usable again: writes land and persist
    autotune.put("lsa", 32, 8, {"impl": "unfused"})
    data = json.loads(path.read_text())
    assert data["version"] == autotune._SCHEMA_VERSION
    assert len(data["entries"]) == 1


@pytest.mark.parametrize("payload", [
    "[]",                                   # wrong top-level type
    '{"version": 999, "entries": {}}',      # alien schema version
    '{"version": 1, "entries": [1, 2]}',    # entries not a map
])
def test_table_alien_schema_recovers_empty(tmp_path, payload):
    (tmp_path / autotune.TABLE_FILE).write_text(payload)
    autotune.enable_autotune(str(tmp_path))
    assert autotune._AUTOTUNE["table"] == {}


def test_lookup_nearest_b_and_counters():
    autotune.put("lsa", 32, 8, {"impl": "unfused"})
    autotune.put("lsa", 32, 128, {"impl": "fused", "tile_u": 0})
    # exact hit
    assert autotune.lookup("lsa", 32, 8)["impl"] == "unfused"
    # nearest-B in log space: B=64 is closer to 128 than to 8
    assert autotune.lookup("lsa", 32, 64)["impl"] == "fused"
    assert autotune.lookup("lsa", 32, 2)["impl"] == "unfused"
    # other N -> miss
    assert autotune.lookup("lsa", 64, 8) is None
    s = autotune.autotune_stats()
    assert s["autotune_hits"] == 3 and s["autotune_misses"] == 1
    assert s["autotune_entries"] == 2
    assert "pallas_interpret" in s


def test_enable_is_idempotent_and_repoint_reloads(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    autotune.enable_autotune(str(a))
    autotune.put("lsa", 16, 8, {"impl": "fused"})
    assert autotune.enable_autotune(str(a)) == str(a)   # no-op
    assert autotune.lookup("lsa", 16, 8, count=False) is not None
    autotune.enable_autotune(str(b))                    # re-point: empty
    assert autotune.lookup("lsa", 16, 8, count=False) is None
    autotune.enable_autotune(str(a))                    # back: reloaded
    assert autotune.lookup("lsa", 16, 8, count=False) is not None


# --------------------------------------------------------------- dispatch

def test_resolve_config_uses_table_winners():
    autotune.put("lsa", 16, 64, {"impl": "fused", "tile_u": 8})
    autotune.put("bma", 16, 64, {"impl": "unfused"})
    autotune.put("merge", 1024, 128, {"impl": "fused"})
    cfg = EngineConfig(use_kernel="auto")
    r = autotune.resolve_config(cfg, slots=16, batch=8)   # b_eff = 64
    assert r.use_kernel == "auto"
    assert r.dispatch == KernelDispatch(
        lsa_fused=True, lsa_tile_u=8, bma_fused=False, merge_fused=True)
    # non-auto configs pass through untouched
    cfg2 = EngineConfig(use_kernel=True)
    assert autotune.resolve_config(cfg2, 16, 8) is cfg2


def test_resolve_config_untuned_falls_back_to_heuristic():
    cfg = EngineConfig(use_kernel="auto")
    r = autotune.resolve_config(cfg, slots=16, batch=8)
    assert r.dispatch == autotune.static_heuristic(16)
    if autotune.pallas_interpret():
        # the CPU footgun fix: interpret-mode pallas never wins by default
        assert r.dispatch == KernelDispatch()


def test_resolve_config_neutralises_hostile_tiles():
    # a hand-edited table entry whose tile doesn't divide the bucket
    autotune.put("lsa", 16, 64, {"impl": "fused", "tile_u": 7})
    autotune.put("bma", 16, 64, {"impl": "fused", "tile_v": "x",
                                 "tile_u": -8})
    r = autotune.resolve_config(EngineConfig(use_kernel="auto"), 16, 8)
    assert r.dispatch.lsa_fused and r.dispatch.lsa_tile_u == 0
    assert r.dispatch.bma_fused and r.dispatch.bma_tile_v == 0 \
        and r.dispatch.bma_tile_u == 0


def test_concrete_dispatch_is_pure_in_cfg():
    # booleans map to global on/off regardless of the table
    autotune.put("lsa", 16, 8, {"impl": "unfused"})
    on = autotune.concrete_dispatch(EngineConfig(use_kernel=True), 16)
    assert on.lsa_fused and on.bma_fused and not on.merge_fused
    off = autotune.concrete_dispatch(EngineConfig(use_kernel=False), 16)
    assert off == KernelDispatch()
    # a resolved dispatch wins over everything
    d = KernelDispatch(merge_fused=True)
    cfg = EngineConfig(use_kernel="auto", dispatch=d)
    assert autotune.concrete_dispatch(cfg, 16) is d
    # tiles that do not divide the bucket's axis fall back to the default
    tiled = KernelDispatch(lsa_fused=True, lsa_tile_u=8, bma_fused=True,
                           bma_tile_v=8, bma_tile_u=8)
    cfg3 = EngineConfig(use_kernel="auto", dispatch=tiled)
    assert autotune.concrete_dispatch(cfg3, 16) is tiled
    assert autotune.concrete_dispatch(cfg3, 4) == KernelDispatch(
        lsa_fused=True, bma_fused=True)
    # unresolved "auto" at trace time -> the static heuristic, never the
    # table (the jit cache keys on cfg, not on mutable table state)
    cfg2 = EngineConfig(use_kernel="auto")
    assert autotune.concrete_dispatch(cfg2, 16) == \
        autotune.static_heuristic(16)


def test_engine_config_validates_use_kernel():
    with pytest.raises(ValueError):
        EngineConfig(use_kernel="fast")
    # the three legal values construct fine
    for v in (True, False, "auto"):
        assert EngineConfig(use_kernel=v).use_kernel == v


def test_tune_shape_records_measured_winner():
    ent = autotune.tune_shape("lsa", 8, 4, tiles=((0, 0),), budget_s=0.01)
    assert ent["impl"] in ("fused", "unfused")
    assert ent["us"] == min(ent["fused_us"], ent["unfused_us"])
    assert autotune.lookup("lsa", 8, 4, count=False) is ent or \
        autotune.lookup("lsa", 8, 4, count=False) == ent
    assert autotune.autotune_stats()["autotune_sweep_s"] > 0


# ------------------------------------------------- engine parity (the gate)

_DISPATCHES = [
    KernelDispatch(),                                        # all unfused
    KernelDispatch(lsa_fused=True, bma_fused=True),          # default tiles
    KernelDispatch(lsa_fused=True, lsa_tile_u=8,
                   bma_fused=True, bma_tile_v=8, bma_tile_u=8),  # tuned
    KernelDispatch(merge_fused=True),                        # fused merge
    KernelDispatch(lsa_fused=True, bma_fused=True,
                   merge_fused=True),                        # everything
]


@pytest.mark.parametrize("strategy", ["astar", "dfs"])
def test_engine_bit_identical_across_dispatch_compute(strategy):
    """Every dispatch decision must yield byte-identical engine output —
    the whole dict, not just the distance (the kernels are exact vs their
    oracles and the merge kernel computes identical integer ranks)."""
    pairs = _make_pairs(23, 6)
    t = pack_pairs(pairs, slots=16)
    base = dict(pool=128, expand=4, max_iters=128, strategy=strategy)
    ref = ged_batch(t, EngineConfig(use_kernel=False, **base))
    for d in _DISPATCHES:
        cfg = EngineConfig(use_kernel="auto", dispatch=d, **base)
        out = ged_batch(t, cfg)
        assert set(out) == set(ref)
        for key in out:
            assert np.array_equal(out[key], ref[key]), (strategy, d, key)


@pytest.mark.parametrize("strategy", ["astar", "dfs"])
def test_engine_bit_identical_across_dispatch_verify(strategy):
    pairs = _make_pairs(27, 6)
    t = pack_pairs(pairs, slots=16)
    taus = np.asarray([2.0, 3.0, 2.0, 4.0, 1.0, 3.0], np.float32)
    base = dict(pool=128, expand=4, max_iters=128, strategy=strategy)
    ref = verify_batch(t, taus, EngineConfig(use_kernel=False, **base))
    for d in (_DISPATCHES[2], _DISPATCHES[4]):
        cfg = EngineConfig(use_kernel="auto", dispatch=d, **base)
        out = verify_batch(t, taus, cfg)
        for key in out:
            assert np.array_equal(out[key], ref[key]), (strategy, d, key)


def test_dispatch_never_changes_outcome_property():
    """Hypothesis: for random pairs and random dispatch plans, every
    ``GedOutcome`` field through the public facade is invariant."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro import ged

    # draw from a fixed palette so jit compilations are shared across
    # examples (each distinct cfg is its own trace)
    palette = st.sampled_from(_DISPATCHES)

    base = ged.GedEngine("jax", cache=False, pool=128, max_iters=128)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2 ** 10), d=palette)
    def check(seed, d):
        pairs = _make_pairs(seed, 3, nmin=4, nmax=8)
        eng = ged.GedEngine("jax", use_kernel="auto", cache=False,
                            pool=128, max_iters=128, dispatch=d)
        oa = eng.compute(pairs)
        ob = base.compute(pairs)
        for a, b in zip(oa, ob):
            assert (a.ged, a.similar, a.certified, a.lower_bound,
                    a.upper_bound) == (b.ged, b.similar, b.certified,
                                       b.lower_bound, b.upper_bound)
            assert np.array_equal(a.mapping, b.mapping)

    check()


# ----------------------------------------------------------------- facade

def test_facade_accepts_auto_on_every_backend():
    from repro import ged
    pairs = _make_pairs(3, 3, nmin=4, nmax=7)
    outs = {}
    for backend in ("jax", "pallas", "exact"):
        eng = ged.GedEngine(backend, use_kernel="auto", cache=False,
                            pool=128, max_iters=128)
        outs[backend] = [(o.ged, o.certified) for o in eng.compute(pairs)]
    assert outs["jax"] == outs["pallas"] == outs["exact"]
    # contradicting booleans still raise
    with pytest.raises(ValueError):
        ged.GedEngine("jax", use_kernel=True)
    with pytest.raises(ValueError):
        ged.GedEngine("pallas", use_kernel=False)


def test_facade_stats_surface_autotune_and_interpret(tmp_path):
    from repro import ged
    eng = ged.GedEngine("jax", use_kernel="auto", cache=False,
                        autotune_dir=str(tmp_path), pool=128,
                        max_iters=128)
    assert eng.autotune_dir == str(tmp_path)
    eng.compute(_make_pairs(5, 2, nmin=4, nmax=7))
    s = eng.stats
    for key in ("autotune_hits", "autotune_misses", "autotune_sweep_s",
                "autotune_entries", "pallas_interpret"):
        assert key in s, key
    # untuned shapes miss into the heuristic and are counted
    assert s["autotune_misses"] >= 1
    import jax
    assert s["pallas_interpret"] == (jax.default_backend() != "tpu")


def test_facade_auto_resolution_keys_compile_cache(tmp_path):
    """Two buckets, one engine: each resolves its own dispatch, and the
    executor's compile ledger sees the resolved configs."""
    from repro import ged
    autotune.enable_autotune(str(tmp_path))
    # make slots-8 buckets prefer a fused merge, leave slots-16 untuned
    autotune.put("merge", 1024, 64, {"impl": "fused"})
    eng = ged.GedEngine("jax", use_kernel="auto", cache=False,
                        pool=128, max_iters=128)
    small = _make_pairs(7, 2, nmin=4, nmax=7)
    big = _make_pairs(9, 2, nmin=10, nmax=13)
    outs = eng.compute(small + big)
    assert len(outs) == 4
    ref = ged.GedEngine("jax", cache=False, pool=128, max_iters=128)
    want = ref.compute(small + big)
    for a, b in zip(outs, want):
        assert (a.ged, a.certified) == (b.ged, b.certified)
