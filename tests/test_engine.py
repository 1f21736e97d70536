"""Batched JAX engine vs the exact paper-faithful solver."""

import numpy as np
import pytest

from repro.core.engine import EngineConfig, ged_batch, pack_pairs, verify_batch
from repro.core.engine import auction as auc
from repro.core.exact.assignment import hungarian
from repro.core.exact.search import ged as exact_ged

import jax.numpy as jnp

from repro.data.graphs import perturb, random_graph


def _make_pairs(seed, count, nmin=4, nmax=9, ops=5):
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(count):
        n = int(rng.integers(nmin, nmax))
        q = random_graph(rng, n, density=0.35, n_vlabels=3, n_elabels=2)
        if rng.random() < 0.5:
            g = perturb(rng, q, int(rng.integers(0, ops)), n_vlabels=3, n_elabels=2)
        else:
            g = random_graph(rng, int(rng.integers(nmin, nmax)),
                             density=0.35, n_vlabels=3, n_elabels=2)
        pairs.append((q, g))
    return pairs


# ----------------------------------------------------------------- auction
def test_auction_dual_bound_is_admissible():
    rng = np.random.default_rng(3)
    for _ in range(20):
        n = int(rng.integers(2, 10))
        cost = (rng.integers(0, 12, size=(n, n)) * 0.5).astype(np.float32)
        _, opt = hungarian(cost)
        c = jnp.asarray(cost)[None]
        for sweeps in (0, 1, 4, 16, 64):
            st = auc.run_auction(c, sweeps)
            lb = float(auc.dual_bound(c, st.prices)[0])
            assert lb <= opt + 1e-4, f"sweeps={sweeps}: {lb} > {opt}"
        # enough sweeps should reach (near-)optimality via the dual
        st = auc.run_auction(c, 4 * n + 16)
        lb = float(auc.dual_bound(c, st.prices)[0])
        assert lb >= opt - n * 0.25 - 1e-3


def test_auction_forced_dual_bounds_admissible():
    rng = np.random.default_rng(5)
    for _ in range(15):
        n = int(rng.integers(2, 8))
        cost = (rng.integers(0, 12, size=(n, n)) * 0.5).astype(np.float32)
        row = int(rng.integers(0, n))
        c = jnp.asarray(cost)[None]
        st = auc.run_auction(c, 24)
        forced = np.asarray(
            auc.forced_dual_bounds(c, st.prices, jnp.asarray([row]))
        )[0]
        # oracle: exact forced optimum per column
        from repro.core.exact.assignment import solve_forced_all
        want, _, _ = solve_forced_all(cost.astype(float), row)
        assert np.all(forced <= want + 1e-3), (forced, want)


def test_greedy_primal_is_permutation():
    rng = np.random.default_rng(7)
    for _ in range(10):
        n = int(rng.integers(2, 12))
        cost = jnp.asarray(rng.random((1, n, n)), jnp.float32)
        st = auc.run_auction(cost, 8)
        perm = np.asarray(auc.greedy_primal(cost, st.prices))[0]
        assert sorted(perm.tolist()) == list(range(n))


# ------------------------------------------------------------------ engine
@pytest.mark.parametrize("bound,min_exact", [("lsa", 0.9), ("bma", 0.75),
                                             ("hybrid", 0.9)])
def test_engine_matches_exact_ged(bound, min_exact):
    pairs = _make_pairs(11, 12)
    t = pack_pairs(pairs, slots=16)
    cfg = EngineConfig(pool=1024, expand=4, max_iters=1024, sweeps=12,
                       bound=bound)
    out = ged_batch(t, cfg)
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    ok = out["exact"]
    # certified results must be right; the certificate must usually fire
    # (pure-bma dual bounds are looser -> more conservative certificates)
    assert np.array_equal(out["ged"][ok].astype(int), want[ok]), (out, want)
    assert ok.mean() >= min_exact, out


def test_engine_dfs_strategy_matches():
    pairs = _make_pairs(13, 8)
    t = pack_pairs(pairs, slots=16)
    cfg = EngineConfig(pool=1024, expand=4, max_iters=2048, sweeps=8,
                       bound="hybrid", strategy="dfs")
    out = ged_batch(t, cfg)
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    ok = out["exact"]
    assert np.all(out["ged"][ok].astype(int) == want[ok])
    assert ok.mean() >= 0.9


def test_engine_verification_matches_exact():
    pairs = _make_pairs(17, 10)
    t = pack_pairs(pairs, slots=16)
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    for delta in (-1, 0, 1):
        taus = np.maximum(want + delta, 0).astype(np.float32)
        out = verify_batch(t, taus, EngineConfig(pool=512, expand=4,
                                                 max_iters=512, sweeps=8))
        expect = want <= taus
        assert np.all(out["exact"])
        assert np.array_equal(out["similar"], expect), (delta, out, want)


def test_engine_certificate_detects_truncation():
    """With a pathologically small budget, inexact results must be flagged."""
    pairs = _make_pairs(19, 6, nmin=8, nmax=10, ops=8)
    t = pack_pairs(pairs, slots=16)
    cfg = EngineConfig(pool=16, expand=2, max_iters=3, sweeps=2, bound="lsa")
    out = ged_batch(t, cfg)
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    wrong = out["ged"].astype(int) != want
    # every wrong answer must carry exact=False
    assert not np.any(wrong & out["exact"]), (out["ged"], want, out["exact"])


@pytest.mark.parametrize("strategy", ["astar", "dfs"])
@pytest.mark.parametrize("bound", ["lsa", "hybrid"])
def test_engine_kernel_and_reference_paths_bit_identical(strategy, bound):
    """use_kernel=True/False must produce bit-identical engine outputs —
    every field, not just the distance: the fused kernels compute the very
    same bound values (small-half float arithmetic is exact), so the whole
    search trajectory must match."""
    pairs = _make_pairs(23, 6)
    t = pack_pairs(pairs, slots=16)
    base = dict(pool=256, expand=4, bound=bound, strategy=strategy)
    out_k = ged_batch(t, EngineConfig(use_kernel=True, **base))
    out_r = ged_batch(t, EngineConfig(use_kernel=False, **base))
    assert set(out_k) == set(out_r)
    for key in out_k:
        assert np.array_equal(out_k[key], out_r[key]), (strategy, bound, key)


@pytest.mark.parametrize("strategy", ["astar", "dfs"])
def test_engine_kernel_paths_bit_identical_verification(strategy):
    pairs = _make_pairs(27, 6)
    t = pack_pairs(pairs, slots=16)
    taus = np.asarray([2.0, 3.0, 2.0, 4.0, 1.0, 3.0], np.float32)
    base = dict(pool=256, expand=4, strategy=strategy)
    out_k = verify_batch(t, taus, EngineConfig(use_kernel=True, **base))
    out_r = verify_batch(t, taus, EngineConfig(use_kernel=False, **base))
    assert set(out_k) == set(out_r)
    for key in out_k:
        assert np.array_equal(out_k[key], out_r[key]), (strategy, key)


def test_engine_kernel_paths_bit_identical_pad_heavy():
    """Small graphs rattling around big slot buckets: PAD slots dominate
    and the kernels must mask them exactly like the reference path."""
    pairs = _make_pairs(31, 5, nmin=3, nmax=6)
    t = pack_pairs(pairs, slots=32)
    out_k = ged_batch(t, EngineConfig(pool=128, expand=4, use_kernel=True))
    out_r = ged_batch(t, EngineConfig(pool=128, expand=4, use_kernel=False))
    for key in out_k:
        assert np.array_equal(out_k[key], out_r[key]), key
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    ok = out_k["exact"]
    assert ok.mean() >= 0.8
    assert np.array_equal(out_k["ged"][ok].astype(int), want[ok])


def test_engine_identical_graphs_zero():
    rng = np.random.default_rng(29)
    pairs = [(g, g.copy()) for g in
             (random_graph(rng, n, 0.3) for n in (4, 6, 9, 12))]
    t = pack_pairs(pairs, slots=16)
    out = ged_batch(t, EngineConfig(pool=128, expand=2))
    assert np.all(out["ged"] == 0)
    assert np.all(out["exact"])


def test_engine_unequal_sizes():
    rng = np.random.default_rng(31)
    pairs = []
    for _ in range(6):
        q = random_graph(rng, int(rng.integers(3, 6)), 0.4, 3, 2)
        g = random_graph(rng, int(rng.integers(6, 10)), 0.3, 3, 2)
        pairs.append((q, g))
    t = pack_pairs(pairs, slots=16)
    out = ged_batch(t, EngineConfig(pool=1024, expand=8, max_iters=1024))
    want = np.array([exact_ged(q, g, bound="BMa").ged for q, g in pairs])
    ok = out["exact"]
    assert ok.mean() >= 0.8
    assert np.array_equal(out["ged"][ok].astype(int), want[ok])


def test_bma_bound_admissible_when_graphs_fill_the_bucket():
    """A graph that fills its slot bucket leaves a single dummy row and
    column after its first anchor.  Bids against that row's forbidden
    second-best column once inflated a price to ~1e7, where f32 rounding
    made the forced dual bound overshoot OPT: a certified wrong reject
    (pair 3 here).  Capped bids keep the bound exact and admissible."""
    from repro import ged
    from repro.core.exact.search import ged_verify
    from repro.data.graphs import aids_like_graph
    rng = np.random.default_rng(0)
    pairs, taus = [], []
    for _ in range(4):
        q = aids_like_graph(rng, int(rng.choice([16, 32])))
        k = int(rng.integers(1, 5))
        pairs.append((q, perturb(rng, q, k, 62, 3)))
        taus.append(float(k))
    outs = ged.GedEngine("jax", slots=32, cache=False).verify(pairs, taus)
    for i, (o, (q, g), tau) in enumerate(zip(outs, pairs, taus)):
        assert o.certified and o.similar == ged_verify(q, g, tau).similar, i
