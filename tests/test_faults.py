"""Chaos matrix for the robustness layer (``docs/robustness.md``).

The contract under test, in order of importance:

1. **Never a wrong answer.**  No injected fault or deadline may flip an
   answer — a degraded or timed-out outcome is uncertified or carries
   admissible bounds that bracket the true GED (checked against the
   brute-force oracle), and every *certified* outcome matches the
   fault-free run exactly.
2. **Always an answer.**  Faults and expired deadlines produce valid
   :class:`~repro.ged.GedOutcome` rows for every pair — never an
   exception out of ``compute``/``verify``.
3. **No poisoned caches.**  Timed-out or uncertified-degraded outcomes
   must not enter the result caches (in-memory or shared).
4. **Bit-identity without faults.**  The robustness plumbing is inert
   when no deadline is set and no fault fires.
"""

import os

import numpy as np
import pytest

from repro import ged
from repro.core.exact.brute import brute_force_ged
from repro.data.graphs import random_graph
from repro.ged.faults import (Deadline, FaultInjector, Overloaded,
                              RetryPolicy, cheap_lower_bound,
                              install_injector)
from repro.store_io.atomic import LockTimeout, file_lock

ENGINE_OPTS = dict(slots=16, batch_size=8, pool=64, expand=4,
                   max_iters=256, cache=False)


def _pairs(n=6, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        q = random_graph(rng, int(rng.integers(2, 6)), density=0.5,
                         n_vlabels=2, n_elabels=2)
        g = random_graph(rng, int(rng.integers(2, 6)), density=0.5,
                         n_vlabels=2, n_elabels=2)
        out.append((q, g))
    return out


@pytest.fixture(autouse=True)
def _no_global_injector():
    install_injector(None)
    yield
    install_injector(None)


def _truths(pairs):
    return [float(brute_force_ged(q, g)) for q, g in pairs]


def _assert_sound(outs, truths, taus=None):
    for i, (o, t) in enumerate(zip(outs, truths)):
        if not (o.certified and taus is not None):
            # Certified verification verdicts may carry the engine's
            # tau-prune sentinel as lower_bound (evidence that lb > tau,
            # not a global bound); everything else — compute outcomes,
            # timed-out and degraded fallbacks — must bracket the truth.
            assert o.lower_bound <= t + 1e-9, (i, o.lower_bound, t)
            assert o.upper_bound >= t - 1e-9, (i, o.upper_bound, t)
        if o.certified and o.ged is not None:
            assert o.ged == pytest.approx(t), (i, o.ged, t)
        if taus is not None and o.similar is not None:
            assert o.similar == (t <= taus[i] + 1e-9), (i, o.similar, t)


# ----------------------------------------------------------- deadlines


def test_expired_deadline_exact_backend_answers_soundly():
    pairs = _pairs()
    truths = _truths(pairs)
    eng = ged.GedEngine("exact", deadline_s=0.0, cache=False)
    outs = eng.compute(pairs)
    assert len(outs) == len(pairs)
    for o in outs:
        assert o.timed_out and not o.certified
    _assert_sound(outs, truths)
    assert eng.stats["timed_out_pairs"] == len(pairs)


def test_expired_deadline_auto_backend_answers_soundly():
    pairs = _pairs()
    truths = _truths(pairs)
    taus = [1.0] * len(pairs)
    eng = ged.GedEngine("auto", deadline_s=0.0, **ENGINE_OPTS)
    outs = eng.verify(pairs, taus)
    assert len(outs) == len(pairs)
    assert all(o.timed_out and not o.certified for o in outs)
    _assert_sound(outs, truths, taus)


def test_mid_run_deadline_auto_keeps_rung_bounds():
    # A short-but-nonzero budget with a forced multi-rung ladder: some
    # pairs certify in time, the rest must carry admissible best-so-far
    # bounds from the rungs that did run.
    pairs = _pairs(10, seed=11)
    truths = _truths(pairs)
    taus = [2.0] * len(pairs)
    eng = ged.GedEngine("auto", **ENGINE_OPTS)
    eng._backend.scheduler.rungs = ((8, 1, 4), (64, 4, 64))
    outs = eng.verify(pairs, taus, deadline_s=0.05)
    assert len(outs) == len(pairs)
    _assert_sound(outs, truths, taus)
    for o in outs:
        assert o.certified or o.timed_out


def test_per_pair_deadline_on_host_solver():
    pairs = _pairs(4, seed=5)
    truths = _truths(pairs)
    eng = ged.GedEngine("exact", per_pair_deadline_s=0.0, cache=False)
    outs = eng.compute(pairs)
    for o in outs:
        assert o.timed_out and not o.certified
    _assert_sound(outs, truths)


def test_no_deadline_bit_identity():
    pairs = _pairs()
    taus = [1.0] * len(pairs)
    plain = ged.GedEngine("auto", **ENGINE_OPTS).verify(pairs, taus)
    roomy = ged.GedEngine("auto", deadline_s=3600.0,
                          **ENGINE_OPTS).verify(pairs, taus)
    for a, b in zip(plain, roomy):
        assert (a.similar, a.certified, a.ged, a.lower_bound,
                a.upper_bound) == (b.similar, b.certified, b.ged,
                                   b.lower_bound, b.upper_bound)
        assert not a.timed_out and not b.timed_out


def test_deadline_object_is_shared_across_flush():
    d = Deadline(3600.0)
    assert not d.expired() and d.remaining() > 3599.0
    child = d.sub(1.0)
    assert child.remaining() <= 1.0
    inherit = d.sub(None)
    assert inherit.t_end == d.t_end
    assert Deadline(0.0).expired()
    assert not Deadline(None).expired()


# -------------------------------------------------------------- faults


def test_transient_dispatch_fault_retries_to_identical_answers():
    pairs = _pairs()
    taus = [1.0] * len(pairs)
    clean = ged.GedEngine("jax", **ENGINE_OPTS).verify(pairs, taus)
    eng = ged.GedEngine("jax", fault_inject="dispatch@times=1,kind=transient",
                        retry=RetryPolicy(max_retries=2, base_s=0.0),
                        **ENGINE_OPTS)
    outs = eng.verify(pairs, taus)
    assert eng.stats["retries"] == 1
    for a, b in zip(clean, outs):
        assert (a.similar, a.certified, a.lower_bound, a.upper_bound) == \
            (b.similar, b.certified, b.lower_bound, b.upper_bound)


def test_permanent_dispatch_fault_degrades_to_host():
    pairs = _pairs()
    taus = [1.0] * len(pairs)
    truths = _truths(pairs)
    clean = ged.GedEngine("jax", **ENGINE_OPTS).verify(pairs, taus)
    eng = ged.GedEngine("jax", fault_inject="dispatch@times=inf",
                        retry=RetryPolicy(max_retries=1, base_s=0.0),
                        **ENGINE_OPTS)
    outs = eng.verify(pairs, taus)
    assert eng.stats["degraded_host"] == len(pairs)
    _assert_sound(outs, truths, taus)
    for a, b in zip(clean, outs):
        # host fallback is exact: verdicts agree, flagged degraded.
        assert a.similar == b.similar and b.certified and b.degraded


def test_kernel_fault_degrades_to_unfused_bit_identical():
    pairs = _pairs()
    taus = [1.0] * len(pairs)
    clean = ged.GedEngine("pallas", **ENGINE_OPTS).verify(pairs, taus)
    eng = ged.GedEngine("pallas", fault_inject="kernel@times=inf",
                        retry=RetryPolicy(max_retries=0, base_s=0.0),
                        **ENGINE_OPTS)
    outs = eng.verify(pairs, taus)
    assert eng.stats.get("degraded_kernel", 0) >= 1
    for a, b in zip(clean, outs):
        # unfused path is bit-identical, so certification survives.
        assert (a.similar, a.certified, a.lower_bound, a.upper_bound) == \
            (b.similar, b.certified, b.lower_bound, b.upper_bound)


def test_result_site_fault_recovers_via_refused_redispatch():
    pairs = _pairs()
    taus = [1.0] * len(pairs)
    clean = ged.GedEngine("pallas", **ENGINE_OPTS).verify(pairs, taus)
    eng = ged.GedEngine("pallas", fault_inject="result@times=1",
                        **ENGINE_OPTS)
    outs = eng.verify(pairs, taus)
    for a, b in zip(clean, outs):
        assert a.similar == b.similar and b.certified


def test_host_fault_yields_uncertified_sound_floor():
    pairs = _pairs(3, seed=9)
    truths = _truths(pairs)
    eng = ged.GedEngine("exact", fault_inject="host@times=inf",
                        cache=False)
    outs = eng.compute(pairs)
    for o in outs:
        assert not o.certified and o.degraded
    _assert_sound(outs, truths)
    assert eng.stats["fault_host"] == len(pairs)


def test_rung_scoped_fault_leaves_other_rungs_alone():
    pairs = _pairs(8, seed=21)
    taus = [2.0] * len(pairs)
    truths = _truths(pairs)
    clean_eng = ged.GedEngine("auto", **ENGINE_OPTS)
    clean_eng._backend.scheduler.rungs = ((8, 1, 4), (64, 4, 64))
    clean = clean_eng.verify(pairs, taus)
    eng = ged.GedEngine("auto", fault_inject="dispatch@rung=1,times=inf",
                        retry=RetryPolicy(max_retries=0, base_s=0.0),
                        **ENGINE_OPTS)
    eng._backend.scheduler.rungs = ((8, 1, 4), (64, 4, 64))
    outs = eng.verify(pairs, taus)
    _assert_sound(outs, truths, taus)
    for a, b in zip(clean, outs):
        assert a.similar == b.similar and b.certified


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_kernel_lowering_refusal_propagates(monkeypatch, backend):
    """A kernel the Pallas lowering refuses is a fault in the program: it
    propagates out of compute/verify as CompileRefusal — no retry, no
    unfused fallback, no host-solver rescue.  Steering the kernels off
    interpret mode on the CPU makes the lowering refuse them for real."""
    import jax

    from repro.kernels import ops
    pairs = _pairs(3)
    eng = ged.GedEngine(backend, use_kernel=True, **ENGINE_OPTS)
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    try:
        with pytest.raises(ged.CompileRefusal) as ei:
            eng.verify(pairs, 1.0)
    finally:
        monkeypatch.undo()
        jax.clear_caches()       # no trace with interpret=False may leak
    assert ei.value.__cause__ is not None
    stats = eng.stats
    assert not any(stats.get(k) for k in ("retries", "degraded_kernel",
                                          "degraded_host",
                                          "executor_degraded_kernel",
                                          "executor_retries"))
    # the same engine answers once the kernels lower again
    assert len(eng.verify(pairs, 1.0)) == len(pairs)


def test_fault_injector_spec_parsing():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultInjector("badsite@times=1")
    with pytest.raises(ValueError, match="unknown fault-spec key"):
        FaultInjector("dispatch@nope=1")
    inj = FaultInjector("dispatch@times=2,rung=1;lock")
    inj.check("dispatch", rung=0)                 # rung mismatch: no-op
    with pytest.raises(Exception):
        inj.check("dispatch", rung=1)
    with pytest.raises(Exception):
        inj.check("lock")
    inj.check("lock")                             # budget spent
    assert inj.fired == 2


def test_env_injector_pickup(monkeypatch):
    from repro.ged.faults import get_injector
    monkeypatch.setenv("REPRO_GED_FAULT_INJECT", "host@times=1")
    inj = get_injector()
    assert inj is not None
    monkeypatch.delenv("REPRO_GED_FAULT_INJECT")
    assert get_injector() is None


# ------------------------------------------------------ cache hygiene


def test_timed_out_outcomes_do_not_poison_caches(tmp_path):
    pairs = _pairs(3, seed=7)
    truths = _truths(pairs)
    eng = ged.GedEngine("exact", cache_size=64,
                        shared_cache_dir=str(tmp_path))
    bad = eng.compute(pairs, deadline_s=0.0)
    assert all(o.timed_out for o in bad)
    # Same engine, no deadline: must re-solve, not replay the fallback.
    good = eng.compute(pairs)
    for o, t in zip(good, truths):
        assert o.certified and o.ged == pytest.approx(t)
    # Shared tier never saw the uncertified rows either.
    fresh = ged.GedEngine("exact", cache_size=0,
                          shared_cache_dir=str(tmp_path))
    again = fresh.compute(pairs)
    for o, t in zip(again, truths):
        assert o.ged == pytest.approx(t)


# ------------------------------------------------- lock timeouts (io)


def test_file_lock_timeout_raises_lock_timeout(tmp_path):
    import fcntl
    path = str(tmp_path / "lk")
    held = open(path, "a+")
    fcntl.flock(held.fileno(), fcntl.LOCK_EX)
    try:
        with pytest.raises(LockTimeout):
            with file_lock(path, timeout=0.05, poll_s=0.01):
                pass
    finally:
        fcntl.flock(held.fileno(), fcntl.LOCK_UN)
        held.close()
    with file_lock(path, timeout=0.05):           # released: acquires
        pass


def test_shared_cache_lock_timeout_fails_open(tmp_path):
    from repro.ged.results import GedOutcome
    from repro.store_io.shared_cache import SharedResultCache
    install_injector(FaultInjector("lock@times=1"))
    cache = SharedResultCache(str(tmp_path), lock_timeout_s=0.05)
    key = ("exact", b"q", b"g", False, None, None, "jax")
    out = GedOutcome(ged=2.0, similar=None, certified=True,
                     lower_bound=2.0, upper_bound=2.0, mapping=None,
                     backend="jax", wall_s=0.0)
    assert cache.put(key, out)                    # fail-open write
    assert cache.lock_timeouts == 1
    hit = cache.get(key)
    assert hit is not None and hit.ged == 2.0
    assert cache.stats["lock_timeouts"] == 1.0


def test_engine_surfaces_lock_timeout_stat(tmp_path):
    eng = ged.GedEngine("exact", shared_cache_dir=str(tmp_path),
                        fault_inject="lock@times=1")
    install_injector(FaultInjector("lock@times=1"))
    eng.compute(_pairs(1))
    assert eng.stats["shared_cache_lock_timeouts"] >= 1.0


# ------------------------------------------------------------ serving


def test_admission_control_sheds_and_recovers():
    from repro.serving.ged_service import AdmissionController
    ac = AdmissionController(capacity=4)
    with ac.admit(3):
        with pytest.raises(Overloaded) as ei:
            with ac.admit(2):
                pass
    err = ei.value
    assert err.retry_after_s > 0 and err.capacity == 4
    with ac.admit(2):                             # drained: admits again
        pass
    with ac.admit(100):                           # oversized-but-idle
        pass
    h = ac.health
    assert h["shed"] == 1 and h["queue_depth"] == 0
    assert h["p99_wall_s"] >= h["p50_wall_s"] >= 0


def test_service_deadline_and_health():
    from repro.serving.ged_service import (GedRequest,
                                           GedVerificationService)
    svc = GedVerificationService(batch_size=8, slots=16, capacity=16)
    pairs = _pairs(3, seed=13)
    truths = _truths(pairs)
    reqs = [GedRequest(q, g, tau=1.0, deadline_s=0.0) for q, g in pairs]
    outs = svc.verify(reqs)
    _assert_sound(outs, truths, [1.0] * len(pairs))
    for o in outs:
        assert o.timed_out or o.certified     # cache hits may certify
    h = svc.health()
    assert h["admitted"] == 1 and "p99_wall_s" in h
    assert h["timed_out_pairs"] >= 1


# ----------------------------------------------------------- property


def _bound_property(seed, budget):
    rng = np.random.default_rng(seed)
    pairs = [(random_graph(rng, int(rng.integers(2, 6)), density=0.5,
                           n_vlabels=2, n_elabels=2),
              random_graph(rng, int(rng.integers(2, 6)), density=0.5,
                           n_vlabels=2, n_elabels=2)) for _ in range(3)]
    truths = _truths(pairs)
    eng = ged.GedEngine("auto", deadline_s=budget, **ENGINE_OPTS)
    outs = eng.verify(pairs, [1.0] * len(pairs))
    for o, t in zip(outs, truths):
        if not o.certified:     # see _assert_sound on certified verdicts
            assert o.lower_bound <= t + 1e-9 <= o.upper_bound + 2e-9, \
                (seed, budget, o.lower_bound, t, o.upper_bound)
        assert o.certified or o.timed_out or o.degraded
        assert cheap_lower_bound(*pairs[0]) >= 0


def test_bounds_bracket_truth_under_seeded_deadline_sweep():
    for seed in (0, 1, 2, 3):
        for budget in (0.0, 0.002, 0.02, 3600.0):
            _bound_property(seed, budget)


def test_bounds_bracket_truth_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 10_000),
           budget=st.floats(0.0, 0.05, allow_nan=False))
    def run(seed, budget):
        _bound_property(seed, budget)

    run()
