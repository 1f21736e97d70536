"""The ``repro.ged`` facade: backend parity, bucketed compile reuse,
ingestion adapters, streaming, the sharded executor, the engine-level
result cache, and the unified result schema."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import ged
from repro.core.engine.api import run_batch_traces
from repro.core.exact.brute import brute_force_ged
from repro.core.exact.graph import Graph
from repro.data.graphs import perturb, random_graph


def _small_pairs(seed, count, nmin=3, nmax=6):
    rng = np.random.default_rng(seed)
    pairs = []
    for _ in range(count):
        q = random_graph(rng, int(rng.integers(nmin, nmax + 1)),
                         density=0.4, n_vlabels=3, n_elabels=2)
        if rng.random() < 0.5:
            g = perturb(rng, q, int(rng.integers(0, 4)),
                        n_vlabels=3, n_elabels=2)
        else:
            g = random_graph(rng, int(rng.integers(nmin, nmax + 1)),
                             density=0.4, n_vlabels=3, n_elabels=2)
        pairs.append((q, g))
    return pairs


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("backend", ["exact", "jax", "auto"])
def test_backend_matches_brute_force_oracle(backend):
    pairs = _small_pairs(0, 10)
    truth = [brute_force_ged(q, g) for q, g in pairs]
    outs = ged.GedEngine(backend, pool=1024, expand=4,
                         max_iters=1024).compute(pairs)
    for o, t in zip(outs, truth):
        assert o.certified
        assert o.ged == t, (backend, o, t)


def test_exact_and_jax_backends_agree_everywhere():
    pairs = _small_pairs(1, 12)
    a = ged.GedEngine("exact").compute(pairs)
    b = ged.GedEngine("jax", pool=1024, expand=4, max_iters=1024
                      ).compute(pairs)
    for oa, ob in zip(a, b):
        assert ob.certified and oa.ged == ob.ged


def test_verification_parity_across_backends():
    pairs = _small_pairs(2, 8)
    truth = [brute_force_ged(q, g) for q, g in pairs]
    for delta in (-1, 0, 1):
        taus = [max(t + delta, 0) for t in truth]
        for backend in ("exact", "jax", "auto"):
            outs = ged.GedEngine(backend, pool=1024, expand=4,
                                 max_iters=1024).verify(pairs, taus)
            for o, t, tau in zip(outs, truth, taus):
                assert o.certified
                assert o.similar == (t <= tau), (backend, delta, o, t)


# ----------------------------------------------------------- result schema

def test_outcome_schema_and_bounds():
    pairs = _small_pairs(3, 6)
    for backend in ("exact", "jax", "auto"):
        for o in ged.GedEngine(backend, pool=1024).compute(pairs):
            assert o.similar is None and o.ged is not None
            assert o.lower_bound <= o.ged <= o.upper_bound
            assert o.backend.startswith(backend.split("/")[0])
            assert o.wall_s >= 0.0
            if o.certified:
                assert o.lower_bound == o.ged == o.upper_bound
                # a certified computation carries a witness mapping whose
                # image is a valid partial permutation
                assert o.mapping is not None
                img = o.mapping[o.mapping >= 0]
                assert len(set(img.tolist())) == len(img)
        for o in ged.GedEngine(backend, pool=1024).verify(pairs, 3.0):
            assert o.ged is None and o.similar is not None
            assert o.tau == 3.0


def test_mapping_cost_matches_ged():
    """The witness mapping is on the padded (q', g') pair and realises the
    reported distance."""
    from repro.core.exact.graph import editorial_cost, pad_pair
    pairs = _small_pairs(4, 6)
    for backend in ("exact", "jax"):
        outs = ged.GedEngine(backend, pool=1024, expand=4).compute(pairs)
        for (q, g), o in zip(pairs, outs):
            if not o.certified or o.mapping is None:
                continue
            qp, gp, _ = pad_pair(q, g)
            assert editorial_cost(qp, gp, o.mapping) == o.ged


# -------------------------------------------------------------- ingestion

def test_input_adapters_are_equivalent():
    q = Graph.from_edges([0, 1, 1], [(0, 1, 1), (1, 2, 2)])
    g = Graph.from_edges([0, 1, 2], [(0, 1, 1), (0, 2, 1)])
    as_tuple = ([0, 1, 1], [(0, 1, 1), (1, 2, 2)])
    as_dict = {"vlabels": [0, 1, 1], "edges": [(0, 1, 1), (1, 2, 2)]}
    as_adjdict = {"a": (0, [("b", 1)]),
                  "b": (1, [("a", 1), ("c", 2)]),
                  "c": (1, [("b", 2)])}
    want = ged.compute([(q, g)], backend="exact")[0].ged
    for form in (as_tuple, as_dict, as_adjdict):
        assert ged.compute([(form, g)], backend="exact")[0].ged == want


def test_adapter_rejects_garbage():
    with pytest.raises(TypeError):
        ged.compute([(42, 43)], backend="exact")


# -------------------------------------------------------------- streaming

def test_submit_flush_preserves_order_and_modes():
    pairs = _small_pairs(5, 5)
    truth = [brute_force_ged(q, g) for q, g in pairs]
    eng = ged.GedEngine("exact")
    tickets = []
    for i, (q, g) in enumerate(pairs):
        tau = float(truth[i]) if i % 2 else None  # alternate verify/compute
        tickets.append(eng.submit(q, g, tau=tau))
    assert tickets == list(range(len(pairs)))
    outs = eng.flush()
    assert len(outs) == len(pairs)
    for i, (o, t) in enumerate(zip(outs, truth)):
        if i % 2:
            assert o.similar is True and o.tau == t
        else:
            assert o.ged == t
    assert eng.flush() == []  # drained


# ------------------------------------------------- bucketing / compile cache

VOCAB = ((0, 1, 2), (1, 2))


def _sized_pairs(seed, sizes):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        q = random_graph(rng, n, density=0.4, n_vlabels=3, n_elabels=2)
        out.append((q, perturb(rng, q, 2, n_vlabels=3, n_elabels=2)))
    return out


def test_bucketing_reuses_compilations_across_batches():
    """Mixed-size workloads must compile once per slot bucket, then reuse."""
    eng = ged.GedEngine("jax", vocab=VOCAB, pool=128, expand=2, max_iters=64)
    # sizes 3..4 -> 4-slot bucket, 5..8 -> 8-slot bucket; 4 pairs per bucket
    batch1 = _sized_pairs(7, [3, 4, 5, 6, 4, 3, 7, 8])
    t0 = run_batch_traces()
    outs = eng.compute(batch1)
    assert len(outs) == len(batch1)
    new_traces = run_batch_traces() - t0
    assert new_traces == 2, f"expected one trace per bucket, got {new_traces}"

    # same buckets, different pairs and batch sizes (padded to pow2) -> no
    # new traces at all
    batch2 = _sized_pairs(8, [4, 5, 6, 3, 8, 5, 4])
    t1 = run_batch_traces()
    eng.compute(batch2)
    assert run_batch_traces() - t1 == 0, "same-bucket batch re-traced"
    assert eng.stats["compile_cache_hits"] >= 2


def test_bucketing_results_match_unbucketed():
    pairs = _sized_pairs(9, [3, 5, 8, 4, 6])
    bucketed = ged.GedEngine("jax", pool=512, expand=4).compute(pairs)
    pinned = ged.GedEngine("jax", slots=8, pool=512, expand=4).compute(pairs)
    for a, b in zip(bucketed, pinned):
        assert a.certified == b.certified
        if a.certified:
            assert a.ged == b.ged


def test_slot_bucket_is_pow2_and_monotone():
    assert [ged.slot_bucket(n) for n in (1, 3, 4, 5, 8, 9, 16, 17)] == \
        [4, 4, 4, 8, 8, 16, 16, 32]


# ------------------------------------------------------------- registry

def test_backend_registry_round_trip():
    assert set(ged.available_backends()) >= {"exact", "jax", "pallas",
                                             "sharded", "auto"}
    with pytest.raises(ValueError):
        ged.GedEngine("no-such-backend")

    class EchoBackend:
        name = "echo"

        def run(self, plan, taus, verification, cfg):
            from repro.ged.results import GedOutcome
            return [GedOutcome(ged=0.0, similar=None, certified=False,
                               lower_bound=0.0, upper_bound=0.0,
                               mapping=None, backend=self.name, wall_s=0.0)
                    for _ in plan.pairs]

    ged.register_backend("echo", EchoBackend)
    try:
        outs = ged.GedEngine("echo").compute(_small_pairs(10, 2))
        assert [o.backend for o in outs] == ["echo", "echo"]
    finally:
        from repro.ged import backends as B
        B._REGISTRY.pop("echo", None)


def test_module_level_one_shots():
    pairs = _small_pairs(11, 3)
    truth = [brute_force_ged(q, g) for q, g in pairs]
    outs = ged.compute(pairs, backend="auto")
    assert [o.ged for o in outs] == truth
    vers = ged.verify(pairs, truth, backend="auto")
    assert all(o.similar for o in vers)


# ------------------------------------------------- sharded executor layer

ENGINE_OPTS = dict(pool=256, expand=4, max_iters=256)


def test_sharded_backend_matches_jax_backend():
    """Same policy, different placement => identical outcomes (compute and
    verification), whatever the local device count."""
    pairs = _small_pairs(12, 10)
    a = ged.GedEngine("jax", **ENGINE_OPTS).compute(pairs)
    b = ged.GedEngine("sharded", **ENGINE_OPTS).compute(pairs)
    for oa, ob in zip(a, b):
        assert (oa.ged, oa.certified, oa.lower_bound) == \
            (ob.ged, ob.certified, ob.lower_bound)
        assert ob.backend == "sharded"
    for tau in (2.0, 4.0):
        va = ged.GedEngine("jax", **ENGINE_OPTS).verify(pairs, tau)
        vb = ged.GedEngine("sharded", **ENGINE_OPTS).verify(pairs, tau)
        for oa, ob in zip(va, vb):
            assert (oa.similar, oa.certified) == (ob.similar, ob.certified)


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, %r)
    import jax, numpy as np
    from repro import ged
    from repro.data.graphs import perturb, random_graph

    assert jax.device_count() == 8
    rng = np.random.default_rng(5)
    pairs = []
    for _ in range(11):     # odd count: padded to 16 (a multiple of 8)
        q = random_graph(rng, int(rng.integers(4, 10)), density=0.4,
                         n_vlabels=3, n_elabels=2)
        pairs.append((q, perturb(rng, q, 3, n_vlabels=3, n_elabels=2)))
    opts = dict(pool=256, expand=4, max_iters=256)

    ref = ged.GedEngine("jax", **opts).compute(pairs)
    eng = ged.GedEngine("sharded", **opts)
    assert eng.batch_multiple == 8
    got = eng.compute(pairs)
    assert [(o.ged, o.certified) for o in got] == \\
        [(o.ged, o.certified) for o in ref]

    vref = ged.GedEngine("jax", **opts).verify(pairs, 4.0)
    vgot = ged.GedEngine("sharded", **opts).verify(pairs, 4.0)
    assert [(o.similar, o.certified) for o in vgot] == \\
        [(o.similar, o.certified) for o in vref]

    # production-shaped 2-D mesh: pairs shard over the batch axes only
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    v2d = ged.GedEngine("sharded", mesh=mesh, **opts).verify(pairs, 4.0)
    assert [(o.similar, o.certified) for o in v2d] == \\
        [(o.similar, o.certified) for o in vref]
    print("OK")
""")


@pytest.mark.slow
def test_sharded_backend_parity_on_8_devices():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT % src],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_sharded_single_device_fast_path():
    """On a one-shard mesh the sharded executor must skip shard_map (the
    fast path counter fires) and pad to batch_multiple == 1, with outcomes
    identical to the jax backend."""
    import jax
    if jax.device_count() != 1:
        pytest.skip("needs exactly one local device")
    pairs = _small_pairs(21, 8)
    eng = ged.GedEngine("sharded", **ENGINE_OPTS)
    assert eng.batch_multiple == 1              # no shard-multiple padding
    got = eng.compute(pairs)
    ref = ged.GedEngine("jax", **ENGINE_OPTS).compute(pairs)
    assert [(o.ged, o.certified) for o in got] == \
        [(o.ged, o.certified) for o in ref]
    assert eng.stats["executor_single_device_fastpath"] >= 1


@pytest.fixture
def _compile_cache_reset():
    """The persistent compile cache is process-global jax config; point it
    back off after the test so later tests don't write into a deleted
    tmp_path.  The config update alone is not enough — jax latches its
    cache state at first use, so without ``reset_cache()`` every later
    compile in the process keeps writing into the removed directory."""
    yield
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.ged import exec as gexec
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    gexec._PERSISTENT_CACHE["dir"] = None


def test_compile_cache_dir_knob(tmp_path, _compile_cache_reset):
    """GedEngine(compile_cache_dir=...) enables jax's persistent cache:
    executables are serialised into the directory and the stats surface
    the process-wide hit/miss counters."""
    d = str(tmp_path / "cc")
    eng = ged.GedEngine("jax", compile_cache_dir=d, **ENGINE_OPTS)
    assert eng.compile_cache_dir == d
    eng.compute(_small_pairs(22, 2))
    stats = eng.stats
    for key in ("persistent_cache_hits", "persistent_cache_misses",
                "persistent_cache_entries"):
        assert key in stats, stats
    # the engine's compile may have been answered by this process's jit
    # cache (no XLA compile => nothing to persist); force a fresh entry
    if stats["persistent_cache_entries"] == 0:
        import jax
        import jax.numpy as jnp
        jax.jit(lambda x: x * 2 + 19)(jnp.ones(3)).block_until_ready()
    assert len(os.listdir(d)) >= 1


def test_compile_cache_env_default(tmp_path, monkeypatch,
                                   _compile_cache_reset):
    """A JAX_COMPILATION_CACHE_DIR set from outside is the cache directory,
    whatever the caller passes."""
    import jax

    from repro.ged.exec import enable_compile_cache
    d = str(tmp_path / "env_cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    jax.config.update("jax_compilation_cache_dir", d)   # as jax reads it
    assert enable_compile_cache(None) == d
    assert os.path.isdir(d)
    # an explicit directory does not override the caller's variable, and
    # nothing in the code points jax anywhere else
    assert enable_compile_cache(str(tmp_path / "other")) == d
    assert jax.config.jax_compilation_cache_dir == d
    assert not os.path.exists(tmp_path / "other")


def test_compile_cache_unset_default_is_fixed_checkout_path(
        monkeypatch, _compile_cache_reset):
    """Unset, the cache lands in the fixed .jax_cache/ at the checkout's
    root — never a temporary, per-process or time-stamped path."""
    import jax

    from repro.ged.exec import DEFAULT_COMPILE_CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(root, ".jax_cache")
    assert enable_compile_cache() == DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_COMPILE_CACHE_DIR


def test_engine_enables_no_cache_by_default(_compile_cache_reset):
    """Constructing an engine without compile_cache_dir= turns nothing on:
    only entry points enable the persistent cache."""
    import jax

    from repro.ged import exec as gexec
    before = (gexec._PERSISTENT_CACHE["dir"],
              jax.config.jax_compilation_cache_dir)
    eng = ged.GedEngine("jax", **ENGINE_OPTS)
    assert eng.compile_cache_dir is None
    assert (gexec._PERSISTENT_CACHE["dir"],
            jax.config.jax_compilation_cache_dir) == before


def test_shard_padding_round_trip():
    """Buckets padded to shard multiples still answer exactly the real
    pairs, in order, with the same results as unpadded planning."""
    from repro.ged.plan import build_plan, padded_batch

    assert [padded_batch(r, 1) for r in (1, 3, 5, 8)] == [1, 4, 8, 8]
    assert [padded_batch(r, 8) for r in (1, 3, 8, 9)] == [8, 8, 8, 16]
    assert padded_batch(4, 6) == 6 and padded_batch(7, 6) == 12

    pairs = _sized_pairs(14, [3, 5, 8, 4, 6])
    plain = build_plan(pairs)
    padded = build_plan(pairs, batch_multiple=8)
    for plan in (plain, padded):
        covered = sorted(i for b in plan.buckets for i in b.indices)
        assert covered == list(range(len(pairs)))
    assert all(b.packed.batch % 8 == 0 for b in padded.buckets)

    from repro.core.engine.search import EngineConfig
    from repro.ged.backends import EngineBackend
    cfg = EngineConfig(use_kernel=False, **ENGINE_OPTS)
    taus = np.zeros(len(pairs), dtype=np.float32)
    a = EngineBackend().run(plain, taus, False, cfg)
    b = EngineBackend().run(padded, taus, False, cfg)
    assert [(o.ged, o.certified) for o in a] == \
        [(o.ged, o.certified) for o in b]


# ------------------------------------------------------- result caching

def test_result_cache_answers_repeats_without_reexecution():
    eng = ged.GedEngine("jax", **ENGINE_OPTS)
    pairs = _small_pairs(13, 5)
    first = eng.compute(pairs)
    calls = eng.stats["executor_calls"]
    assert eng.stats["result_cache_misses"] == len(pairs)

    t0 = run_batch_traces()
    second = eng.compute(pairs)
    assert run_batch_traces() - t0 == 0, "cached pairs must not re-compile"
    assert eng.stats["executor_calls"] == calls, \
        "cached pairs must not re-execute"
    assert eng.stats["result_cache_hits"] == len(pairs)
    for a, b in zip(first, second):
        assert (a.ged, a.certified) == (b.ged, b.certified)
        assert b.stats.get("cached") and not a.stats.get("cached")


def test_result_cache_dedups_within_one_batch():
    eng = ged.GedEngine("jax", **ENGINE_OPTS)
    (p0, p1) = _small_pairs(15, 2)
    outs = eng.compute([p0, p0, p1, p0])
    assert eng.stats["result_cache_misses"] == 2
    assert eng.stats["result_cache_hits"] == 2
    assert eng.stats["executor_pairs"] == 2     # only the unique pairs ran
    assert outs[0].ged == outs[1].ged == outs[3].ged
    # every position is its own outcome: mutating one entry (stats dict
    # or mapping array) must not leak into duplicates or later cache hits
    outs[1].stats["caller_tag"] = 1
    assert "caller_tag" not in outs[3].stats
    if outs[1].mapping is not None:
        outs[1].mapping[:] = -7
        assert not np.array_equal(outs[3].mapping, outs[1].mapping)
    again = eng.compute([p0])[0]
    assert "caller_tag" not in again.stats
    if again.mapping is not None:
        assert not np.array_equal(again.mapping, outs[1].mapping)


def test_result_cache_is_tau_and_mode_aware():
    eng = ged.GedEngine("jax", **ENGINE_OPTS)
    pairs = _small_pairs(16, 3)
    eng.compute(pairs)
    eng.verify(pairs, 3.0)              # different mode: all misses
    assert eng.stats["result_cache_hits"] == 0
    eng.verify(pairs, 4.0)              # different tau: all misses
    assert eng.stats["result_cache_hits"] == 0
    eng.verify(pairs, 3.0)              # same tau: all hits
    assert eng.stats["result_cache_hits"] == len(pairs)


def test_result_cache_key_is_vocab_independent():
    """The same pair hits the cache even when its batch companions change
    the shared label vocabulary."""
    rng = np.random.default_rng(17)
    q = random_graph(rng, 4, density=0.4, n_vlabels=2, n_elabels=1)
    p0 = (q, perturb(rng, q, 1, n_vlabels=2, n_elabels=1))
    rich = random_graph(rng, 5, density=0.5, n_vlabels=6, n_elabels=3)
    p1 = (rich, perturb(rng, rich, 2, n_vlabels=6, n_elabels=3))
    eng = ged.GedEngine("jax", **ENGINE_OPTS)
    eng.compute([p0])
    eng.compute([p0, p1])               # bigger vocab, same p0
    assert eng.stats["result_cache_hits"] == 1


def test_cache_can_be_disabled():
    eng = ged.GedEngine("jax", cache=False, **ENGINE_OPTS)
    pairs = _small_pairs(18, 3)
    eng.compute(pairs)
    calls = eng.stats["executor_calls"]
    eng.compute(pairs)
    assert "result_cache_hits" not in eng.stats
    assert eng.stats["executor_calls"] == 2 * calls  # repeats re-execute
