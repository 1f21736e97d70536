"""Overlapped ``auto`` escalation on the executor mesh: mesh parity,
sequential-vs-overlapped parity, the always-certified regression guard,
survivor re-bucketing, and the async stats knobs."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import ged
from repro.core.exact.brute import brute_force_ged
from repro.data.graphs import perturb, random_graph
from repro.ged.exec import Executor, ShardedExecutor


def _pairs(seed, count, nmin=4, nmax=8, ops=(1, 5)):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        q = random_graph(rng, int(rng.integers(nmin, nmax + 1)),
                         density=0.4, n_vlabels=3, n_elabels=2)
        out.append((q, perturb(rng, q, int(rng.integers(*ops)),
                               n_vlabels=3, n_elabels=2)))
    return out


OPTS = dict(batch_size=4, pool=256, expand=4, max_iters=256)


def _tiny_rungs(eng, rungs=((8, 2, 4), (256, 4, 128))):
    """Shrink the escalation ladder so rung 0 leaves real survivors."""
    eng._backend.scheduler.rungs = rungs
    return eng


# ----------------------------------------------------------- mesh parity

def test_auto_on_mesh_matches_plain_auto():
    """``GedEngine(backend="auto", mesh=...)`` must return outcomes
    identical (ged / similar / certified) to plain ``auto`` on the same
    pairs — only the placement differs."""
    import jax
    pairs = _pairs(0, 10)
    mesh = jax.make_mesh((jax.device_count(),), ("data",))

    plain = ged.GedEngine("auto", **OPTS)
    sharded = ged.GedEngine("auto", mesh=mesh, **OPTS)
    assert isinstance(plain._backend.executor, Executor)
    assert isinstance(sharded._backend.executor, ShardedExecutor)
    assert sharded.batch_multiple == jax.device_count()

    a = plain.compute(pairs)
    b = sharded.compute(pairs)
    for oa, ob in zip(a, b):
        assert (oa.ged, oa.certified) == (ob.ged, ob.certified)

    for tau in (2.0, 4.0):
        va = ged.GedEngine("auto", **OPTS).verify(pairs, tau)
        vb = ged.GedEngine("auto", mesh=mesh, **OPTS).verify(pairs, tau)
        for oa, ob in zip(va, vb):
            assert (oa.similar, oa.certified) == (ob.similar, ob.certified)


AUTO_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, %r)
    import jax, numpy as np
    from repro import ged
    from repro.ged.exec import ShardedExecutor
    from repro.data.graphs import perturb, random_graph

    assert jax.device_count() == 8
    rng = np.random.default_rng(6)
    pairs = []
    for _ in range(11):     # odd count: rung batches pad to multiples of 8
        q = random_graph(rng, int(rng.integers(4, 9)), density=0.4,
                         n_vlabels=3, n_elabels=2)
        pairs.append((q, perturb(rng, q, 3, n_vlabels=3, n_elabels=2)))
    opts = dict(batch_size=4, pool=256, expand=4, max_iters=256)

    ref = ged.GedEngine("auto", **opts).compute(pairs)
    mesh = jax.make_mesh((8,), ("data",))
    eng = ged.GedEngine("auto", mesh=mesh, **opts)
    assert isinstance(eng._backend.executor, ShardedExecutor)
    assert eng.batch_multiple == 8
    got = eng.compute(pairs)
    assert [(o.ged, o.certified) for o in got] == \\
        [(o.ged, o.certified) for o in ref]

    vref = ged.GedEngine("auto", **opts).verify(pairs, 4.0)
    vgot = ged.GedEngine("auto", mesh=mesh, **opts).verify(pairs, 4.0)
    assert [(o.similar, o.certified) for o in vgot] == \\
        [(o.similar, o.certified) for o in vref]
    print("OK")
""")


@pytest.mark.slow
def test_auto_on_mesh_parity_on_8_devices():
    """The PR-2 subprocess harness, pointed at auto-on-sharded: overlapped
    escalation over a real 8-shard mesh answers exactly like plain auto."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", AUTO_MESH_SCRIPT % src],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ------------------------------------------- overlapped-vs-sequential

def test_sequential_and_overlapped_agree_under_escalation():
    pairs = _pairs(1, 12)
    seq = _tiny_rungs(ged.GedEngine("auto", overlap=False, **OPTS))
    ovl = _tiny_rungs(ged.GedEngine("auto", overlap=True, max_in_flight=3,
                                    **OPTS))
    a = seq.compute(pairs)
    b = ovl.compute(pairs)
    assert [(o.ged, o.certified) for o in a] == \
        [(o.ged, o.certified) for o in b]

    vseq = _tiny_rungs(ged.GedEngine("auto", overlap=False, **OPTS))
    vovl = _tiny_rungs(ged.GedEngine("auto", overlap=True, **OPTS))
    va = vseq.verify(pairs, 3.0)
    vb = vovl.verify(pairs, 3.0)
    assert [(o.similar, o.certified) for o in va] == \
        [(o.similar, o.certified) for o in vb]


def test_overlapped_escalation_never_uncertified():
    """Regression guard for the async scheduler: whatever rung answered a
    pair — engine rung in flight, re-bucketed survivor, or host-solver
    tail overlapped with device work — the outcome carries a certificate
    and matches the brute-force oracle."""
    pairs = _pairs(2, 10, nmin=3, nmax=6, ops=(2, 6))
    truth = [brute_force_ged(q, g) for q, g in pairs]
    eng = _tiny_rungs(ged.GedEngine("auto", overlap=True, max_in_flight=3,
                                    **OPTS), rungs=((4, 1, 2),))
    outs = eng.compute(pairs)
    assert all(o.certified for o in outs)
    assert [o.ged for o in outs] == truth
    # the tiny ladder must have really exercised escalation + host tail
    assert eng.stats["escalated"] > 0
    assert eng.stats["host_solved"] > 0
    assert any(o.rung == -1 for o in outs)


def test_overlap_stats_knobs():
    pairs = _pairs(3, 8)
    eng = _tiny_rungs(ged.GedEngine("auto", overlap=True, **OPTS))
    eng.compute(pairs)
    s = eng.stats
    assert s["executor_rows"] >= s["executor_pairs"]
    assert s["dispatches"] > 0 and s["batches"] > 0
    assert "survivors_rung_0" in s
    survivors = sum(v for k, v in s.items()
                    if k.startswith("survivors_rung_"))
    assert survivors == s["escalated"]


# ------------------------------------------------ survivor re-bucketing

def test_subset_buckets_rebuckets_survivors():
    from repro.ged.plan import build_plan

    sizes = [3, 5, 8, 4, 6]
    rng = np.random.default_rng(4)
    pairs = []
    for n in sizes:
        q = random_graph(rng, n, density=0.4, n_vlabels=3, n_elabels=2)
        pairs.append((q, perturb(rng, q, 2, n_vlabels=3, n_elabels=2)))

    plan = build_plan(pairs)
    ex = Executor()
    survivors = [0, 2, 3]
    buckets = plan.subset_buckets(survivors, ex.pack)
    assert sorted(i for b in buckets for i in b.indices) == survivors
    # sizes 3 and 4 share the 4-slot bucket; size 8 gets its own
    assert [b.slots for b in buckets] == [4, 8]
    for b in buckets:
        assert b.packed.batch % ex.batch_multiple == 0
        assert b.real == len(b.indices)

    # pinned slots disable re-bucketing: one bucket at the fixed shape
    pinned = build_plan(pairs, slots=16)
    (bucket,) = pinned.subset_buckets(survivors, ex.pack)
    assert bucket.slots == 16 and bucket.indices == survivors


def test_shard_padded_subset_buckets():
    """Re-bucketed survivor batches honour the executor's shard multiple
    (what a mesh executor needs between rungs)."""
    from repro.ged.plan import build_plan

    class Wide(Executor):
        batch_multiple = 8

    pairs = _pairs(5, 5, nmin=3, nmax=6)
    plan = build_plan(pairs)
    buckets = plan.subset_buckets([0, 1, 4], Wide().pack)
    assert all(b.packed.batch % 8 == 0 for b in buckets)
    assert sorted(i for b in buckets for i in b.indices) == [0, 1, 4]
