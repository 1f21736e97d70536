"""GED verification under real (fake-device) mesh sharding.

The dry-run proves the 512-chip lowering; this test EXECUTES the batched
engine through ``GedEngine("sharded", mesh=...)`` with the pair batch
sharded over 8 devices and checks that the answers are identical to the
single-device run (lockstep vmap semantics are placement-invariant) and
that the engine outputs stay sharded (no implicit gather).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_DISABLE_PALLAS"] = "1"
    import sys; sys.path.insert(0, %r)
    import jax, numpy as np
    from repro import ged
    from repro.data.graphs import perturb, random_graph

    rng = np.random.default_rng(5)
    pairs = []
    for _ in range(16):
        q = random_graph(rng, 10)
        pairs.append((q, perturb(rng, q, 3)))
    opts = dict(slots=16, pool=256, expand=4, max_iters=256, cache=False)

    # single-device reference
    ref = ged.GedEngine("jax", **opts).verify(pairs, 4.0)

    # sharded execution: the pair batch over an 8-device mesh
    mesh = jax.make_mesh((8,), ("data",))
    eng = ged.GedEngine("sharded", mesh=mesh, **opts)
    out = eng.verify(pairs, 4.0)
    for a, b in zip(ref, out):
        assert (a.similar, a.certified) == (b.similar, b.certified)
    assert eng.batch_multiple == 8
    assert eng.stats["executor_single_device_fastpath"] == 0

    # outputs stayed sharded: inspect the dispatched (un-materialised)
    # device arrays of one bucket
    ex = eng._backend.executor
    plan = ged.build_plan(pairs, slots=16, batch_multiple=8)
    bucket = plan.buckets[0]
    arrays = ex._dispatch(bucket.packed,
                          bucket.pad_values(np.full(16, 4.0, np.float32)),
                          eng.config, True)
    assert len(arrays["similar"].sharding.device_set) == 8
    print("OK")
""")


@pytest.mark.slow
def test_verify_batch_sharded_matches_single_device():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT % src],
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
