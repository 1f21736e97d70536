"""``chip_smoke.py`` on the CPU: its phases at a tiny size, and its refusals.

The script itself insists on a TPU; its phase functions take sizes as
parameters, so their control flow and their checks against the host
reference run here on a ~150-graph corpus and a handful of pairs.
"""

import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


@pytest.fixture(scope="module")
def corpus():
    graphs, base_of = cs.make_corpus(150, 7)
    queries = cs.make_queries(graphs, base_of, 7, taus=(1, 3), topk=1, k=2)
    return graphs, base_of, queries


def test_corpus_and_queries_follow_their_assumptions(corpus):
    graphs, base_of, queries = corpus
    assert len(graphs) == len(base_of) == 150
    assert all(2 <= g.n <= cs.MAX_VERTICES for g in graphs)
    for i, b in enumerate(base_of):
        assert base_of[b] == b and b <= i        # series start at their base
    taus = [q["request"].tau for q in queries if q["request"].tau]
    assert taus == [1.0, 3.0]
    top = [q for q in queries if q["request"].k]
    assert len(top) == 1 and top[0]["request"].k == 2
    assert np.bincount(base_of)[top[0]["source"]] > 8   # a big series


def test_phases_agree_with_the_host_reference(corpus):
    from repro.serving.ged_service import GedRequest
    graphs, base_of, queries = corpus
    svc, answers = cs.phase_search(graphs, base_of, queries, 7, sample=15)
    assert len(answers) == len(queries)
    assert all(answers), "every query finds at least its source"
    rng = np.random.default_rng(1)
    requests = [GedRequest(q, g, tau=float(rng.integers(1, 6)))
                for q, g in cs.make_pairs(8, 3, n_min=5, n_max=8,
                                          max_edits=3)]
    pairs = cs.make_pairs(4, 4, n_min=5, n_max=8, max_edits=3)
    unfused = cs.phase_verify(requests, pairs)
    kernels = cs.phase_kernels(pairs, unfused)
    assert [o.ged for o in kernels] == [o.ged for o in unfused]


def test_range_check_catches_a_missing_hit(corpus):
    graphs, base_of, queries = corpus
    q = queries[0]
    with pytest.raises(cs.SmokeFailure, match="missed"):
        cs.check_range(graphs, base_of, q["request"].query, q["request"].tau,
                       q["source"], [], 5, np.random.default_rng(0))


def test_fallback_counters_fail_the_phase():
    cs.check_no_fallback("ok", {"retries": 0, "engine_degraded_host": 0.0,
                                "hits": 3})
    for key in ("retries", "executor_degraded_kernel", "fault_dispatch",
                "engine_timed_out_pairs"):
        with pytest.raises(cs.SmokeFailure, match="fell back"):
            cs.check_no_fallback("bad", {"hits": 1}, {key: 1})


def test_main_exits_nonzero_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as ei:
        cs.main([])
    assert ei.value.code not in (0, None)
    assert "no TPU" in str(ei.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("var", cs.REFUSED_ENV)
def test_main_refuses_off_chip_switches(monkeypatch, capsys, var):
    monkeypatch.setenv(var, "1")
    with pytest.raises(SystemExit) as ei:
        cs.main([])
    assert var in str(ei.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script fails and prints no
    result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


FOUR_DEVICES = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [%r, %r]
    import jax
    import chip_smoke as cs
    graphs, base_of = cs.make_corpus(120, 7)
    queries = cs.make_queries(graphs, base_of, 7, taus=(2,), topk=1, k=2)
    mesh = jax.make_mesh((4,), ("data",))
    cs.phase_four_chips(graphs, base_of, queries, 7, 10, mesh)
    print("OK")
""")


def test_four_chip_phase_on_virtual_devices():
    """--four-chips' phase on four virtual CPU devices: sharded hits equal
    the one-device store's and the reference sample, outputs stay
    sharded, no dispatch takes the single-device path."""
    src = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES % (ROOT, src)],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
