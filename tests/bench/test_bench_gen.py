"""The benchmark's generators repeat from a seed and offer a fixed amount
of work."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen, harness  # noqa: E402

SEARCH = harness.load_json(os.path.join(ROOT, "bench/configs/aids-search.json"))
VERIFY = harness.load_json(os.path.join(ROOT, "bench/configs/aids-verify.json"))


def _small(cfg, graphs=200):
    cfg = dict(cfg)
    cfg["corpus"] = dict(cfg["corpus"], graphs=graphs)
    return cfg


def _same(a, b):
    return all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1])
               for x, y in zip(a, b)) and len(a) == len(b)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, -5, 2 ** 70 + 1])
def test_corpus_repeats_from_seed(seed):
    a, base_a = gen.make_corpus(_small(SEARCH), seed)
    b, base_b = gen.make_corpus(_small(SEARCH), seed)
    assert _same(a, b) and np.array_equal(base_a, base_b)
    c, _ = gen.make_corpus(_small(SEARCH), seed + 1)
    assert not _same(a, c)


def test_corpus_follows_config():
    graphs, base_of = gen.make_corpus(_small(SEARCH, 500), 3)
    sizes = np.array([len(g[0]) for g in graphs])
    assert len(graphs) == 500 and sizes.min() >= 2 and sizes.max() <= 64
    assert 20 < sizes.mean() < 31
    for g, b in zip(graphs, base_of):    # analogs one edit from their base
        assert len(g[0]) == len(graphs[b][0])
    labels = np.concatenate([g[0] for g in graphs])
    assert labels.min() >= 0 and labels.max() < 62


def test_pairs_repeat_and_stay_in_range():
    traffic = harness.load_json(os.path.join(ROOT,
                                             "bench/traffic/batch256.json"))
    a = gen.make_pairs(VERIFY, traffic, gen.stream(9, gen.TRAFFIC), 64)
    b = gen.make_pairs(VERIFY, traffic, gen.stream(9, gen.TRAFFIC), 64)
    assert _same([p[0] for p in a], [p[0] for p in b])
    assert [p[2] for p in a] == [p[2] for p in b]
    assert {p[2] for p in a} <= {1.0, 2.0, 3.0, 4.0, 5.0}
    w = gen.make_pairs(VERIFY, traffic, gen.stream(9, gen.WARMUP), 64)
    assert not _same([p[0] for p in a], [p[0] for p in w])


@pytest.mark.parametrize("traffic", ["range-open", "topk-closed"])
def test_queries_repeat_from_seed(traffic):
    tr = harness.load_json(os.path.join(ROOT, f"bench/traffic/{traffic}.json"))
    graphs, base_of = gen.make_corpus(_small(SEARCH, 400), 1)
    tr = dict(tr, min_series=4) if "k" in tr else tr
    a = gen.make_queries(SEARCH, tr, graphs, base_of, gen.stream(4, 2), 10)
    b = gen.make_queries(SEARCH, tr, graphs, base_of, gen.stream(4, 2), 10)
    assert _same([x["query"] for x in a], [x["query"] for x in b])
    assert [x["source"] for x in a] == [x["source"] for x in b]


def test_arrivals_fixed_count_inside_window():
    a = gen.arrivals(gen.stream(1, 2), 3.0, 40.0)
    b = gen.arrivals(gen.stream(2, 2), 3.0, 40.0)
    assert len(a) == len(b) == 120
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 40.0
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11, 2 ** 70 + 3])
def test_fixed_mix_offers_every_call_the_same_work(seed):
    traffic = harness.load_json(os.path.join(ROOT,
                                             "bench/traffic/batch256.json"))
    traffic = dict(traffic, batch=32)
    mol = VERIFY["molecules"]
    ref = (sorted(gen.stratified_counts(mol["mean_vertices"],
                                        mol["max_vertices"], 32)),
           sorted(gen.cycled(traffic["edits"], 32)),
           sorted(gen.cycled(traffic["tau"], 32)))
    sizes, edits, taus = gen.mix(VERIFY, traffic, gen.stream(seed, 2), 96)
    for i in range(0, 96, 32):
        assert (sorted(sizes[i:i + 32]), sorted(edits[i:i + 32]),
                sorted(taus[i:i + 32])) == ref
    assert not np.array_equal(sizes[:32], sizes[32:64])   # another order
    pairs = gen.make_pairs(VERIFY, traffic, gen.stream(seed, 2), 32)
    assert sorted(len(q[0]) for q, _, _ in pairs) == ref[0]
    assert sorted(p[2] for p in pairs) == ref[2]


def test_stratified_counts_follow_the_distribution():
    mol = VERIFY["molecules"]
    s = gen.stratified_counts(mol["mean_vertices"], mol["max_vertices"], 256)
    assert 2 <= s.min() and s.max() <= mol["max_vertices"]
    assert abs(s.mean() - mol["mean_vertices"]) < 1.0
    drawn = gen.vertex_counts(gen.stream(1, 9), 20000, mol["mean_vertices"],
                              mol["max_vertices"])
    for q in (0.1, 0.5, 0.9):
        assert abs(np.quantile(s, q) - np.quantile(drawn, q)) <= 1


def test_label_carriers_use_every_label():
    for cap in (64, 12):
        cfg = dict(VERIFY, molecules=dict(VERIFY["molecules"],
                                          max_vertices=cap))
        carriers = gen.label_carriers(cfg, gen.stream(cap, 3))
        vl = set(np.concatenate([q[0] for q, _, _ in carriers]).tolist())
        el = set(np.concatenate([q[1].ravel()
                                 for q, _, _ in carriers]).tolist()) - {0}
        assert vl == set(range(VERIFY["molecules"]["vertex_labels"]))
        assert el == set(range(1, VERIFY["molecules"]["edge_labels"] + 1))
        assert all(len(q[0]) <= cap for q, _, _ in carriers)
        assert all(np.array_equal(q[1], g[1]) for q, g, _ in carriers)
        again = gen.label_carriers(cfg, gen.stream(cap + 1, 3))
        assert not all(np.array_equal(a[0][0], b[0][0])
                       for a, b in zip(carriers, again))
