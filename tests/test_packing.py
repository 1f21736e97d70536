"""Batch packing: the matching order against an exact-arithmetic reference
of paper App. A.1, and ``pack_pairs``'s tensors against a per-pair
relabelling."""

import json
import os
import sys
from collections import Counter
from fractions import Fraction

import numpy as np
import pytest

from repro.core.engine.tensor_graphs import label_vocab, pack_pairs
from repro.core.exact.graph import BOTTOM, Graph, pad_pair
from repro.core.exact.order import matching_order, matching_orders
from repro.data.graphs import random_graph
from repro.ged.plan import merge_vocab, pack_bucket, padded_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import gen  # noqa: E402


# ------------------------------------------------------------ reference

def fraction_order(q: Graph, g: Graph):
    """App. A.1 with rational weights, ties to the lowest vertex index.
    Returns ``(order, ties)``: ``ties`` counts the steps at which two or
    more candidates shared the best weight."""
    n = q.n
    vfreq = Counter(g.vlabels.tolist())
    efreq = Counter(int(g.adj[i, j]) for i in range(g.n)
                    for j in range(i + 1, g.n) if g.adj[i, j])
    n_g, m_g = max(g.n, 1), max(g.m, 1)
    wv = [1 - Fraction(vfreq[int(a)], n_g) for a in q.vlabels]
    we = [[1 - Fraction(efreq[int(q.adj[v, u])], m_g) if q.adj[v, u]
           else Fraction(0) for u in range(n)] for v in range(n)]
    chosen, ties = [], 0
    while len(chosen) < n:
        left = [v for v in range(n) if v not in chosen]
        cands = [v for v in left if q.vlabels[v] != BOTTOM] or left
        if chosen:
            near = [v for v in cands if any(q.adj[v, u] for u in chosen)]
            pool = near or cands
            weight = {v: wv[v] + sum(we[v][u] for u in chosen)
                      for v in pool}
        else:
            pool = cands
            weight = {v: wv[v] + sum(we[v]) for v in pool}
        top = max(weight.values())
        best = [v for v in pool if weight[v] == top]
        ties += len(best) > 1
        chosen.append(best[0])
    return np.asarray(chosen, dtype=np.int64), ties


# ---------------------------------------------------------------- pairs

def _aids_mix(count=48, seed=2914000001):
    with open(os.path.join(ROOT, "bench", "configs", "aids-verify.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "batch256.json")) as f:
        traffic = json.load(f)
    reqs = gen.make_pairs(cfg, traffic, gen.stream(seed, gen.TRAFFIC), count)
    return [(Graph(q[0], q[1]), Graph(g[0], g[1])) for q, g, _ in reqs]


def _random_pairs(kind: str, count: int = 24):
    rng = np.random.default_rng(KINDS.index(kind) + 101)
    out = []
    for _ in range(count):
        n = int(rng.integers(1, 65))
        m = int(rng.integers(1, 65))
        if kind == "random":
            m = n
        elif kind == "bottom_padded":
            n, m = min(n, m), max(n, m)
        elif kind == "swapped":
            n, m = max(n, m), min(n, m)
        density = 0.0 if kind == "edgeless" else float(rng.uniform(0.05, 0.4))
        q = random_graph(rng, n, density=density, n_vlabels=4, n_elabels=3)
        g = random_graph(rng, m, density=density, n_vlabels=4, n_elabels=3)
        out.append((q, g))
    return out


KINDS = ["random", "bottom_padded", "swapped", "edgeless", "aids_mix",
         "unpadded"]


def _pairs(kind):
    """Pairs as the engine and the host solver see them, padded by
    ``pad_pair``; ``unpadded`` keeps two sizes per pair."""
    pairs = _aids_mix() if kind == "aids_mix" else _random_pairs(kind)
    if kind == "unpadded":
        return pairs
    return [pad_pair(q, g)[:2] for q, g in pairs]


# ---------------------------------------------------------------- order

@pytest.mark.parametrize("kind", KINDS)
def test_matching_order_equals_exact_reference(kind):
    ties = 0
    for q, g in _pairs(kind):
        want, t = fraction_order(q, g)
        ties += t
        np.testing.assert_array_equal(matching_order(q, g), want)
    if kind in ("edgeless", "aids_mix"):
        assert ties > 0      # the tie rule is exercised, not just assumed


@pytest.mark.parametrize("kind", KINDS)
def test_batched_rows_equal_per_pair_order(kind):
    pairs = _pairs(kind)
    slots = 64
    orders = matching_orders(pairs, slots)
    assert orders.shape == (len(pairs), slots)
    assert orders.dtype == np.int32
    for b, (q, g) in enumerate(pairs):
        np.testing.assert_array_equal(orders[b, : q.n], matching_order(q, g))
        np.testing.assert_array_equal(orders[b, q.n:],
                                      np.arange(q.n, slots))


def test_matching_order_ties_go_to_lowest_index():
    # a 4-cycle of one label against itself: every step is a tie
    q = Graph.from_edges([0, 0, 0, 0],
                         [(0, 1, 1), (1, 2, 1), (2, 3, 1), (3, 0, 1)])
    np.testing.assert_array_equal(matching_order(q, q), [0, 1, 2, 3])
    # BOTTOM padding goes last even though it weighs the most
    qp, gp, _ = pad_pair(Graph.from_edges([0, 1], [(0, 1, 1)]),
                         Graph.from_edges([0, 0, 0], [(0, 1, 1), (1, 2, 1)]))
    np.testing.assert_array_equal(matching_order(qp, gp), [1, 0, 2])


# -------------------------------------------------------------- packing

def per_pair_pack(pairs, slots, vocab=None):
    """A per-pair relabelling through Python dicts: the oracle for every
    tensor of ``pack_pairs`` but the order."""
    padded = [pad_pair(q, g)[:2] for q, g in pairs]
    if vocab is not None:
        vset = sorted(int(a) for a in vocab[0])
        eset = sorted(int(a) for a in vocab[1])
    else:
        vset, eset = (list(s) for s in label_vocab(padded))
    vmap = {a: i for i, a in enumerate(vset)}
    emap = {a: i + 1 for i, a in enumerate(eset)}
    emap[0] = 0
    lv, le = len(vset), len(eset)
    bottom, pad = lv, lv + 1
    B = len(padded)
    qv = np.full((B, slots), pad, dtype=np.int32)
    gv = np.full((B, slots), pad, dtype=np.int32)
    qa = np.zeros((B, slots, slots), dtype=np.int32)
    ga = np.zeros((B, slots, slots), dtype=np.int32)
    ns = np.zeros((B,), dtype=np.int32)
    for b, (qp, gp) in enumerate(padded):
        n = gp.n
        ns[b] = n
        qv[b, :n] = [bottom if int(a) == BOTTOM else vmap[int(a)]
                     for a in qp.vlabels]
        gv[b, :n] = [bottom if int(a) == BOTTOM else vmap[int(a)]
                     for a in gp.vlabels]
        qa[b, :n, :n] = np.vectorize(lambda a: emap[int(a)])(qp.adj)
        ga[b, :n, :n] = np.vectorize(lambda a: emap[int(a)])(gp.adj)
    return qv, gv, qa, ga, ns, lv, le


def _assert_same_tensors(t, want):
    qv, gv, qa, ga, ns, lv, le = want
    for name, ref in (("qv", qv), ("gv", gv), ("qa", qa), ("ga", ga),
                      ("n", ns)):
        got = getattr(t, name)
        assert got.dtype == ref.dtype, name
        np.testing.assert_array_equal(got, ref, err_msg=name)
    assert (t.n_vlabels, t.n_elabels) == (lv, le)
    assert type(t.n_vlabels) is int and type(t.n_elabels) is int


@pytest.mark.parametrize("with_vocab", [False, True])
@pytest.mark.parametrize("kind", KINDS)
def test_pack_pairs_bit_identical_to_per_pair_relabelling(kind, with_vocab):
    # raw (unpadded) pairs: pack_pairs pads and swaps them itself
    pairs = _aids_mix() if kind == "aids_mix" else _random_pairs(kind)
    vocab = None
    if with_vocab:
        # a caller vocabulary wider than the batch, listed out of order
        vocab = merge_vocab(label_vocab(pairs),
                            [Graph.from_edges([90, 7], [(0, 1, 11)])])
        vocab = (tuple(reversed(vocab[0])), vocab[1])
    t = pack_pairs(pairs, slots=64, vocab=vocab)
    _assert_same_tensors(t, per_pair_pack(pairs, 64, vocab))
    padded = [pad_pair(q, g)[:2] for q, g in pairs]
    np.testing.assert_array_equal(t.order, matching_orders(padded, 64))


def test_pack_pairs_default_slots_and_small_batch():
    rng = np.random.default_rng(5)
    pairs = [(random_graph(rng, 5), random_graph(rng, 3))]
    t = pack_pairs(pairs)
    assert t.slots == 8
    _assert_same_tensors(t, per_pair_pack(pairs, 8))


def test_pack_pairs_missing_vocab_raises():
    q = Graph.from_edges([0, 3], [(0, 1, 2)])
    g = Graph.from_edges([0, 5, 3], [(0, 1, 2), (1, 2, 4)])
    with pytest.raises(ValueError,
                       match=r"vocab does not cover batch labels: \[4, 5\]$"):
        pack_pairs([(q, g)], slots=4, vocab=((0, 3), (2,)))
    # a covering vocabulary packs
    pack_pairs([(q, g)], slots=4, vocab=((0, 3, 5), (2, 4)))


@pytest.mark.parametrize("batch_multiple", [1, 8])
def test_pack_bucket_filler_rows_copy_the_last_row(batch_multiple):
    pairs = _aids_mix(count=11)
    vocab = label_vocab(pairs)
    t, real = pack_bucket(pairs, 64, vocab, batch_multiple)
    assert real == 11
    assert t.batch == padded_batch(11, batch_multiple)
    ref = pack_pairs(pairs, slots=64, vocab=vocab)
    for name in ("qv", "gv", "qa", "ga", "order", "n"):
        got, want = getattr(t, name), getattr(ref, name)
        assert got.dtype == want.dtype, name
        np.testing.assert_array_equal(got[:real], want, err_msg=name)
        for b in range(real, t.batch):
            np.testing.assert_array_equal(got[b], want[-1], err_msg=name)
    assert (t.n_vlabels, t.n_elabels) == (ref.n_vlabels, ref.n_elabels)
