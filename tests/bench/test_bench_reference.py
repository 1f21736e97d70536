"""The plain reference decides GED exactly: against brute force over every
bijection on small graphs, and against the program's own host solver on
AIDS-like near pairs."""

import itertools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen, reference  # noqa: E402


def brute(q, g):
    n = max(len(q[0]), len(g[0]))
    return min(reference.mapping_cost(q, g, f)
               for f in itertools.permutations(range(n)))


def small_pairs(seed, count, related):
    rng = gen.stream(seed, 99)
    out = []
    for _ in range(count):
        n1 = int(rng.integers(2, 7))
        q = gen.aids_like_graph(rng, n1, 3, 2)
        if related:
            g = gen.perturb(rng, q, int(rng.integers(0, 4)), 3, 2)
        else:
            g = gen.aids_like_graph(rng, int(rng.integers(2, 7)), 3, 2)
        out.append((q, g))
    return out


@pytest.mark.parametrize("related", [True, False])
def test_distance_matches_brute_force(related):
    for q, g in small_pairs(1 + related, 40, related):
        d = brute(q, g)
        assert reference.distance(q, g) == d
        assert reference.distance(g, q) == d
        assert reference.root_bound(q, g) <= d
        assert reference.within(q, g, d)
        assert not reference.within(q, g, d - 1)


def test_corpus_bounds_admissible():
    pairs = small_pairs(5, 30, False) + small_pairs(6, 30, True)
    corpus = [g for _, g in pairs]
    bounds = reference.CorpusBounds(corpus, 3, 2)
    for q, _ in pairs[::6]:
        lb = bounds.of(q)
        for j, g in enumerate(corpus):
            assert lb[j] <= brute(q, g)


def test_agrees_with_program_host_solver():
    from repro.core.exact.graph import Graph
    from repro.core.exact.search import ged
    rng = gen.stream(7, 98)
    for _ in range(12):
        q = gen.aids_like_graph(rng, int(rng.integers(8, 16)), 62, 3)
        g = gen.perturb(rng, q, int(rng.integers(1, 6)), 62, 3)
        assert reference.distance(q, g) == ged(Graph(*q), Graph(*g)).ged
