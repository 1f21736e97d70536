"""Seeded traffic and data for the benchmark: AIDS-like molecules and their
edits, with nothing of the system under test.

A graph here is a pair ``(vlabels, adj)`` of NumPy arrays: ``vlabels[i]``
is vertex ``i``'s label (``>= 0``), ``adj`` a symmetric int matrix with
``0`` for no edge and ``a >= 1`` for an edge labelled ``a``.  The system
adapter (``system.py``) converts them to the program's own type.

The molecule and edit generators are frozen copies of the ones the
program's smoke run used, so that a change to the program cannot change
the yardstick.  Every random stream is drawn from ``stream(seed, ...)``:
the same seed gives the same graphs, and streams for the corpus, the
warm-up set and the timed traffic never overlap.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

Graph = Tuple[np.ndarray, np.ndarray]

# stream ids: one per use, so the warm-up set never repeats timed traffic
CORPUS, TRAFFIC, WARMUP, SAMPLE = 1, 2, 3, 4


def stream(seed: int, *ids: int) -> np.random.Generator:
    """An independent generator for ``(seed, *ids)``; any whole ``seed``,
    negative or above 64 bits, is accepted."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, *ids]))


def vertex_counts(rng: np.random.Generator, size: int, mean: float,
                  cap: int) -> np.ndarray:
    """Molecule sizes: gamma-distributed (shape 6) around ``mean``, rounded
    and clipped to ``[2, cap]``."""
    x = rng.gamma(6.0, mean / 6.0, size=size)
    return np.clip(np.rint(x), 2, cap).astype(int)


def aids_like_graph(rng: np.random.Generator, n: int, n_vlabels: int,
                    n_elabels: int) -> Graph:
    """Sparse and molecule-like: a random spanning tree plus ~8% extra
    edges, with Zipfian vertex labels (a few atoms dominate)."""
    ranks = np.arange(1, n_vlabels + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    vlabels = rng.choice(n_vlabels, size=n, p=probs).astype(np.int64)
    adj = np.zeros((n, n), dtype=np.int64)
    for v in range(1, n):
        u = int(rng.integers(0, v))
        a = int(rng.integers(1, n_elabels + 1))
        adj[u, v] = adj[v, u] = a
    for _ in range(max(0, int(0.08 * n))):
        u, v = rng.integers(0, n, size=2)
        if u != v and adj[u, v] == 0:
            a = int(rng.integers(1, n_elabels + 1))
            adj[u, v] = adj[v, u] = a
    return vlabels, adj


def perturb(rng: np.random.Generator, g: Graph, n_ops: int, n_vlabels: int,
            n_elabels: int) -> Graph:
    """``n_ops`` random edits: vertex relabel, edge relabel, edge insert or
    edge delete (the vertex count stays fixed), so the result is at most
    ``n_ops`` from ``g``."""
    vlabels, adj = g[0].copy(), g[1].copy()
    n = len(vlabels)
    for _ in range(n_ops):
        op = rng.integers(0, 4)
        if op == 0 and n > 0:
            v = int(rng.integers(0, n))
            new = int(rng.integers(0, n_vlabels))
            if new == vlabels[v]:
                new = (new + 1) % max(n_vlabels, 2)
            vlabels[v] = new
        elif op == 1:
            ii, jj = np.nonzero(np.triu(adj, k=1))
            if len(ii) == 0:
                continue
            k = int(rng.integers(0, len(ii)))
            u, v = int(ii[k]), int(jj[k])
            new = int(rng.integers(1, n_elabels + 1))
            if new == adj[u, v]:
                new = 1 + (new % max(n_elabels, 2))
            adj[u, v] = adj[v, u] = new
        elif op == 2 and n >= 2:
            for _attempt in range(8):
                u, v = rng.integers(0, n, size=2)
                if u != v and adj[u, v] == 0:
                    a = int(rng.integers(1, n_elabels + 1))
                    adj[u, v] = adj[v, u] = a
                    break
        else:
            ii, jj = np.nonzero(np.triu(adj, k=1))
            if len(ii) == 0:
                continue
            k = int(rng.integers(0, len(ii)))
            u, v = int(ii[k]), int(jj[k])
            adj[u, v] = adj[v, u] = 0
    return vlabels, adj


def make_corpus(cfg: dict, seed: int) -> Tuple[List[Graph], np.ndarray]:
    """An AIDS-like corpus of analog series: a base molecule followed by
    analogs one edit from it, series sizes geometric (mean 4, capped at
    32).  Returns ``(graphs, base_of)``, ``base_of[i]`` the id of graph
    ``i``'s series base."""
    c = cfg["corpus"]
    n_graphs, lv, le = c["graphs"], c["vertex_labels"], c["edge_labels"]
    rng = stream(seed, CORPUS)
    sizes = vertex_counts(rng, n_graphs, c["mean_vertices"], c["max_vertices"])
    graphs: List[Graph] = []
    base_of: List[int] = []
    while len(graphs) < n_graphs:
        base_id = len(graphs)
        base = aids_like_graph(rng, int(sizes[base_id]), lv, le)
        series = min(int(rng.geometric(0.25)), 32, n_graphs - base_id)
        graphs.append(base)
        for _ in range(series - 1):
            graphs.append(perturb(rng, base, 1, lv, le))
        base_of.extend([base_id] * series)
    return graphs, np.asarray(base_of)


def _between(rng: np.random.Generator, lo_hi: Sequence[int]) -> int:
    lo, hi = lo_hi
    return int(rng.integers(lo, hi + 1))


def stratified_counts(mean: float, cap: int, count: int) -> np.ndarray:
    """``count`` molecule sizes that stand for ``vertex_counts``'s
    distribution: its quantiles at ``(i + 1/2) / count``, rounded and
    clipped alike.  They do not depend on any seed."""
    from scipy.stats import gamma
    x = gamma.ppf((np.arange(count) + 0.5) / count, 6.0, scale=mean / 6.0)
    return np.clip(np.rint(x), 2, cap).astype(int)


def cycled(lo_hi: Sequence[int], count: int) -> np.ndarray:
    """``count`` values cycling over an inclusive range, so each value comes
    equally often, to within one."""
    lo, hi = lo_hi
    return np.resize(np.arange(lo, hi + 1), count)


def mix(cfg: dict, traffic: dict, rng: np.random.Generator, count: int
        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sizes, edit counts and thresholds of ``count`` requests.

    Every block of ``traffic["batch"]`` requests holds the same sizes
    (``stratified_counts``), the same edit counts and the same thresholds
    (``cycled``), each shuffled on its own by ``rng``: every seed and every
    call asks for the same amount of work, in another order."""
    c = cfg["molecules"]
    b = int(traffic["batch"])
    blocks = max(1, -(-count // b))
    sizes = stratified_counts(c["mean_vertices"], c["max_vertices"], b)
    edits, taus = cycled(traffic["edits"], b), cycled(traffic["tau"], b)
    drawn = [np.concatenate([rng.permutation(x) for _ in range(blocks)])
             for x in (sizes, edits, taus)]
    return tuple(x[:count] for x in drawn)


def make_pairs(cfg: dict, traffic: dict, rng: np.random.Generator,
               count: int) -> List[Tuple[Graph, Graph, float]]:
    """``count`` verification requests ``(q, g, tau)``: ``q`` sized like a
    corpus molecule, ``g`` is ``q`` after ``traffic["edits"]`` edits and
    ``tau`` is drawn from ``traffic["tau"]`` (both inclusive ranges); see
    ``mix`` for how sizes, edits and thresholds are chosen."""
    c = cfg["molecules"]
    lv, le = c["vertex_labels"], c["edge_labels"]
    out = []
    for n, k, tau in zip(*mix(cfg, traffic, rng, count)):
        q = aids_like_graph(rng, int(n), lv, le)
        g = perturb(rng, q, int(k), lv, le)
        out.append((q, g, float(tau)))
    return out


def label_carriers(cfg: dict, rng: np.random.Generator
                   ) -> List[Tuple[Graph, Graph, float]]:
    """Requests that between them use every vertex and edge label of the
    configuration, as a call of many molecules does: each ``q`` is a path
    of at most ``max_vertices`` vertices whose vertices and edges take the
    labels in an order drawn from ``rng`` (so that no two calls send the
    same request, and no result cache answers one), ``g`` is ``q`` and
    ``tau`` is 1."""
    c = cfg["molecules"]
    lv, le, cap = c["vertex_labels"], c["edge_labels"], c["max_vertices"]
    out = []
    for lo in range(0, lv, cap):
        labels = rng.permutation(np.arange(lo, min(lo + cap, lv)))
        n = max(len(labels), 2, le + 1)
        vl = np.resize(labels, n).astype(np.int64)
        edge = rng.permutation(np.resize(np.arange(1, le + 1), n - 1))
        adj = np.zeros((n, n), dtype=np.int64)
        for v in range(1, n):
            adj[v - 1, v] = adj[v, v - 1] = edge[v - 1]
        out.append(((vl, adj), (vl.copy(), adj.copy()), 1.0))
    return out


def make_queries(cfg: dict, traffic: dict, graphs: Sequence[Graph],
                 base_of: np.ndarray, rng: np.random.Generator, count: int
                 ) -> List[dict]:
    """``count`` corpus queries, each a perturbation of a corpus member
    (its ``source``).

    A range query (``traffic["tau"]`` given) perturbs a uniformly drawn
    member by ``traffic["edits"]`` edits and draws its ``tau``.  A top-k
    query (``traffic["k"]`` given) perturbs the base of a series with more
    than ``traffic["min_series"]`` members, so its nearest neighbours are
    close analogs.
    """
    c = cfg["corpus"]
    lv, le = c["vertex_labels"], c["edge_labels"]
    out = []
    if "k" in traffic:
        counts = np.bincount(base_of, minlength=len(graphs))
        big = np.flatnonzero(counts > traffic["min_series"])
        if len(big) == 0:
            raise ValueError("no analog series has more than "
                             f"{traffic['min_series']} members")
        for _ in range(count):
            src = int(rng.choice(big))
            q = perturb(rng, graphs[src], _between(rng, traffic["edits"]),
                        lv, le)
            out.append({"query": q, "k": int(traffic["k"]), "source": src})
        return out
    for _ in range(count):
        src = int(rng.integers(len(graphs)))
        q = perturb(rng, graphs[src], _between(rng, traffic["edits"]), lv, le)
        out.append({"query": q, "tau": float(_between(rng, traffic["tau"])),
                    "source": src})
    return out


def arrivals(rng: np.random.Generator, rate: float, seconds: float
             ) -> np.ndarray:
    """Due times of an open loop: Poisson arrivals at ``rate`` per second,
    conditioned on their count, ``round(rate * seconds)``, so every seed
    offers the same work at the same mean rate in another order."""
    count = max(1, int(round(rate * seconds)))
    return np.sort(rng.uniform(0.0, seconds, size=count))
