"""The plain reference that decides ``correct``: graph edit distance under
uniform costs, written from the definition and importing nothing of the
system under test.

GED(q, g) is the least number of edits (vertex relabel, insert or delete;
edge relabel, insert or delete; each costs 1) that turn ``q`` into ``g``.
Padding the smaller graph with isolated vertices of a label no real vertex
has turns it into a bijection problem: the cost of a bijection ``f`` is the
number of vertices ``v`` with ``label(v) != label(f(v))`` plus the number
of vertex pairs ``{v, w}`` whose edge labels (0 = no edge) differ from
those of ``{f(v), f(w)}``.

``within(q, g, limit)`` decides ``GED <= limit`` by a depth-first search
over bijections: each node maps one more ``q`` vertex, and is cut when its
lower bound exceeds the limit.  The bound is the cost of the vertices
already mapped, plus an optimal assignment of the unmapped vertices under
their label and edge costs towards the mapped part, plus the label-multiset
distance of the edges among unmapped vertices.  ``distance`` raises the
limit until ``within`` holds.  ``corpus_bounds`` is the same root bound,
vectorised over a whole corpus, so that a range query is checked against
every member.

``root_bound`` alone is the control: it answers from an admissible lower
bound without the search, the step that would drop the exactness the
configurations guarantee.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
from scipy.optimize import linear_sum_assignment

Graph = Tuple[np.ndarray, np.ndarray]
PAD = -1   # label of padding vertices; real labels are >= 0


def _pad(q: Graph, g: Graph) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]:
    n = max(len(q[0]), len(g[0]))

    def grow(x: Graph):
        vl = np.full(n, PAD, dtype=np.int64)
        vl[:len(x[0])] = x[0]
        adj = np.zeros((n, n), dtype=np.int64)
        adj[:len(x[0]), :len(x[0])] = x[1]
        return vl, adj

    (ql, qa), (gl, ga) = grow(q), grow(g)
    return ql, qa, gl, ga


def mapping_cost(q: Graph, g: Graph, f: Sequence[int]) -> int:
    """Cost of the bijection ``f`` (``f[v]`` = image of padded-q vertex
    ``v``) between the padded graphs."""
    ql, qa, gl, ga = _pad(q, g)
    f = np.asarray(f, dtype=np.int64)
    return int(np.count_nonzero(ql != gl[f])
               + np.count_nonzero(np.triu(qa != ga[np.ix_(f, f)], k=1)))


def _multiset_gap(a: np.ndarray, b: np.ndarray) -> int:
    """max(|A|, |B|) - |A ∩ B| for multisets of non-negative ints."""
    if len(a) == 0 or len(b) == 0:
        return max(len(a), len(b))
    size = int(max(a.max(), b.max())) + 1
    common = np.minimum(np.bincount(a, minlength=size),
                        np.bincount(b, minlength=size)).sum()
    return int(max(len(a), len(b)) - common)


class _Search:
    """Depth-first search for a bijection of cost ``<= limit``."""

    def __init__(self, q: Graph, g: Graph):
        self.ql, self.qa, self.gl, self.ga = _pad(q, g)
        self.n = len(self.ql)
        self.order = self._order()
        self.nodes = 0

    def _order(self) -> List[int]:
        """Rarest label first, then always the unmapped vertex with the
        most edges into the mapped part: edge costs are then paid early."""
        n, qa = self.n, self.qa
        freq = {int(a): int(c) for a, c in
                zip(*np.unique(self.gl, return_counts=True))}
        rarity = np.array([freq.get(int(a), 0) for a in self.ql])
        deg = np.count_nonzero(qa, axis=1)
        order: List[int] = []
        links = np.zeros(n, dtype=np.int64)
        left = np.ones(n, dtype=bool)
        for _ in range(n):
            key = np.where(left, links * 10_000 - rarity * 64 + deg, -10 ** 9)
            v = int(np.argmax(key))
            order.append(v)
            left[v] = False
            links += qa[v] != 0
        return order

    def bound(self, depth: int, f: np.ndarray, used: np.ndarray
              ) -> Tuple[int, np.ndarray, np.ndarray]:
        """Lower bound on the cost of the unmapped part, with the cost
        matrix of the next vertex's candidates.  Returns ``(bound, rows,
        cols)`` where ``rows`` are the unmapped q vertices (the next one
        first) and ``cols`` the free g vertices."""
        mapped = np.asarray(self.order[:depth], dtype=np.int64)
        rows = np.asarray(self.order[depth:], dtype=np.int64)
        cols = np.flatnonzero(~used)
        cost = (self.ql[rows][:, None] != self.gl[cols][None, :]).astype(
            np.int64)
        if depth:
            qx = self.qa[np.ix_(rows, mapped)]            # (r, d)
            gx = self.ga[np.ix_(cols, f[mapped])]         # (c, d)
            cost = cost + (qx[:, None, :] != gx[None, :, :]).sum(axis=2)
        r, c = linear_sum_assignment(cost)
        inner_q = self.qa[np.ix_(rows, rows)][np.triu_indices(len(rows), 1)]
        inner_g = self.ga[np.ix_(cols, cols)][np.triu_indices(len(cols), 1)]
        edges = _multiset_gap(inner_q[inner_q > 0], inner_g[inner_g > 0])
        return int(cost[r, c].sum()) + edges, cost, cols

    def run(self, limit: int) -> Optional[int]:
        """The cost of some bijection ``<= limit``, or None when none
        exists."""
        f = np.full(self.n, -1, dtype=np.int64)
        used = np.zeros(self.n, dtype=bool)
        return self._dfs(0, 0, f, used, limit)

    def _dfs(self, depth: int, spent: int, f: np.ndarray, used: np.ndarray,
             limit: int) -> Optional[int]:
        self.nodes += 1
        if depth == self.n:
            return spent
        lb, cost, cols = self.bound(depth, f, used)
        if spent + lb > limit:
            return None
        v = self.order[depth]
        row = cost[0]
        for j in np.argsort(row, kind="stable"):
            step = int(row[j])
            if spent + step > limit:
                break
            u = int(cols[j])
            f[v], used[u] = u, True
            got = self._dfs(depth + 1, spent + step, f, used, limit)
            f[v], used[u] = -1, False
            if got is not None:
                return got
        return None


def root_bound(q: Graph, g: Graph) -> int:
    """The search's bound before any vertex is mapped: an admissible lower
    bound on GED(q, g)."""
    s = _Search(q, g)
    return s.bound(0, np.full(s.n, -1), np.zeros(s.n, dtype=bool))[0]


def within(q: Graph, g: Graph, limit: float) -> bool:
    """``GED(q, g) <= limit``, exactly."""
    limit = int(np.floor(limit + 1e-9))
    if limit < 0:
        return False
    ql, qa, gl, ga = _pad(q, g)
    if len(q[0]) == len(g[0]) and mapping_cost(q, g, np.arange(len(ql))) \
            <= limit:
        return True            # the identity is a witness
    return _Search(q, g).run(limit) is not None


def distance(q: Graph, g: Graph) -> int:
    """GED(q, g), exactly."""
    s = _Search(q, g)
    d = root_bound(q, g)
    while s.run(d) is None:
        d += 1
    return d


class CorpusBounds:
    """The label-multiset and degree bound of every corpus member against
    one query, vectorised: ``Γ(vertex labels) + max(Γ(edge labels),
    ceil(L1(sorted degrees) / 2))``, with both graphs padded to the larger
    vertex count."""

    def __init__(self, graphs: Sequence[Graph], n_vlabels: int,
                 n_elabels: int):
        count = len(graphs)
        self.n = np.array([len(g[0]) for g in graphs])
        self.width = int(self.n.max())
        self.vhist = np.zeros((count, n_vlabels), dtype=np.int32)
        self.ehist = np.zeros((count, n_elabels + 1), dtype=np.int32)
        self.deg = np.zeros((count, self.width), dtype=np.int32)
        for i, (vl, adj) in enumerate(graphs):
            self.vhist[i] = np.bincount(vl, minlength=n_vlabels)
            up = adj[np.triu_indices(len(vl), 1)]
            self.ehist[i] = np.bincount(up, minlength=n_elabels + 1)
            d = np.sort(np.count_nonzero(adj, axis=1))[::-1]
            self.deg[i, :len(d)] = d
        self.ehist[:, 0] = 0
        self.m = self.ehist.sum(axis=1)

    def of(self, q: Graph) -> np.ndarray:
        vl, adj = q
        vh = np.bincount(vl, minlength=self.vhist.shape[1])
        up = adj[np.triu_indices(len(vl), 1)]
        eh = np.bincount(up, minlength=self.ehist.shape[1])
        eh[0] = 0
        n_v = np.maximum(self.n, len(vl))
        gv = n_v - np.minimum(self.vhist, vh[None, :]).sum(axis=1)
        ge = np.maximum(self.m, eh.sum()) - np.minimum(
            self.ehist, eh[None, :]).sum(axis=1)
        width = max(self.width, len(vl))
        qd = np.zeros(width, dtype=np.int32)
        d = np.sort(np.count_nonzero(adj, axis=1))[::-1]
        qd[:len(d)] = d
        cd = self.deg
        if width > self.width:
            cd = np.zeros((len(self.n), width), dtype=np.int32)
            cd[:, :self.width] = self.deg
        l1 = np.abs(cd - qd[None, :]).sum(axis=1)
        return gv + np.maximum(ge, (l1 + 1) // 2)
