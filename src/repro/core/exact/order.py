"""Frequency-aware connected matching order (paper App. A.1).

Infrequency weight of a vertex/edge of ``q`` = 1 - frequency of its label in
``g``.  Greedy: start from the vertex with the largest total weight (vertex +
adjacent edges), then repeatedly append the vertex with the largest total
weight of (its own label + edges connecting it to the chosen prefix),
preferring vertices connected to the prefix.  Padding (``BOTTOM``) vertices
are structureless and are deferred to the end of the order.

The weights are kept as integers: the weight times ``n_g * m_g`` (with
``n_g = max(|V(g)|, 1)`` and ``m_g = max(|E(g)|, 1)``), so a vertex weighs
``(n_g - vfreq) * m_g`` and an edge ``(m_g - efreq) * n_g``.  Every
comparison is then exact, and two candidates whose totals are equal as
rationals tie; a tie goes to the lowest vertex index.

:func:`matching_orders` runs the greedy for a whole batch at once, with a
few array operations per position; :func:`matching_order` is its one-row
call, so the host solver and the device rungs share one definition.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from repro.core.exact.graph import BOTTOM, Graph, stack_pairs

_NEG = np.iinfo(np.int64).min


def matching_order(q: Graph, g: Graph) -> np.ndarray:
    """The order of ``q``'s vertices for matching against ``g``."""
    slots = max(q.n, g.n)
    return matching_orders([(q, g)], slots)[0, : q.n].astype(np.int64)


def matching_orders(pairs: Sequence[Tuple[Graph, Graph]],
                    slots: int) -> np.ndarray:
    """``(B, slots)`` int32 matching orders of a batch of ``(q, g)`` pairs
    (normally padded by :func:`repro.core.exact.graph.pad_pair`); position
    ``t >= |V(q)|`` of a row holds ``t``."""
    return stacked_orders(*stack_pairs(pairs, slots))


def stacked_orders(qv: np.ndarray, gv: np.ndarray, qa: np.ndarray,
                   ga: np.ndarray, nq: np.ndarray, ng: np.ndarray
                   ) -> np.ndarray:
    """:func:`matching_orders` on the arrays of
    :func:`repro.core.exact.graph.stack_pairs`."""
    B, S = qv.shape
    idx = np.arange(S)
    qvalid = idx < nq[:, None]
    gvalid = idx < ng[:, None]

    # label frequencies in g of every vertex and edge of q
    vfreq = ((qv[:, :, None] == gv[:, None, :])
             & gvalid[:, None, :]).sum(axis=2)
    q_edge, g_edge = qa != 0, ga != 0
    labels, code = np.unique(np.concatenate([ga[g_edge], qa[q_edge]]),
                             return_inverse=True)
    k = max(len(labels), 1)
    n_gedge = int(np.count_nonzero(g_edge))
    hist = np.bincount(np.nonzero(g_edge)[0] * k + code[:n_gedge],
                       minlength=B * k).reshape(B, k)
    efreq = np.zeros(qa.shape, dtype=np.int64)
    efreq[q_edge] = hist[np.nonzero(q_edge)[0], code[n_gedge:]] // 2

    n_g = np.maximum(ng, 1)[:, None]
    m_g = np.maximum(hist.sum(axis=1) // 2, 1)[:, None]
    wv = np.where(qvalid, (n_g - vfreq) * m_g, 0)
    we = np.where(q_edge, (m_g[:, :, None] - efreq) * n_g[:, :, None], 0)

    rows = np.arange(B)
    order = np.broadcast_to(idx, (B, S)).astype(np.int32)
    in_order = np.zeros((B, S), dtype=bool)
    connected = np.zeros((B, S), dtype=bool)
    real = qvalid & (qv != BOTTOM)
    prefix = wv.copy()               # wv + sum of we to the chosen prefix
    for t in range(int(nq.max(initial=0))):
        free = qvalid & ~in_order
        real_free = real & ~in_order
        cands = np.where(real_free.any(axis=1, keepdims=True),
                         real_free, free)
        if t == 0:
            pool, score = cands, wv + we.sum(axis=2)
        else:
            near = cands & connected
            pool = np.where(near.any(axis=1, keepdims=True), near, cands)
            score = prefix
        pick = np.where(pool, score, _NEG).argmax(axis=1)
        live = t < nq
        order[live, t] = pick[live]
        r, p = rows[live], pick[live]
        in_order[r, p] = True
        prefix[r] += we[r, p]
        connected[r] |= q_edge[r, p]
    return order
