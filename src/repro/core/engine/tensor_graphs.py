"""Dense padded tensor representation of (q, g) pairs.

Label conventions (compact, per *batch*):
* vertex labels ``0 .. Lv-1`` are real, ``Lv`` is the BOTTOM padding label
  (paper's ``_|_``), ``Lv+1`` marks PAD slots (non-vertices beyond ``n``).
* edge labels ``1 .. Le`` real, ``0`` = no edge.  PAD slots have no edges.

All pairs in a batch share the static size ``N`` (max vertices) and the label
vocabularies ``Lv`` / ``Le``; the per-pair true size ``n`` is data.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import numpy as np

from repro.core.exact.graph import BOTTOM, Graph, pad_pair, stack_pairs
from repro.core.exact.order import stacked_orders


@dataclasses.dataclass
class GraphPairTensors:
    """A batch of B graph pairs, padded to N slots."""

    qv: np.ndarray      # (B, N) int32 vertex labels of q (compact)
    gv: np.ndarray      # (B, N) int32 vertex labels of g
    qa: np.ndarray      # (B, N, N) int32 edge labels of q (0 = absent)
    ga: np.ndarray      # (B, N, N) int32 edge labels of g
    order: np.ndarray   # (B, N) int32 matching order of q (PAD slots at end)
    n: np.ndarray       # (B,) int32 true vertex count per pair
    n_vlabels: int      # Lv (real labels); BOTTOM = Lv, PAD = Lv + 1
    n_elabels: int      # Le (real labels); absent = 0

    @property
    def batch(self) -> int:
        return self.qv.shape[0]

    @property
    def slots(self) -> int:
        return self.qv.shape[1]

    def pair(self, i: int) -> "GraphPairTensors":
        return GraphPairTensors(
            self.qv[i : i + 1], self.gv[i : i + 1], self.qa[i : i + 1],
            self.ga[i : i + 1], self.order[i : i + 1], self.n[i : i + 1],
            self.n_vlabels, self.n_elabels,
        )


def label_vocab(
    pairs: Sequence[Tuple[Graph, Graph]],
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Joint (vertex, edge) label vocabularies across a set of pairs.

    Sharing one vocabulary across several ``pack_pairs`` calls keeps the
    static ``n_vlabels`` / ``n_elabels`` arguments of the jitted engine
    identical between batches, so bucketed workloads reuse compilations.
    """
    vset = sorted(
        {int(a) for q, g in pairs for a in q.vlabels if a != BOTTOM}
        | {int(a) for q, g in pairs for a in g.vlabels if a != BOTTOM}
    )
    eset = sorted(
        {int(a) for q, g in pairs for a in np.unique(q.adj) if a != 0}
        | {int(a) for q, g in pairs for a in np.unique(g.adj) if a != 0}
    )
    return tuple(vset), tuple(eset)


def pack_pairs(
    pairs: Sequence[Tuple[Graph, Graph]],
    slots: int | None = None,
    vocab: Tuple[Sequence[int], Sequence[int]] | None = None,
) -> GraphPairTensors:
    """Pad, relabel and stack a list of (q, g) pairs into batch tensors.

    ``vocab`` — optional ``(vertex_labels, edge_labels)`` from
    :func:`label_vocab`; when given it must cover every label in the batch
    and is used verbatim so batches packed with the same vocab share the
    compact label space (and hence jit compilations).

    The whole batch is relabelled at once: the distinct labels present are
    looked up in the sorted vocabulary and mapped back by index.
    """
    padded = [pad_pair(q, g)[:2] for q, g in pairs]
    nmax = max(gp.n for _, gp in padded)
    if slots is None:
        slots = max(4, int(2 ** np.ceil(np.log2(max(nmax, 1)))))
    if nmax > slots:
        raise ValueError(f"pair with {nmax} vertices does not fit {slots} slots")

    stacked = stack_pairs(padded, slots)
    qv, gv, qa, ga, ns, _ = stacked
    verts = np.stack([qv, gv])                    # (2, B, N) as labelled
    adjs = np.stack([qa, ga])                     # (2, B, N, N)
    valid = np.arange(slots) < ns[:, None]
    is_bottom = valid & (verts == BOTTOM)
    is_real = valid & ~is_bottom
    is_edge = adjs != 0
    seen_v, inv_v = np.unique(verts[is_real], return_inverse=True)
    seen_e, inv_e = np.unique(adjs[is_edge], return_inverse=True)

    # Joint compact label maps across the batch (or the caller's vocab).
    if vocab is not None:
        vset = np.sort(np.asarray(vocab[0], dtype=np.int64))
        eset = np.sort(np.asarray(vocab[1], dtype=np.int64))
        missing = (set(np.setdiff1d(seen_v, vset).tolist())
                   | set(np.setdiff1d(seen_e, eset).tolist()))
        if missing:
            raise ValueError(f"vocab does not cover batch labels: {sorted(missing)}")
    else:
        vset, eset = seen_v, seen_e
    lv, le = len(vset), len(eset)
    bottom, pad = lv, lv + 1

    # label -> position in the sorted vocabulary (the last of equal entries)
    vcode = np.full(verts.shape, pad, dtype=np.int32)
    vcode[is_bottom] = bottom
    vcode[is_real] = (np.searchsorted(vset, seen_v, side="right") - 1)[inv_v]
    ecode = np.zeros(adjs.shape, dtype=np.int32)
    ecode[is_edge] = np.searchsorted(eset, seen_e, side="right")[inv_e]

    order = stacked_orders(*stacked)
    return GraphPairTensors(vcode[0], vcode[1], ecode[0], ecode[1], order,
                            ns.astype(np.int32), lv, le)
