"""Workload planning for the ``repro.ged`` facade.

Three jobs, all shape-related:

1. **Ingestion** — :func:`as_graph` accepts the formats users actually have
   (``Graph`` objects, ``(vlabels, edges)`` tuples, adjacency dicts) so the
   facade never forces a manual conversion step.
2. **Bucketing** — :func:`build_plan` groups pairs by power-of-two slot
   count and pads each bucket's batch dimension to a power of two.  A
   mixed-size workload therefore presents the jitted engine with a handful
   of canonical shapes instead of one shape per odd batch, and every bucket
   shares one label vocabulary so the static ``n_vlabels``/``n_elabels``
   arguments match across buckets.
3. **Compile-cache bookkeeping** — the executables live in ``jax.jit``'s
   cache; :class:`CompileCache` mirrors the key set so callers can observe
   hits vs misses (``GedEngine(...).stats``) and tests can assert reuse.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.engine.search import EngineConfig
from repro.core.engine.tensor_graphs import (GraphPairTensors, label_vocab,
                                             pack_pairs)
from repro.core.exact.graph import Graph
from repro.ged.spans import span

MIN_SLOTS = 4

Vocab = Tuple[Tuple[int, ...], Tuple[int, ...]]


# ------------------------------------------------------------- ingestion

def as_graph(obj) -> Graph:
    """Coerce a user-facing graph description into a :class:`Graph`.

    Accepted forms:

    * ``Graph`` — returned as-is;
    * ``(vlabels, edges)`` tuple/list with ``edges`` of ``(i, j, elabel)``;
    * ``{"vlabels": [...], "edges": [...]}`` or ``{"vlabels": [...],
      "adj": matrix}`` dicts;
    * adjacency dict ``{node: (vlabel, [(neighbor, elabel), ...])}`` with
      arbitrary hashable node ids (indexed in sorted order).

    >>> g = as_graph(([0, 1, 1], [(0, 1, 1), (1, 2, 2)]))
    >>> g.n, g.m
    (3, 2)
    >>> as_graph({"a": (0, [("b", 1)]), "b": (1, [("a", 1)])}).n
    2
    """
    if isinstance(obj, Graph):
        return obj
    if isinstance(obj, dict):
        if "vlabels" in obj:
            if "adj" in obj:
                return Graph(np.asarray(obj["vlabels"]), np.asarray(obj["adj"]))
            return Graph.from_edges(list(obj["vlabels"]),
                                    list(obj.get("edges", ())))
        nodes = sorted(obj)
        index = {v: i for i, v in enumerate(nodes)}
        vlabels = [int(obj[v][0]) for v in nodes]
        edges, seen = [], set()
        for v in nodes:
            for nbr, lab in obj[v][1]:
                i, j = index[v], index[nbr]
                key = (min(i, j), max(i, j))
                if i == j or key in seen:
                    continue
                seen.add(key)
                edges.append((i, j, int(lab)))
        return Graph.from_edges(vlabels, edges)
    if isinstance(obj, (tuple, list)) and len(obj) == 2:
        vlabels, edges = obj
        return Graph.from_edges(list(vlabels), list(edges))
    raise TypeError(
        f"cannot interpret {type(obj).__name__} as a graph; expected Graph, "
        "(vlabels, edges), or an adjacency dict")


def as_pairs(pairs) -> List[Tuple[Graph, Graph]]:
    out = []
    for p in pairs:
        q, g = p
        out.append((as_graph(q), as_graph(g)))
    return out


def graphs_vocab(graphs: Sequence[Graph]) -> Vocab:
    """Shared ``(vertex_labels, edge_labels)`` vocabulary for a corpus.

    The single-graph analogue of
    :func:`repro.core.engine.tensor_graphs.label_vocab` — a
    :class:`repro.ged.GraphStore` computes it once at ingest so every
    query bucket (and the stage-0 feature histograms) share one compact
    label space.

    >>> g = as_graph(([0, 5], [(0, 1, 2)]))
    >>> graphs_vocab([g])
    ((0, 5), (2,))
    """
    return label_vocab([(g, g) for g in graphs])


def merge_vocab(vocab: Vocab, graphs: Sequence[Graph]) -> Vocab:
    """``vocab`` extended with any labels ``graphs`` introduce.

    Queries against an ingested corpus may carry labels the corpus never
    uses; packing with the merged vocabulary keeps every bucket coverage
    check satisfied while staying stable (and therefore compile-cached)
    for the common all-known-labels case.

    >>> merge_vocab(((0,), (1,)), [as_graph(([0, 7], [(0, 1, 3)]))])
    ((0, 7), (1, 3))
    """
    extra_v, extra_e = graphs_vocab(graphs)
    return (tuple(sorted(set(vocab[0]) | set(extra_v))),
            tuple(sorted(set(vocab[1]) | set(extra_e))))


# -------------------------------------------------------------- bucketing

def _pow2(n: int) -> int:
    return max(1, 1 << (int(n) - 1).bit_length())


def slot_bucket(n: int, min_slots: int = MIN_SLOTS) -> int:
    """Power-of-two slot count for a padded pair of ``n`` vertices.

    >>> [slot_bucket(n) for n in (1, 4, 5, 9)]
    [4, 4, 8, 16]
    """
    return max(min_slots, _pow2(max(n, 1)))


def pad_tail(values: np.ndarray, batch: int) -> np.ndarray:
    """Pad a per-pair value array to ``batch`` by repeating the last entry —
    the same rule :func:`pack_bucket` uses for the pairs themselves."""
    arr = np.asarray(values)
    return np.concatenate([arr, np.repeat(arr[-1:], batch - arr.shape[0],
                                          axis=0)])


def padded_batch(real: int, batch_multiple: int = 1) -> int:
    """Batch size after padding: the power of two >= ``real``, rounded up to
    a multiple of ``batch_multiple`` (the executor's shard count, so every
    device mesh shard receives an equal slice).

    >>> [padded_batch(r) for r in (1, 3, 5)]
    [1, 4, 8]
    >>> padded_batch(9, batch_multiple=8)
    16
    """
    b = _pow2(real)
    if b % batch_multiple:
        b = -(-b // batch_multiple) * batch_multiple
    return b


def pack_bucket(
    pairs: Sequence[Tuple[Graph, Graph]],
    slots: int,
    vocab: Optional[Vocab],
    batch_multiple: int = 1,
) -> Tuple[GraphPairTensors, int]:
    """Pack ``pairs`` at ``slots``, padding the batch dim to
    :func:`padded_batch` (the filler rows are copies of the last packed
    row).  Returns ``(tensors, real_count)``."""
    real = len(pairs)
    batch = padded_batch(real, batch_multiple)
    with span("ged.pack", slots=slots, real=real, batch=batch):
        t = pack_pairs(pairs, slots=slots, vocab=vocab)
        return GraphPairTensors(
            pad_tail(t.qv, batch), pad_tail(t.gv, batch),
            pad_tail(t.qa, batch), pad_tail(t.ga, batch),
            pad_tail(t.order, batch), pad_tail(t.n, batch),
            t.n_vlabels, t.n_elabels), real


@dataclasses.dataclass
class Bucket:
    slots: int
    indices: List[int]          # positions in the plan's pair list
    packed: GraphPairTensors    # batch padded to a power of two
    real: int                   # pairs before batch padding

    def pad_values(self, values: np.ndarray) -> np.ndarray:
        """Gather per-pair values for this bucket, padded like the batch."""
        return pad_tail(np.asarray(values)[self.indices], self.packed.batch)


@dataclasses.dataclass
class Plan:
    pairs: List[Tuple[Graph, Graph]]
    buckets: List[Bucket]
    vocab: Vocab
    fixed_slots: Optional[int]  # user-pinned slot count (disables bucketing)

    @classmethod
    def lazy(cls, pairs, vocab: Optional[Vocab] = None,
             slots: Optional[int] = None) -> "Plan":
        """A plan with *no* packed buckets: pack subsets on demand.

        The staged filter-verify pipeline (:class:`repro.ged.GraphStore`)
        holds |corpus| candidate pairs per query but expects the filter
        stages to prune most of them before anything is packed; a lazy
        plan defers all packing to :meth:`subset_buckets`, so only
        survivors ever touch tensors::

            plan = Plan.lazy([(q, g) for g in survivors], vocab=vocab)
            for bucket in plan.subset_buckets(range(len(plan.pairs)),
                                              executor.pack):
                ...
        """
        pairs = as_pairs(pairs)
        if vocab is None:
            vocab = label_vocab(pairs)
        return cls(pairs, [], vocab, slots)

    def subset_buckets(self, indices: Sequence[int], packer) -> List[Bucket]:
        """Incrementally re-bucket a subset of this plan's pairs.

        The overlapped ``auto`` scheduler calls this between escalation
        rungs: survivors of rung *k* are regrouped by slot bucket
        (honouring ``fixed_slots``) and re-packed with the plan's shared
        vocab, so rung *k+1* batches keep canonical shapes — and shard
        multiples — without re-ingesting or re-planning the whole
        workload.  ``packer`` is :meth:`repro.ged.exec.Executor.pack`
        shaped: ``packer(pairs, slots, vocab) -> (tensors, real)``, which
        is how the executor's ``batch_multiple`` reaches the padding.

        Example (survivors 0 and 3 re-queued for the next rung)::

            for bucket in plan.subset_buckets([0, 3], executor.pack):
                pending = executor.run_packed_async(
                    bucket.packed, bucket.pad_values(taus), rcfg,
                    verification, real=bucket.real)
        """
        by_slots: Dict[int, List[int]] = {}
        for gi in indices:
            q, g = self.pairs[gi]
            s = self.fixed_slots or slot_bucket(max(q.n, g.n))
            by_slots.setdefault(s, []).append(gi)
        out = []
        for s in sorted(by_slots):
            idxs = by_slots[s]
            packed, real = packer([self.pairs[i] for i in idxs], s,
                                  self.vocab)
            out.append(Bucket(s, idxs, packed, real))
        return out


def build_plan(
    raw_pairs,
    slots: Optional[int] = None,
    vocab: Optional[Vocab] = None,
    batch_multiple: int = 1,
) -> Plan:
    """Ingest ``raw_pairs`` and group them into canonical-shape buckets.

    ``batch_multiple`` — pad every bucket's batch to a multiple of this
    (the executor's shard count; 1 for single-device execution).
    """
    with span("ged.plan"):
        pairs = as_pairs(raw_pairs)
        if vocab is None:
            vocab = label_vocab(pairs)
        else:
            vocab = tuple(sorted(int(a) for a in vocab[0])), \
                tuple(sorted(int(a) for a in vocab[1]))
        by_slots: Dict[int, List[int]] = {}
        for i, (q, g) in enumerate(pairs):
            s = slots if slots is not None else slot_bucket(max(q.n, g.n))
            by_slots.setdefault(s, []).append(i)
        buckets = []
        for s in sorted(by_slots):
            idxs = by_slots[s]
            packed, real = pack_bucket([pairs[i] for i in idxs], s, vocab,
                                       batch_multiple)
            buckets.append(Bucket(s, idxs, packed, real))
        return Plan(pairs, buckets, vocab, slots)


# ---------------------------------------------------------- compile cache

@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0


class CompileCache:
    """Mirror of the jit cache keys the facade has exercised.

    ``jax.jit`` owns the compiled executables; this class only tracks which
    ``(batch_shape, vocab_sizes, config, mode)`` keys have been seen, so
    engine stats can report compile reuse and tests can assert that
    same-bucket batches do not re-trace.
    """

    def __init__(self) -> None:
        self._keys: set = set()
        self.stats = CacheStats()

    @staticmethod
    def key(packed: GraphPairTensors, cfg: EngineConfig,
            verification: bool) -> tuple:
        return (packed.qv.shape, packed.n_vlabels, packed.n_elabels,
                cfg, bool(verification))

    def record(self, packed: GraphPairTensors, cfg: EngineConfig,
               verification: bool) -> bool:
        """Note one engine invocation; returns True on a cache hit."""
        k = self.key(packed, cfg, verification)
        if k in self._keys:
            self.stats.hits += 1
            return True
        self._keys.add(k)
        self.stats.misses += 1
        return False
