"""The comparison that decides ``correct``: what the timed window produced,
answer by answer, against the plain reference (``reference.py``).

Every check returns a ``Tally`` of four numbers, each held to the limit 0
(the configurations guarantee exact, certified answers, so the comparison
is exact): ``wrong`` answers that differ from the reference, ``uncertified``
answers, ``missing`` answers (shed, raised or never returned), and
``fallbacks``, the program's own retry/degrade/fault/timeout counters.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import reference

LIMITS = {"wrong": 0, "uncertified": 0, "missing": 0, "fallbacks": 0}


@dataclasses.dataclass
class Tally:
    checked: int = 0
    wrong: int = 0
    uncertified: int = 0
    missing: int = 0
    fallbacks: int = 0
    notes: List[str] = dataclasses.field(default_factory=list)

    def note(self, text: str) -> None:
        if len(self.notes) < 8:
            self.notes.append(text)

    def numbers(self) -> Dict[str, dict]:
        return {k: {"value": getattr(self, k), "limit": lim}
                for k, lim in LIMITS.items()}

    def correct(self) -> bool:
        return self.checked > 0 and all(
            getattr(self, k) <= lim for k, lim in LIMITS.items())


def check_verify(requests: Sequence, answers: Sequence,
                 tally: Tally) -> None:
    """``answers[i]`` is ``(similar, certified)`` for ``requests[i] = (q,
    g, tau)``, or None when it never came."""
    for i, ((q, g, tau), ans) in enumerate(zip(requests, answers)):
        if ans is None:
            continue            # counted as missing by the caller
        tally.checked += 1
        similar, certified = ans
        if not certified:
            tally.uncertified += 1
        want = reference.within(q, g, tau)
        if similar is None or bool(similar) != want:
            tally.wrong += 1
            tally.note(f"verify request {i}: similar={similar}, "
                       f"reference {want} (tau {tau})")


def check_range(bounds: reference.CorpusBounds, graphs: Sequence,
                queries: Sequence[dict], answers: Sequence,
                tally: Tally) -> None:
    """Each answer is the full hit list ``[(graph_id, distance or None,
    certified), ...]``: it must hold exactly the corpus members within
    ``tau`` of the query (every member is judged, by the corpus bound and
    then the search), each certified, and any distance it states exact."""
    for i, (req, ans) in enumerate(zip(queries, answers)):
        if ans is None:
            continue
        tally.checked += 1
        q, tau = req["query"], req["tau"]
        lb = bounds.of(q)
        want = {int(j) for j in np.flatnonzero(lb <= tau)
                if reference.within(q, graphs[j], tau)}
        got = {gid for gid, _, _ in ans}
        bad = len(got) != len(ans) or got != want
        for gid, dist, certified in ans:
            if not certified:
                tally.uncertified += 1
            if dist is not None and gid in want \
                    and dist != reference.distance(q, graphs[gid]):
                bad = True
        if bad:
            tally.wrong += 1
            tally.note(f"range query {i} (tau {tau}): extra "
                       f"{sorted(got - want)[:5]}, missed "
                       f"{sorted(want - got)[:5]}")


def reference_topk(bounds: reference.CorpusBounds, graphs: Sequence,
                   series: Sequence[int], q, k: int) -> List[tuple]:
    """The ``k`` nearest members by ``(distance, id)``.  The query's own
    analog series is measured first, which sets a small k-th distance;
    then members are taken in increasing bound order, each entering only
    if it lies within the current k-th distance, until the bound passes
    it."""
    lb = bounds.of(q)
    best: List[tuple] = sorted((reference.distance(q, graphs[j]), int(j))
                               for j in series)[:k]
    seen = set(int(j) for j in series)
    for j in np.lexsort((np.arange(len(lb)), lb)):
        j = int(j)
        kth = best[k - 1][0] if len(best) >= k else None
        if kth is not None and lb[j] > kth:
            break
        if j in seen:
            continue
        if kth is not None and not reference.within(q, graphs[j], kth):
            continue
        best = sorted(best + [(reference.distance(q, graphs[j]), j)])[:k]
    return best


def check_topk(bounds: reference.CorpusBounds, graphs: Sequence,
               base_of: np.ndarray, queries: Sequence[dict],
               answers: Sequence, tally: Tally) -> None:
    """Each answer is ``[(graph_id, distance, certified), ...]`` and must
    equal the reference's ``k`` nearest by ``(distance, id)``: the same
    members, the same distances, the same cutoff."""
    for i, (req, ans) in enumerate(zip(queries, answers)):
        if ans is None:
            continue
        tally.checked += 1
        series = np.flatnonzero(base_of == base_of[req["source"]])
        want = reference_topk(bounds, graphs, series, req["query"], req["k"])
        got = []
        for gid, dist, certified in ans:
            if not certified:
                tally.uncertified += 1
            got.append((None if dist is None else int(round(dist)), gid))
        if got != [(d, j) for d, j in want]:
            tally.wrong += 1
            tally.note(f"top-k query {i}: got {got[:4]}..., reference "
                       f"{want[:4]}...")


def sample(count: int, cap: Optional[int], rng: np.random.Generator
           ) -> np.ndarray:
    """Sorted indices of the answers to compare: all of them, or ``cap``
    drawn from the seed."""
    if cap is None or count <= cap:
        return np.arange(count)
    return np.sort(rng.choice(count, size=cap, replace=False))
