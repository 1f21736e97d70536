#!/usr/bin/env python3
"""The program's host spans in a profiler trace: count, total and self
time per span name, and the device's idle gaps named by the innermost
span, the program's own among them.

``xplane.py`` reads only the benchmark's ``bench.*`` spans, so its idle
gaps all fall to ``bench.verify``.  The program opens spans of its own
(``ged.*``, listed in ``repro.ged.spans.SPANS``), on the same host thread
and on the profiler's clock.  Here both kinds are read with the host line
(thread) each ran on:

- a span's *self time* is its duration inside the window minus what its
  children, the spans it encloses on the same line, cover inside the
  window;
- an idle gap of the device is named by the shortest span, of either
  kind, that covers the gap's middle.

Run as a script, it makes one traced run of a cell, as ``run.py --trace
1`` does, with this reduction in place of ``xplane.reduce`` and with the
readers of ``SPAN_METRICS`` added to the cell's per-layer metrics::

    python3 bench/spans.py --workload aids-verify.batch --seed <n> \\
        --seconds 10

The last line printed is the run's result line, with ``"spans"`` added.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__" and ROOT not in sys.path:
    sys.path.insert(0, ROOT)        # run as a script

from bench import xplane  # noqa: E402

# the program's spans, and the benchmark's own but for the window
PREFIXES = ("ged.", xplane.SPAN_PREFIX)

# per-layer metrics whose readers take their numbers from the spans
SPAN_METRICS = (
    {"name": "pack_ms_per_pair.verify", "unit": "ms/pair"},
    {"name": "dispatch_ms_per_pair.verify", "unit": "ms/pair"},
    {"name": "policy_ms_per_pair.verify", "unit": "ms/pair"},
    {"name": "host_wait_share.verify", "unit": "%"},
)

Span = Tuple[str, float, float, int]     # name, start, duration, line


def read(path: str) -> dict:
    """``xplane.read``'s events, and under ``"spans"`` every host span
    whose name starts with one of ``PREFIXES`` (the window excepted), as
    ``(name, start, duration, line)`` in seconds; ``line`` numbers the
    host thread."""
    from jax.profiler import ProfileData
    events = xplane.read(path)
    spans: List[Span] = []
    line_no = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            for ev in line.events:
                if (ev.name.startswith(PREFIXES)
                        and ev.name != xplane.WINDOW_SPAN):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9, line_no))
    events["spans"] = spans
    return events


def window(events: dict) -> Tuple[float, float]:
    """The traced window as ``xplane.reduce`` takes it: the
    ``bench.window`` span, or the first to the last device op."""
    wins = [(s, s + d) for n, s, d in events["host"]
            if n == xplane.WINDOW_SPAN]
    if wins:
        return min(w[0] for w in wins), max(w[1] for w in wins)
    ops = [(s, s + d) for dev in events["devices"]
           for _, s, d, _ in dev["ops"]]
    return min(s for s, _ in ops), max(e for _, e in ops)


def self_times(spans: List[Span], lo: float, hi: float
               ) -> Dict[str, Dict[str, float]]:
    """Per span name: ``count`` of spans that overlap ``[lo, hi)``,
    ``total_s`` of their durations inside it, and ``self_s``, that less
    what their direct children on the same line cover inside it."""
    by_line: Dict[int, List[Tuple[float, float, str]]] = {}
    for name, s, d, line in spans:
        by_line.setdefault(line, []).append((s, s + d, name))
    out: Dict[str, Dict[str, float]] = {}
    for evs in by_line.values():
        # parents before their children: by start, the longer first
        evs.sort(key=lambda t: (t[0], -t[1]))
        # open spans: [end, name, clipped, covered by children, overlaps]
        stack: List[list] = []
        for s, e, name in evs:
            while stack and stack[-1][0] < e:
                _close(stack.pop(), out)
            clipped = max(0.0, min(e, hi) - max(s, lo))
            if stack:
                stack[-1][3] += clipped
            overlaps = (s < hi and e > lo) or lo <= s < hi
            stack.append([e, name, clipped, 0.0, overlaps])
        while stack:
            _close(stack.pop(), out)
    return out


def _close(frame: list, out: Dict[str, Dict[str, float]]) -> None:
    _, name, clipped, covered, overlaps = frame
    if not overlaps:
        return
    row = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
    row["count"] += 1
    row["total_s"] += clipped
    row["self_s"] += clipped - covered


def idle_gaps(events: dict, lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds of the device, averaged over its planes, by the
    innermost span of ``events["spans"]`` that covers each gap's
    middle."""
    spans = sorted(((s, s + d, n) for n, s, d, _ in events["spans"]),
                   key=lambda t: t[1] - t[0])
    devices = events["devices"]
    gaps: Dict[str, float] = {}
    for dev in devices:
        ops = [(s, s + d) for _, s, d, _ in dev["ops"]]
        merged = xplane.union(xplane.clip(ops, lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            who = next((n for s, e, n in spans if s <= mid <= e),
                       "untraced")
            gaps[who] = gaps.get(who, 0.0) + (b - a) / len(devices)
    return gaps


def reduce(events: dict) -> dict:
    """``xplane.reduce(events)``, with ``"spans"`` (per name: ``count``,
    ``total_s``, ``self_s`` inside the window) and the breakdown's idle
    gaps named by the innermost span of ``events["spans"]``."""
    out = xplane.reduce(events)
    lo, hi = window(events)
    out["spans"] = self_times(events["spans"], lo, hi)
    gaps = sorted(idle_gaps(events, lo, hi).items(), key=lambda kv: -kv[1])
    out["breakdown"] = dict(out["breakdown"],
                            idle_gaps=[[k, v] for k, v in gaps[:xplane.TOP]])
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = xplane.find(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(read(path))


def self_s(run, names) -> Optional[float]:
    """Self seconds of the spans ``names`` in ``run``'s trace, or None
    where the trace holds none of them."""
    spans = (run.trace or {}).get("spans") or {}
    rows = [spans[n] for n in names if n in spans]
    return sum(r["self_s"] for r in rows) if rows else None


def traced_run(cell, seed: int, seconds: float, started: float,
               **options) -> dict:
    """``harness.run_cell(cell, seed, seconds, True, started, **options)``
    with this module's reduction of the trace and the readers of
    ``SPAN_METRICS`` and ``pairs_per_s`` (the traced window's own rate)
    added to the cell's per-layer metrics; the result line gains
    ``"spans"``."""
    from bench import harness
    names = {m["name"] for m in cell.per_layer}
    extra = SPAN_METRICS + ({"name": "pairs_per_s", "unit": "pairs/s"},)
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + [
        m for m in extra if m["name"] not in names])
    reduced: Dict[str, dict] = {}

    def reduce_and_keep(trace_dir: str) -> dict:
        reduced.update(reduce_dir(trace_dir))
        return reduced

    saved = xplane.reduce_dir
    xplane.reduce_dir = reduce_and_keep
    try:
        result = harness.run_cell(cell, seed, seconds, True, started,
                                  **options)
    finally:
        xplane.reduce_dir = saved
    checks = result.pop("checks")
    result["spans"] = reduced.get("spans", {})
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    # libtpu would otherwise write its logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, system
    try:
        result = traced_run(harness.find_cell(args.workload), args.seed,
                            args.seconds, STARTED)
    except system.NoChip as e:
        print(f"bench: {e}; this run needs the chip", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
