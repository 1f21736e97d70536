"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, time per XLA program and per named
kernel, and the idle gaps named by what the host was doing.

Only ``jax.profiler.ProfileData`` is used to read the file.  Device planes
are those named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per operation run, and the ``XLA Modules`` line one per program run.
Host spans are the benchmark's own ``TraceAnnotation``s, whose names start
with ``bench.``; the one named ``bench.window`` spans the traced window.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10

Interval = Tuple[float, float]


def clear(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def find(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit__run_batch(42)`` -> ``_run_batch``: the program's name without
    its id and the ``jit_`` prefix JAX gives it."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def _stats(event) -> Dict[str, object]:
    out = {}
    try:
        for k, v in event.stats:
            out[str(k)] = v
    except (TypeError, ValueError):
        pass
    return out


def read(path: str) -> dict:
    """The events the reduction needs, as plain lists (seconds)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9, _stats(ev))
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9)
                               for ev in line.events]
            devices.append({"plane": plane.name, "ops": ops,
                            "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def reduce(events: dict, kernels: Iterable[str] = ("reduced_top2",)) -> dict:
    """Busy and idle time, time per program and per kernel, averaged over
    the device planes, inside the ``bench.window`` span (or, without it,
    from the first to the last device event)."""
    devices, host = events["devices"], events["host"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    windows = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(w[0] for w in windows), max(w[1] for w in windows)
    else:
        starts = [s for dev in devices for _, s, _, _ in dev["ops"]]
        ends = [s + d for dev in devices for _, s, d, _ in dev["ops"]]
        lo, hi = min(starts), max(ends)
    spans = sorted(((s, s + d, n) for n, s, d in host if n != WINDOW_SPAN),
                   key=lambda t: t[1] - t[0])
    n_dev = len(devices)
    busy = 0.0
    per_module: Dict[str, float] = {}
    per_kernel: Dict[str, dict] = {k: {"seconds": 0.0, "calls": []}
                                   for k in kernels}
    gaps: Dict[str, float] = {}
    for dev in devices:
        ops = [(s, s + d) for _, s, d, _ in dev["ops"]]
        merged = union(clip(ops, lo, hi))
        busy += sum(e - s for s, e in merged)
        for name, s, d in dev["modules"]:
            inside = clip([(s, s + d)], lo, hi)
            if inside:
                key = module_name(name)
                per_module[key] = per_module.get(key, 0.0) + (
                    inside[0][1] - inside[0][0]) / n_dev
        for name, s, d, st in dev["ops"]:
            if not (lo <= s < hi):
                continue
            text = " ".join([name] + [str(v) for v in st.values()])
            for k in kernels:
                if k in text:
                    per_kernel[k]["seconds"] += d / n_dev
                    per_kernel[k]["calls"].append((d, name, st))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            # the innermost host span covering the gap's middle
            who = next((n for s, e, n in spans if s <= mid <= e), "untraced")
            gaps[who] = gaps.get(who, 0.0) + (b - a) / n_dev
    window = hi - lo
    ops = sorted(per_module.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy / n_dev, "window_s": window,
            "modules": per_module, "kernels": per_kernel,
            "breakdown": {"device_ops": [[k, v] for k, v in ops],
                          "idle_gaps": [[k, v] for k, v in idle]}}


def reduce_dir(trace_dir: str) -> dict:
    path = find(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(read(path))
