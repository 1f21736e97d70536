#!/usr/bin/env python3
"""Find the highest rate an open-loop cell's system sustains, on the chip.

    python bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --fractions 0.6,0.8,1.0,1.2

One process sets the cell up once, measures the service rate with one
request outstanding for ``--seconds``, then offers Poisson load at each
fraction of that rate for ``--seconds`` and prints, per rate, the median,
90th percentile and largest latency from when requests were due, and the
mean latency of the last quarter of the requests over that of the first (a
backlog that grows reads well above 1).  The rate a cell's traffic file
states is fixed from this by hand, once; runs of the cell do not search.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fractions", default="0.6,0.8,1.0,1.2")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import gen, harness, system
    cell = harness.find_cell(args.workload)
    try:
        system.require_chip(cell.chips)
    except system.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    system.enable_compile_cache()
    log = harness.CompileLog()
    wl = harness.Workload(cell, args.seed)
    sut = system.build(cell.config, wl.graphs)
    for req in wl.calls(gen.WARMUP, int(cell.traffic.get("warmup", 1))):
        wl.send(sut, req)
    print(f"sweep setup_s={time.perf_counter() - STARTED:.3f} "
          f"programs={log.programs}", flush=True)
    before = log.programs
    run = harness.Run(cell)
    harness.closed_loop(wl, sut, wl.calls(gen.TRAFFIC + 100, 64),
                        args.seconds, run)
    rate = len(run.latencies_s) / run.window_s
    rows = [{"loop": "closed", "rate_per_s": rate,
             "mean_ms": 1e3 * statistics.mean(run.latencies_s),
             "p50_ms": 1e3 * statistics.median(run.latencies_s),
             "calls": len(run.latencies_s),
             "service_ms": [round(1e3 * x, 1) for x in run.latencies_s],
             "programs": log.programs - before}]
    print(json.dumps(rows[-1]), flush=True)
    for i, frac in enumerate(float(f) for f in args.fractions.split(",")):
        cell.traffic["rate_per_s"] = frac * rate
        wl.seed = args.seed + 1 + i
        run = harness.Run(cell)
        before = log.programs
        harness.open_loop(wl, sut, wl.calls(gen.TRAFFIC + 200 + i, 8),
                          args.seconds, run)
        lat = run.latencies_s
        q = max(1, len(lat) // 4)
        rows.append({
            "loop": "open", "fraction": frac, "rate_per_s": frac * rate,
            "calls": len(lat), "p50_ms": 1e3 * statistics.median(lat),
            "p90_ms": 1e3 * statistics.quantiles(
                lat, n=10, method="inclusive")[8] if len(lat) > 1 else None,
            "max_ms": 1e3 * max(lat),
            "last_over_first_quarter": statistics.mean(lat[-q:])
            / statistics.mean(lat[:q]),
            "late_max_ms": 1e3 * max(run.lateness_s, default=0.0),
            "programs": log.programs - before,
            "latency_ms": [round(1e3 * x, 1) for x in lat]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
