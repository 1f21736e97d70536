#!/usr/bin/env python3
"""The control: the plain reference put in the program's place, with the
guarantee the configurations state broken.

    python bench/control.py --workload <name> --seed <n> --seconds <s>

It answers every request from an admissible lower bound alone and skips
the search that makes an answer exact: a pair is "similar" when its bound
is within ``tau``, a range query returns every member whose bound is
within ``tau``, a top-k query the ``k`` members of least bound with the
bound as their distance.  That is the step that would tempt a later change
(a filter that stops before verification), and the comparison that decides
``correct`` has to fail it.  The run goes through the harness as a cell's
run does, on the chip, and prints the same result line; the benchmark's
own runs never use the control.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference  # noqa: E402


class Control:
    """Answers from lower bounds, claiming them certified."""

    def __init__(self, config: dict, graphs=None):
        self.graphs = graphs
        if graphs is not None:
            c = config["corpus"]
            self.bounds = reference.CorpusBounds(
                graphs, c["vertex_labels"], c["edge_labels"])

    def verify(self, pairs):
        return [(reference.root_bound(q, g) <= tau, True)
                for q, g, tau in pairs]

    def range(self, query, tau):
        lb = self.bounds.of(query)
        return [(int(j), None, True) for j in np.flatnonzero(lb <= tau)]

    def topk(self, query, k):
        lb = self.bounds.of(query)
        order = np.lexsort((np.arange(len(lb)), lb))[:k]
        return [(int(j), float(lb[j]), True) for j in order]

    def rungs(self):
        return 0            # no engine, so no programs to build

    def stats(self):
        return {}

    def close(self):
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness, system
    try:
        cell = harness.find_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds, False,
                                  STARTED, build=Control)
    except system.NoChip as e:
        print(f"control: {e}; this run needs the chip", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
