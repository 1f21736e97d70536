"""Cells cut to a size that a test on the CPU can hold: the same files,
the same harness and the same checks, with a corpus of a few hundred
small molecules and batches of a few pairs."""

from __future__ import annotations

import copy

from bench import harness


def tiny(cell: harness.Cell, graphs: int = 300) -> harness.Cell:
    cfg = copy.deepcopy(cell.config)
    for key in ("corpus", "molecules"):
        if key in cfg:
            cfg[key].update(mean_vertices=8, max_vertices=12)
    if "corpus" in cfg:
        cfg["corpus"]["graphs"] = graphs
    # building every program a call can reach takes the CPU most of a
    # minute; test_bench_warm runs it on its own
    tr = dict(cell.traffic, warmup=1, pool=2, check=24, warm_shapes=False)
    if tr["loop"] == "open":
        tr["rate_per_s"] = 40.0
    if tr["request"] == "verify":
        tr["batch"] = 8
    if tr["request"] == "topk":
        tr.update(min_series=4, k=3)
    return harness.Cell(cell.name, cell.chips, cfg, tr, cell.end_to_end,
                        cell.per_layer, cell.root)


def search(traffic: str, root: str = harness.ROOT, graphs: int = 300
           ) -> harness.Cell:
    """The search configuration under one of the benchmark's traffic
    files, cut down."""
    return tiny(harness.cell_from_files(
        f"aids-search.{traffic}", "bench/configs/aids-search.json", traffic,
        root), graphs)
