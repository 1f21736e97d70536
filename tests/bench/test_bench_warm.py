"""Set-up builds every engine program a fixed-mix call can reach, so that
nothing compiles inside the measured window (the real program on the CPU,
at a size the CPU holds)."""

import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, tiny  # noqa: E402


def test_warm_shapes_leave_no_compile_to_the_window(capsys):
    cell = tiny.tiny(harness.find_cell("aids-verify.batch"))
    # few vertex labels, so that every call of a few small molecules
    # uses all of them, as a call of 256 AIDS molecules uses all 62
    cell.config["molecules"].update(vertex_labels=4)
    cell.traffic.update(warm_shapes=True, warmup=0)
    out = harness.run_cell(cell, 2 ** 31 + 5, 1.0, False, time.perf_counter(),
                           chip=False)
    printed = capsys.readouterr().out
    assert out["correct"] is True and out["attempted"] > 0
    assert int(re.search(r"shape_calls=(\d+)", printed).group(1)) > 0
    assert int(re.search(r"window_compiles=(\d+)", printed).group(1)) == 0
