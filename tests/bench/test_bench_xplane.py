"""The trace reduction and the roofline arithmetic: interval union and
clipping, the peak table, the operations and bytes of ``reduced_top2``, and
the reduction of a small trace recorded on the chip."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import peaks, xplane  # noqa: E402

SMALL = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")


def test_union_and_clip():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                                   (3, 4)]
    assert xplane.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_module_name():
    assert xplane.module_name("jit__run_batch(42)") == "_run_batch"
    assert xplane.module_name("jit_scan_fn") == "scan_fn"


def test_reduce_synthetic_events():
    dev = {"plane": "/device:TPU:0",
           "ops": [("fusion", 1.0, 1.0, {}), ("reduced_top2.1", 1.5, 1.0, {}),
                   ("copy", 5.0, 1.0, {})],
           "modules": [("jit__run_batch(1)", 1.0, 1.5),
                       ("jit_scan_fn(2)", 5.0, 1.0)]}
    host = [("bench.window", 0.0, 8.0), ("bench.verify", 0.5, 3.0),
            ("bench.wait", 3.6, 1.0)]
    r = xplane.reduce({"devices": [dev], "host": host})
    assert r["window_s"] == 8.0 and r["busy_s"] == pytest.approx(2.5)
    assert r["modules"] == {"_run_batch": 1.5, "scan_fn": 1.0}
    assert r["kernels"]["reduced_top2"]["seconds"] == 1.0
    gaps = dict(r["breakdown"]["idle_gaps"])
    # a gap is named by the span covering its middle: 0-1 by verify,
    # 2.5-5 by wait, 6-8 by none
    assert gaps["bench.verify"] == pytest.approx(1.0)
    assert gaps["bench.wait"] == pytest.approx(2.5)
    assert gaps["untraced"] == pytest.approx(2.0)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("batch,n", [(256, 32), (16, 64)])
def test_reduced_top2_cost(batch, n):
    ops, nbytes = peaks.reduced_top2_cost(batch, n)
    assert ops == 5 * batch * n * n
    assert nbytes == 4 * (batch * n * n + 4 * batch * n)
    t, bound = peaks.least_time(ops, nbytes, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_square_operand():
    text = "(f32[256,1,32], s32[256,1,32]) custom-call(f32[256,32,32] %a)"
    assert peaks.square_operand(text) == (256, 32)
    assert peaks.square_operand("f32[4,8,64,64]") == (32, 64)
    assert peaks.square_operand("f32[8,1,32]") is None


def test_small_chip_trace():
    """A trace recorded on a TPU v5 lite: one batch of 16 verifications
    through ``GedVerificationService`` inside a ``bench.window`` span,
    trimmed to the device plane's XLA lines and the benchmark's host
    spans (0.6 MB of the 27 MB recorded)."""
    from bench import harness
    r = xplane.reduce(xplane.read(SMALL))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert "_run_batch" in r["modules"]
    calls = r["kernels"]["reduced_top2"]["calls"]
    assert calls and all(peaks.square_operand(name) for _, name, _ in calls)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    run = harness.Run(None, device_kind="TPU v5 lite", trace=r)
    share = harness.metric_reader("reduced_top2_roofline.verify")(run)
    assert 0 < share <= 100
    idle = harness.metric_reader("device_idle.verify")(run)
    assert 0 <= idle < 100
