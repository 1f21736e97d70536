"""The reduction of the program's host spans: self time per span name,
idle gaps named by the innermost span, spans that cross the window's
edge, and the readers of the span and padding metrics."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, spans, xplane  # noqa: E402

SMALL = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")


def _events(span_list, ops=(), host=(("bench.window", 0.0, 10.0),)):
    dev = {"plane": "/device:TPU:0",
           "ops": [(n, s, d, {}) for n, s, d in ops],
           "modules": [("jit__run_batch(1)", s, d) for _, s, d in ops]}
    return {"devices": [dev], "host": list(host), "spans": list(span_list)}


# one call: service > engine > plan > pack, then auto > dispatch, wait
CALL = [
    ("bench.verify", 1.0, 8.0, 1),
    ("ged.service.verify", 1.0, 7.5, 1),
    ("ged.engine.run", 1.5, 7.0, 1),
    ("ged.plan", 1.5, 2.0, 1),
    ("ged.pack", 2.0, 1.0, 1),
    ("ged.auto.run", 4.0, 4.5, 1),
    ("ged.exec.dispatch", 4.0, 0.5, 1),
    ("ged.auto.wait", 5.0, 3.0, 1),
]


def test_self_time_is_duration_less_direct_children():
    r = spans.self_times(CALL, 0.0, 10.0)
    assert r["ged.pack"] == {"count": 1, "total_s": 1.0, "self_s": 1.0}
    assert r["ged.plan"]["self_s"] == pytest.approx(1.0)          # 2 - pack
    assert r["ged.auto.run"]["self_s"] == pytest.approx(1.0)      # 4.5-0.5-3
    assert r["ged.engine.run"]["self_s"] == pytest.approx(0.5)    # 7-2-4.5
    assert r["ged.service.verify"]["self_s"] == pytest.approx(0.5)
    assert r["bench.verify"]["self_s"] == pytest.approx(0.5)
    # the self times of nested spans add up to the outermost's duration
    assert sum(v["self_s"] for v in r.values()) == pytest.approx(8.0)


def test_children_on_another_line_are_not_subtracted():
    r = spans.self_times([("ged.auto.run", 0.0, 4.0, 1),
                          ("ged.pack", 1.0, 1.0, 2)], 0.0, 10.0)
    assert r["ged.auto.run"]["self_s"] == pytest.approx(4.0)
    assert r["ged.pack"]["self_s"] == pytest.approx(1.0)


def test_counts_and_totals_add_over_repeated_spans():
    r = spans.self_times([("ged.auto.run", 0.0, 6.0, 1),
                          ("ged.pack", 1.0, 1.0, 1),
                          ("ged.pack", 3.0, 2.0, 1)], 0.0, 10.0)
    assert r["ged.pack"] == {"count": 2, "total_s": 3.0, "self_s": 3.0}
    assert r["ged.auto.run"]["self_s"] == pytest.approx(3.0)


def test_span_crossing_the_window_edge_counts_its_inside_part():
    # the window is [2, 6): auto.run starts before it, wait ends after it
    r = spans.self_times([("ged.auto.run", 0.0, 8.0, 1),
                          ("ged.pack", 1.0, 2.0, 1),
                          ("ged.auto.wait", 5.0, 3.0, 1),
                          ("ged.plan", 6.5, 0.5, 1)], 2.0, 6.0)
    assert r["ged.auto.run"]["total_s"] == pytest.approx(4.0)
    # inside the window: pack covers [2, 3), wait [5, 6)
    assert r["ged.auto.run"]["self_s"] == pytest.approx(2.0)
    assert r["ged.pack"] == {"count": 1, "total_s": 1.0, "self_s": 1.0}
    assert r["ged.auto.wait"]["total_s"] == pytest.approx(1.0)
    assert "ged.plan" not in r          # wholly after the window


def test_idle_gaps_named_by_the_innermost_program_span():
    # device busy [5, 8.5): the gaps are [0, 5) and [8.5, 10)
    ev = _events(CALL, ops=[("fusion", 5.0, 3.5)],
                 host=[("bench.window", 0.0, 10.0),
                       ("bench.verify", 1.0, 8.0)])
    r = spans.reduce(ev)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # [0, 5) has its middle at 2.5, inside ged.pack; [8.5, 10) at 9.25,
    # outside every span
    assert gaps == {"ged.pack": pytest.approx(5.0),
                    "untraced": pytest.approx(1.5)}
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    # xplane alone names the first gap after the benchmark's span
    plain = dict(xplane.reduce(ev)["breakdown"]["idle_gaps"])
    assert "bench.verify" in plain and "ged.pack" not in plain


def test_reduce_keeps_every_xplane_output():
    ev = _events(CALL, ops=[("fusion", 5.0, 3.0)])
    base, r = xplane.reduce(ev), spans.reduce(ev)
    for key in ("busy_s", "window_s", "modules", "kernels"):
        assert r[key] == base[key]
    assert r["breakdown"]["device_ops"] == base["breakdown"]["device_ops"]
    assert set(r["spans"]) == {n for n, _, _, _ in CALL}


def test_small_chip_trace_reads_alike():
    """The trace recorded on the chip before the program had spans: the
    reduction reads what ``xplane.reduce`` reads, and the benchmark's own
    spans name the same gaps."""
    ev = spans.read(SMALL)
    base, r = xplane.reduce(xplane.read(SMALL)), spans.reduce(ev)
    for key in ("busy_s", "window_s", "modules", "kernels"):
        assert r[key] == base[key]
    assert all(n.startswith("bench.") for n, _, _, _ in ev["spans"])
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx(
        dict(base["breakdown"]["idle_gaps"]))
    assert r["spans"]["bench.verify"]["count"] >= 1


def _run(trace=None, answered=100, before=None, after=None):
    run = harness.Run(None, answered=answered, trace=trace)
    run.stats_before, run.stats_after = before or {}, after or {}
    return run


@pytest.fixture
def traced():
    r = spans.reduce(_events(CALL + [("ged.exec.compile", 4.1, 0.2, 1)],
                             ops=[("fusion", 5.0, 3.0)]))
    return _run(r, answered=200)


@pytest.mark.parametrize("name,value", [
    ("pack_ms_per_pair.verify", 1e3 * 1.0 / 200),
    # dispatch 0.5 less its compile child 0.2, plus the compile's 0.2
    ("dispatch_ms_per_pair.verify", 1e3 * 0.5 / 200),
    # service 0.5 + engine 0.5 + plan 1.0 + auto.run 1.0
    ("policy_ms_per_pair.verify", 1e3 * 3.0 / 200),
    ("host_wait_share.verify", 100.0 * 3.0 / 10.0),
])
def test_span_readers(traced, name, value):
    assert harness.metric_reader(name)(traced) == pytest.approx(value)


@pytest.mark.parametrize("name", ["pack_ms_per_pair.verify",
                                  "dispatch_ms_per_pair.verify",
                                  "policy_ms_per_pair.verify",
                                  "host_wait_share.verify"])
def test_span_readers_find_nothing_without_program_spans(name):
    """A trace of a program without spans, reduced by ``xplane`` or by
    ``spans``, gives no value (and raises nothing)."""
    ev = _events([("bench.verify", 1.0, 8.0, 1)], ops=[("fusion", 5.0, 3.0)])
    reader = harness.metric_reader(name)
    assert reader(_run(xplane.reduce(ev))) is None
    assert reader(_run(spans.reduce(ev))) is None
    assert reader(_run(None)) is None


def test_pad_share_reader():
    read = harness.metric_reader("pad_share.verify")
    run = _run(before={"executor_pairs": 256, "executor_rows": 388},
               after={"executor_pairs": 768, "executor_rows": 1164})
    assert read(run) == pytest.approx(100.0 * (1 - 256 / 388))
    # a program without the rows counter gives no value
    assert read(_run(before={"executor_pairs": 0},
                     after={"executor_pairs": 256})) is None
