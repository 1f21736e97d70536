"""The verify path's host spans (``repro.ged.spans``): one verify call
recorded by the JAX profiler holds every span the path reaches, nested as
the calls are, with their stats, and the executor's ``rows`` counter adds
up the padded batches the dispatches carried."""

import glob
import os
import re

import numpy as np
import pytest

import jax

from repro.data.graphs import perturb, random_graph
from repro.ged.plan import padded_batch
from repro.ged.spans import SPANS
from repro.serving.ged_service import GedRequest, GedVerificationService

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


def _requests(seed, sizes):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        q = random_graph(rng, n, density=0.5, n_vlabels=3, n_elabels=2)
        g = perturb(rng, q, 3, n_vlabels=3, n_elabels=2)
        out.append(GedRequest(q, g, tau=3.0))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One cold verify call of 7 pairs in two slot buckets (4 and 8
    slots), the ladder cut to one small rung so that pairs it leaves open
    reach the host solver; returns the call's outcomes, the service and
    its host spans as ``(name, start, end, line, stats)``."""
    svc = GedVerificationService(batch_size=16, slots=None, cache_size=0)
    svc.scheduler.rungs = ((4, 1, 1),)
    reqs = _requests(3, [3, 4, 4, 6, 7, 7, 8])
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level, options.python_tracer_level = 1, 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        outs = svc.verify(reqs)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ged."):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   (plane.name, line_no), dict(ev.stats)))
    return outs, svc, events


def _one(events, name):
    found = [e for e in events if e[0] == name]
    assert found, name
    return found


def test_every_span_of_the_verify_path_is_recorded(recorded):
    outs, svc, events = recorded
    assert all(o.certified for o in outs)
    assert svc.stats["host_solved"] > 0        # the host tail ran
    assert {e[0] for e in events} == {name for name, _ in SPANS}


def test_spans_nest_as_the_calls_do(recorded):
    _, _, events = recorded

    def inside(child, parent):
        return (child[3] == parent[3] and parent[1] <= child[1]
                and child[2] <= parent[2])

    chain = ["ged.exec.dispatch", "ged.auto.run", "ged.engine.run",
             "ged.service.verify"]
    for child, parent in zip(chain, chain[1:]):
        for c in _one(events, child):
            assert any(inside(c, p) for p in _one(events, parent)), child
    for child, parent in [("ged.pack", "ged.plan"),
                          ("ged.pack", "ged.auto.rebucket"),
                          ("ged.exec.compile", "ged.exec.dispatch"),
                          ("ged.auto.host_solve", "ged.auto.run")]:
        assert any(inside(c, p) for c in _one(events, child)
                   for p in _one(events, parent)), (child, parent)


def test_spans_carry_their_stats(recorded):
    _, _, events = recorded
    (call,) = _one(events, "ged.service.verify")
    assert call[4] == {"call": 1, "pairs": 7}
    (run,) = _one(events, "ged.engine.run")
    assert run[4] == {"pairs": 7, "run": 7}
    dispatches = _one(events, "ged.exec.dispatch")
    assert sorted(e[4]["slots"] for e in dispatches) == [4, 8]
    for e in dispatches:
        assert e[4]["rung"] == 0
        assert e[4]["batch"] == padded_batch(e[4]["real"])
    for e in _one(events, "ged.exec.compile"):
        assert {"slots", "batch"} <= set(e[4])


def test_executor_rows_add_up_the_padded_batches(recorded):
    _, svc, events = recorded
    dispatches = _one(events, "ged.exec.dispatch")
    s = svc.stats
    # 3 pairs at 4 slots pad to 4 rows, 4 pairs at 8 slots to 4 rows
    assert s["executor_pairs"] == sum(e[4]["real"] for e in dispatches) == 7
    assert s["executor_rows"] == sum(e[4]["batch"] for e in dispatches) == 8


def test_spans_list_names_every_span_the_program_opens():
    """``SPANS`` is the whole list: every ``span("...")`` in the program
    names a member, and every member is opened somewhere."""
    opened = set()
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            opened |= set(re.findall(r'span\(\s*"(ged\.[a-z_.]+)"', f.read()))
    assert opened == {name for name, _ in SPANS}
    assert len(opened) == len(SPANS)
    assert all(meaning for _, meaning in SPANS)
