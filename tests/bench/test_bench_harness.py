"""The harness: rates over all the work of the whole window, tails over
every request, open-loop latency from when a request was due, cells found
by name from files alone, and no result off the chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import control, gen, harness, tiny  # noqa: E402


class Sleeper:
    """A system whose every call takes ``seconds`` and answers yes."""

    def __init__(self, seconds):
        self.seconds, self.calls = seconds, 0

    def verify(self, pairs):
        time.sleep(self.seconds)
        self.calls += 1
        return [(True, True)] * len(pairs)

    def range(self, query, tau):
        time.sleep(self.seconds)
        self.calls += 1
        return []


def _workload(name):
    wl = harness.Workload.__new__(harness.Workload)
    cell = (tiny.tiny(harness.find_cell(name)) if name == "aids-verify.batch"
            else tiny.search(name))
    wl.seed, wl.config, wl.traffic = 1, cell.config, dict(cell.traffic)
    wl.kind = wl.traffic["request"]
    wl.graphs, wl.base_of = gen.make_corpus(cell.config, 1) \
        if "corpus" in cell.config else (None, None)
    return wl, cell


def test_closed_loop_rate_is_all_work_over_whole_window():
    wl, cell = _workload("aids-verify.batch")
    sut = Sleeper(0.12)
    run = harness.Run(cell)
    sent, answers = harness.closed_loop(wl, sut, wl.calls(gen.TRAFFIC, 2),
                                        0.5, run)
    # the call that crosses the close is counted, and so is its time
    assert len(sent) == sut.calls == len(run.latencies_s) >= 2
    assert run.window_s >= 0.5 > run.window_s - run.latencies_s[-1]
    assert run.answered == 8 * len(sent)
    rate = harness.metric_reader("pairs_per_s")(run)
    assert rate == pytest.approx(8 * len(sent) / run.window_s)
    assert rate < 8 / 0.12


def test_open_loop_latency_counts_from_due_time():
    wl, cell = _workload("range-open")
    wl.traffic["rate_per_s"] = 20.0
    sut = Sleeper(0.1)          # slower than the arrivals: a queue builds
    run = harness.Run(cell)
    t0 = time.perf_counter()
    sent, answers = harness.open_loop(wl, sut, [], 1.0, run)
    elapsed = time.perf_counter() - t0
    assert len(sent) == len(run.latencies_s) == 20
    due = gen.arrivals(gen.stream(1, gen.TRAFFIC, 1), 20.0, 1.0)
    # request i finishes no earlier than (i + 1) service times after start
    for i, (d, lat) in enumerate(zip(due, run.latencies_s)):
        assert d + lat >= 0.1 * (i + 1) - 1e-3
    assert max(run.latencies_s) > 0.9        # the wait in the queue counts
    assert run.window_s == pytest.approx(elapsed, abs=0.05)


def test_tail_is_over_every_request():
    wl, cell = _workload("range-open")
    run = harness.Run(cell)
    run.latencies_s = [0.01] * 90 + [1.0] * 10
    p90 = harness.metric_reader("query_p90_ms")(run)
    p50 = harness.metric_reader("query_p50_ms")(run)
    assert p50 == pytest.approx(10.0)
    assert 10.0 < p90 <= 1000.0
    run.latencies_s = [0.01] * 80 + [1.0] * 20
    assert harness.metric_reader("query_p90_ms")(run) == pytest.approx(1000.0)


def test_cell_added_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (root / "bench/traffic/range-zipf.json").write_text(json.dumps(
        {"request": "range", "loop": "closed", "edits": [1, 2],
         "tau": [1, 2], "warmup": 1, "pool": 4}))
    (root / "bench/metrics/hits_per_query.zipf.py").write_text(
        "def read(run):\n    return run.delta('hits') / run.attempted\n")
    bench["configs"].append({"name": "aids-search", "source": "test",
                             "file": "bench/configs/aids-search.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "aids-search.zipf",
                               "config": "aids-search",
                               "traffic": "range-zipf", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "queries_per_s.zipf",
                                "unit": "queries/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["aids-search.zipf"]})
    shutil.copy(root / "bench/metrics/queries_per_s.py",
                root / "bench/metrics/queries_per_s.zipf.py")
    bench["per_layer"].append({"name": "hits_per_query.zipf", "unit": "hits",
                               "better": "higher", "source": "program_counter",
                               "layer": "corpus store", "moves":
                               "queries_per_s.zipf",
                               "workloads": ["aids-search.zipf"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = tiny.tiny(harness.find_cell("aids-search.zipf", str(root)), 120)
    assert cell.traffic["loop"] == "closed" and cell.root == str(root)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "queries_per_s.zipf"]
    out = harness.run_cell(cell, 5, 0.3, False, time.perf_counter(),
                           chip=False, build=control.Control)
    assert set(out["metrics"]) == {"setup_s", "queries_per_s.zipf"}
    assert out["attempted"] >= 1


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "aids-verify.batch",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def _has_result(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_cpu_run_exits_nonzero_without_result():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0 and not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read()
                        )["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("_out", "__pycache__"))
    p = _bench_cmd(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and not _has_result(p.stdout)
