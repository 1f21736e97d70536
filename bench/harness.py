"""One run of one benchmark cell: set up, warm up, measure, check.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration file its ``configs`` entry names, the traffic mix
``traffic/<traffic>.json``, and one reader per metric,
``metrics/<metric name>.py``.  A cell is added by adding those files and
an entry; nothing here names a cell.

A traffic file says what is asked and how it arrives:

- ``"request"``: ``"verify"`` (batches of ``(q, g, tau)``), ``"range"``
  or ``"topk"`` (corpus queries), with the generator's parameters
  (``edits``, ``tau``, ``k``, ``min_series``; see ``gen.py``);
- ``"loop"``: ``"closed"`` (one caller waits for each reply, then sends
  the next; ``batch`` requests per call) or ``"open"`` (Poisson arrivals
  at ``rate_per_s``, each timed from when it was due);
- ``"warmup"``: how many calls of the same shape to make before the
  window, from a stream of their own;
- ``"warm_shapes"``: (verify) build before the window every engine
  program a call can reach (``warm_shapes``), with
  ``"warm_escalated"`` the most pairs per slot bucket that it expects a
  later rung to take;
- ``"pool"``: how many calls to generate before the window, so that the
  window spends no time generating;
- ``"check"``: at most how many answers to compare with the reference
  (all of them when absent), drawn from the seed.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

import jax

from bench import checks, gen, reference, system

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# A traced run measures a shorter window: its trace must be read back
# within the run's time limit.
TRACE_SECONDS = 10.0
OUT_DIR = os.path.join(HERE, "_out")


# ------------------------------------------------------------ the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = ROOT


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", "") in e2e_names or "moves" not in metric


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return cell_from_files(name, cfg["file"], w["traffic"], root,
                           int(w["chips"]), e2e, layer)


def cell_from_files(name: str, config_file: str, traffic: str,
                    root: str = ROOT, chips: int = 1,
                    end_to_end: List[dict] = (),
                    per_layer: List[dict] = ()) -> Cell:
    """A cell made of a configuration file (relative to ``root``) and the
    traffic file ``bench/traffic/<traffic>.json``."""
    return Cell(name, chips, load_json(os.path.join(root, config_file)),
                load_json(os.path.join(root, "bench", "traffic",
                                       f"{traffic}.json")),
                list(end_to_end), list(per_layer), root)


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run)`` from ``bench/metrics/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------ compile events

class CompileLog:
    """Programs built, by compiling or by loading from the persistent
    cache, as JAX's monitoring events report them: the backend-compile
    event comes for every program, a cache hit besides for those loaded."""

    def __init__(self) -> None:
        self.programs = 0
        self.loaded = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.programs += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    @property
    def compiled(self) -> int:
        return self.programs - self.loaded


# ---------------------------------------------------------------- run

@dataclasses.dataclass
class Run:
    """What a window recorded; the metric readers take their numbers from
    here."""
    cell: Cell
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    # one entry per call of the window
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    answered: int = 0             # certified answers returned
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    setup_programs: int = 0
    window_compiles: int = 0
    stats_before: Dict[str, float] = dataclasses.field(default_factory=dict)
    stats_after: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None
    device_kind: str = ""

    def delta(self, key: str) -> float:
        return (float(self.stats_after.get(key, 0.0))
                - float(self.stats_before.get(key, 0.0)))


def _now() -> float:
    return time.perf_counter()


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


def _call(fn, *args):
    """The answer of one call, or None when the program raised."""
    try:
        return fn(*args)
    except Exception as e:      # a failed request is counted, not fatal
        print(f"bench: request raised {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return None


class Workload:
    """Generates a cell's requests and drives the system with them."""

    def __init__(self, cell: Cell, seed: int):
        self.seed = seed
        self.config, self.traffic = cell.config, cell.traffic
        self.kind = self.traffic["request"]
        self.graphs, self.base_of = (gen.make_corpus(self.config, seed)
                                     if "corpus" in self.config
                                     else (None, None))

    def calls(self, stream_id: int, count: int) -> List:
        """``count`` calls' worth of requests from one stream."""
        rng = gen.stream(self.seed, stream_id)
        if self.kind == "verify":
            b = int(self.traffic["batch"])
            pairs = gen.make_pairs(self.config, self.traffic, rng, b * count)
            return [pairs[i * b:(i + 1) * b] for i in range(count)]
        return gen.make_queries(self.config, self.traffic, self.graphs,
                                self.base_of, rng, count)

    def send(self, sut, request):
        with span(f"bench.{self.kind}"):
            if self.kind == "verify":
                return _call(sut.verify, request)
            if self.kind == "range":
                return _call(sut.range, request["query"], request["tau"])
            return _call(sut.topk, request["query"], request["k"])

    def size(self, request) -> int:
        return len(request) if self.kind == "verify" else 1


def _certified(kind: str, answer) -> int:
    if answer is None:
        return 0
    if kind == "verify":
        return sum(1 for sim, cert in answer if cert and sim is not None)
    return int(all(cert for _, _, cert in answer))


def closed_loop(wl: Workload, sut, calls: List, seconds: float, run: Run):
    """One caller: send, wait for the reply, send the next, until the
    window has passed; the window ends with the last reply."""
    sent, answers = [], []
    t0 = _now()
    i = 0
    while True:
        if i >= len(calls):
            with span("bench.generate"):
                calls.extend(wl.calls(gen.TRAFFIC * 1000 + i, 8))
        start = _now()
        ans = wl.send(sut, calls[i])
        end = _now()
        sent.append(calls[i])
        answers.append(ans)
        run.latencies_s.append(end - start)
        run.answered += _certified(wl.kind, ans)
        i += 1
        if end - t0 >= seconds:
            break
    run.window_s = end - t0
    return sent, answers


def open_loop(wl: Workload, sut, calls: List, seconds: float, run: Run):
    """Requests due at Poisson times; one server answers them in order.
    Latency runs from when a request was due; a request that found the
    server idle waited for its due time, and how late the generator woke
    is recorded apart."""
    rate = float(wl.traffic["rate_per_s"])
    due = gen.arrivals(gen.stream(wl.seed, gen.TRAFFIC, 1), rate, seconds)
    if len(calls) < len(due):
        calls.extend(wl.calls(gen.TRAFFIC * 1000 + len(calls),
                              len(due) - len(calls)))
    answers = []
    t0 = _now()
    for i, d in enumerate(due):
        wait = t0 + d - _now()
        if wait > 0:
            with span("bench.wait"):
                time.sleep(wait)
            run.lateness_s.append(_now() - (t0 + d))
        ans = wl.send(sut, calls[i])
        end = _now()
        answers.append(ans)
        run.latencies_s.append(end - (t0 + d))
        run.answered += _certified(wl.kind, ans)
    run.window_s = max(seconds, end - t0)
    return calls[:len(due)], answers


LOOPS = {"closed": closed_loop, "open": open_loop}


def warm_shapes(wl: Workload, sut) -> int:
    """Build every engine program a verify call can reach; returns
    the number of warm-up calls.

    A call's pairs fall into slot buckets by size, the same buckets in
    every call, since every call holds the same sizes (``gen.mix``).  The
    first rung runs each bucket whole; a later rung runs the pairs an
    earlier one left open, so any count from one to the bucket's, or to
    ``traffic["warm_escalated"]`` where that is smaller.  Each call here holds one
    bucket's worth of pairs at one rung, with ``gen.label_carriers`` so
    that the call's label vocabulary, and with it the program, is the
    window's.
    """
    if not sut.rungs():
        return 0
    cfg, tr = wl.config, wl.traffic
    mol = cfg["molecules"]
    b = int(tr["batch"])
    sizes = gen.stratified_counts(mol["mean_vertices"], mol["max_vertices"], b)
    by_slot: Dict[int, List[int]] = {}
    for n in sizes:
        by_slot.setdefault(sut.shape(int(n), 1)[0], []).append(int(n))
    rng = gen.stream(wl.seed, gen.WARMUP, 1)
    carried: Dict[int, int] = {}
    for q, _, _ in gen.label_carriers(cfg, rng):
        s = sut.shape(len(q[0]), 1)[0]
        carried[s] = carried.get(s, 0) + 1
    lv, le = mol["vertex_labels"], mol["edge_labels"]
    escalated = int(tr.get("warm_escalated", b))
    calls = 0
    for rung in range(sut.rungs()):
        for s, ns in sorted(by_slot.items()):
            counts = [len(ns)] if rung == 0 else range(1, min(len(ns),
                                                             escalated) + 1)
            for c in sorted({sut.shape(ns[0], k)[1] for k in counts}):
                c = min(c, len(ns))
                pairs = []
                for n in ns[:max(c - carried.get(s, 0), 0)]:
                    q = gen.aids_like_graph(rng, n, lv, le)
                    pairs.append((q, gen.perturb(rng, q, 1, lv, le), 1.0))
                sut.verify_at_rung(rung,
                                   pairs + gen.label_carriers(cfg, rng))
                calls += 1
    return calls


# --------------------------------------------------------------- checks

def check(wl: Workload, sent: List, answers: List, run: Run,
          fallbacks: Dict[str, float]) -> checks.Tally:
    tally = checks.Tally()
    tally.fallbacks = int(sum(fallbacks.values()))
    if fallbacks:
        tally.note(f"fallback counters: {fallbacks}")
    cap = wl.traffic.get("check")
    rng = gen.stream(wl.seed, gen.SAMPLE)
    if wl.kind == "verify":
        reqs = [r for batch in sent for r in batch]
        outs = []
        for batch, ans in zip(sent, answers):
            got = list(ans or [])
            if len(got) != len(batch):
                got = (got + [None] * len(batch))[:len(batch)]
            outs.extend(got)
        tally.missing = sum(1 for o in outs if o is None)
        idx = checks.sample(len(reqs), cap, rng)
        checks.check_verify([reqs[i] for i in idx], [outs[i] for i in idx],
                            tally)
        return tally
    bounds = reference.CorpusBounds(wl.graphs,
                                    wl.config["corpus"]["vertex_labels"],
                                    wl.config["corpus"]["edge_labels"])
    tally.missing = sum(1 for a in answers if a is None)
    idx = checks.sample(len(sent), cap, rng)
    if wl.kind == "range":
        checks.check_range(bounds, wl.graphs, [sent[i] for i in idx],
                           [answers[i] for i in idx], tally)
    else:
        checks.check_topk(bounds, wl.graphs, wl.base_of,
                          [sent[i] for i in idx], [answers[i] for i in idx],
                          tally)
    return tally


def _failed(kind: str, sent: List, answers: List) -> int:
    if kind == "verify":
        return sum(len(b) - _certified(kind, a) for b, a in zip(sent,
                                                               answers))
    return sum(1 - _certified(kind, a) for a in answers)


# ------------------------------------------------------------------ main

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             started: float, chip: bool = True,
             build: Callable = system.build) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``started`` is the process's start on the ``perf_counter`` clock.
    ``chip=False`` skips the look for a chip (for tests on the CPU), and
    ``build`` makes the system under test from the configuration and the
    corpus.
    """
    devices = system.require_chip(cell.chips) if chip else jax.devices()
    cache_dir = system.enable_compile_cache() if chip else None
    log = CompileLog()
    run = Run(cell, device_kind=devices[0].device_kind)
    print(f"bench cell={cell.name} seed={seed} device={devices[0].platform}"
          f":{devices[0].device_kind!r}x{len(devices)} "
          f"compile_cache={cache_dir}", flush=True)

    t = _now()
    wl = Workload(cell, seed)
    gen_s = _now() - t
    t = _now()
    sut = build(cell.config, wl.graphs)
    build_s = _now() - t
    calls = wl.calls(gen.TRAFFIC, int(cell.traffic.get("pool", 1)))
    t = _now()
    shape_calls = warm_shapes(wl, sut) if cell.traffic.get("warm_shapes") \
        else 0
    for req in wl.calls(gen.WARMUP, int(cell.traffic.get("warmup", 1))):
        wl.send(sut, req)
    warm_s = _now() - t
    run.setup_programs = log.programs
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    print(f"bench setup: generate_s={gen_s:.3f} build_s={build_s:.3f} "
          f"warmup_s={warm_s:.3f} shape_calls={shape_calls} "
          f"programs={log.programs} "
          f"compiled={log.compiled} loaded={log.loaded} "
          f"compile_s={log.seconds:.3f}", flush=True)

    run.stats_before = sut.stats()
    before, before_s = log.programs, log.seconds
    run.setup_s = _now() - started
    loop = LOOPS[cell.traffic["loop"]]
    if trace:
        from bench import xplane
        trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
        xplane.clear(trace_dir)
        # host spans at the level of TraceAnnotation, no Python tracer:
        # a smaller trace, and less cost on the host
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level, options.python_tracer_level = 1, 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with span(xplane.WINDOW_SPAN):
                sent, answers = loop(wl, sut, calls, window, run)
        finally:
            jax.profiler.stop_trace()
    else:
        with span("bench.window"):
            sent, answers = loop(wl, sut, calls, window, run)
    run.window_compiles = log.programs - before
    run.stats_after = sut.stats()
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    fallbacks = system.fallbacks(run.stats_after)
    if run.lateness_s:
        print(f"bench generator: late_max_ms="
              f"{1e3 * max(run.lateness_s):.3f} late_mean_ms="
              f"{1e3 * statistics.mean(run.lateness_s):.3f} "
              f"idle_waits={len(run.lateness_s)} of {len(sent)}", flush=True)
    print(f"bench window: seconds={run.window_s:.3f} calls={len(sent)} "
          f"window_compiles={run.window_compiles} "
          f"window_compile_s={log.seconds - before_s:.3f}", flush=True)
    moved = {k: run.delta(k) for k in sorted(run.stats_after)
             if isinstance(run.stats_after[k], (int, float)) and run.delta(k)}
    print(f"bench counters: {moved}", flush=True)
    if trace:
        from bench import xplane
        run.trace = xplane.reduce_dir(trace_dir)
        xplane.clear(trace_dir)
    sut.close()

    run.attempted = sum(wl.size(r) for r in sent)
    run.failed = _failed(wl.kind, sent, answers)
    t = _now()
    tally = check(wl, sent, answers, run, fallbacks)
    run.failed += tally.wrong
    print(f"bench reference: seconds={_now() - t:.3f} "
          f"checked={tally.checked}", flush=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": tally.correct(), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    for n in tally.notes:
        print(f"bench check: {n}", file=sys.stderr, flush=True)
    for k, v in tally.numbers().items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr,
              flush=True)
    out["checks"] = tally.numbers()
    return out
