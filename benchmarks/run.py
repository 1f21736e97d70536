"""Benchmark harness entry point: one eval per paper figure (Evals I–IX on
the paper-faithful reference), the batched-engine suite, kernel validation,
and the roofline summary from the dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.run            # quick (default)
  PYTHONPATH=src python -m benchmarks.run --quick    # same, explicit (CI)
  PYTHONPATH=src python -m benchmarks.run --full     # larger sizes
  PYTHONPATH=src python -m benchmarks.run --only eval5,engine

jax's persistent compile cache is on for the whole run (the directory is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
checkout).
"""

from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import eval_engine, eval_kernels, eval_paper
from benchmarks.roofline import load as roofline_load, load_ged, \
    markdown, markdown_ged
from repro.ged.exec import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="explicit quick mode (the default; CI smoke "
                         "steps pass it so intent reads in the workflow)")
    ap.add_argument("--only", default="",
                    help="comma list: eval1..eval9, engine, index, "
                         "deadline, persistence, kernels, eval_kernels, "
                         "compile_cache, roofline")
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    quick = not args.full
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    # tags subsumed by a broader one in a default (no --only) run:
    # "engine" already runs the candidate-index sweep via
    # engine_similarity_search and the anytime-deadline curve via
    # engine_deadline, so those tags only fire when asked for (the CI
    # index-smoke and chaos-smoke steps run `--only index` / `--only
    # deadline`).
    implied = {"index", "deadline"}

    def want(tag: str) -> bool:
        return tag in only if only else tag not in implied

    t0 = time.time()
    failures = []

    # The compile-cache probe starts processes that need the device, so it
    # runs before this process touches JAX (one process per chip).
    if want("compile_cache") or want("eval_kernels"):
        try:
            eval_kernels.kernel_compile_cache(quick=quick)
        except Exception as e:
            failures.append(("compile_cache", e))
            traceback.print_exc()
    enable_compile_cache()

    paper_map = {f"eval{i+1}": fn for i, fn in enumerate(eval_paper.ALL)}
    for tag, fn in paper_map.items():
        if not want(tag):
            continue
        try:
            fn(quick=quick)
        except Exception as e:
            failures.append((tag, e))
            traceback.print_exc()

    engine_map = {
        "engine": (eval_engine.engine_agreement_and_throughput,
                   eval_engine.engine_verification,
                   eval_engine.engine_bound_ablation,
                   eval_engine.engine_sweeps_ablation,
                   eval_engine.engine_backend_throughput,
                   eval_engine.engine_escalation_overlap,
                   eval_engine.engine_similarity_search,
                   eval_engine.engine_deadline,
                   eval_engine.scheduler_cost_model),
        "index": (eval_engine.engine_candidate_index,),
        "deadline": (eval_engine.engine_deadline,),
        # "persistence" is the CI smoke tag for the durable-store rail:
        # cold ingest vs save vs warm open vs journal append (fresh/warm
        # result parity asserted inside, timings informational)
        "persistence": (eval_engine.engine_store_persistence,),
        # "kernels" is the CI smoke tag: oracle validation plus the
        # autotune sweep -> persist -> reload -> dispatch probe (parity
        # asserted inside, timings informational)
        "kernels": (eval_engine.kernel_validation,
                    eval_kernels.kernel_autotune),
        "eval_kernels": eval_kernels.ALL,
    }
    for tag, fns in engine_map.items():
        if not want(tag):
            continue
        for fn in fns:
            try:
                fn(quick=quick)
            except Exception as e:
                failures.append((tag, e))
                traceback.print_exc()

    if want("roofline"):
        rows = roofline_load("single")
        if rows:
            print("\n== Roofline (single-pod, from dry-run artifacts) ==")
            print(markdown(rows))
        ged_rows = load_ged()
        if ged_rows:
            print("\n== GED kernel roofline (from BENCH_engine.json) ==")
            print(markdown_ged(ged_rows))

    print(f"\nbenchmarks done in {time.time() - t0:.0f}s; "
          f"{len(failures)} failures")
    for tag, e in failures:
        print(f"  FAIL {tag}: {e}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
