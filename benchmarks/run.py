# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator: paper figures (modeled, Table-II-parameterized)
plus measured microbenchmarks of the executable JAX/Pallas implementation.

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig14,micro]
                                               [--json BENCH_accum.json]
                                               [--trace trace.json]

``--json PATH`` additionally dumps the collected rows as JSON — the CI smoke
mode is ``--only accum-backends --json BENCH_accum.json`` (tiny shapes, CPU),
which keeps a perf trajectory artifact on every push.

``--trace PATH`` enables the repro.obs tracer for the whole run and exports
a Chrome-trace JSON (load in chrome://tracing or Perfetto) with the metrics
snapshot (planner evidence, cache counters, histograms) merged at top level
under ``"metrics"``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table1,fig14..fig19,micro,accum,"
                         "accum-backends,plan-cache,serve-sparse,dist,"
                         "dist-2d,moe,lm")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write collected rows as JSON to PATH")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="enable repro.obs tracing and export a Chrome-trace"
                         " JSON (with metrics merged) to PATH")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.trace:
        import repro.obs as obs
        obs.enable(reset=True)

    from . import paper_figures as pf
    from . import microbench as mb

    suites = [
        ("table1", pf.table1),
        ("fig14", pf.fig14_performance),
        ("fig15", pf.fig15_energy),
        ("fig16", pf.fig16_utilization),
        ("fig17", pf.fig17_sparsity),
        ("fig18", pf.fig18_stddev),
        ("fig19", pf.fig19_scaling),
        ("micro", mb.spgemm_micro),
        ("kernels", mb.kernels_micro),
        ("accum", mb.sort_merge_micro),
        ("accum-backends", mb.accum_backends_micro),
        ("plan-cache", mb.plan_cache_micro),
        ("serve-sparse", mb.serve_sparse_micro),
        ("dist", mb.dist_spgemm_micro),
        ("dist-2d", mb.dist2d_micro),
        ("moe", mb.moe_dispatch_micro),
        ("lm", mb.lm_step_micro),
    ]
    collected = []
    print("name,us_per_call,derived")
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(f"{row[0]},{row[1]},{row[2]}", flush=True)
                collected.append({"name": row[0], "us_per_call": row[1],
                                  "derived": row[2]})
        except Exception as e:  # a failed suite must not hide the others
            print(f"{name}/ERROR,0,{e!r}", file=sys.stderr, flush=True)
            raise
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": collected}, f, indent=1)
        print(f"# wrote {len(collected)} rows to {args.json}",
              file=sys.stderr, flush=True)
    if args.trace:
        import repro.obs as obs
        obs.export_chrome(args.trace,
                          extra={"metrics": obs.metrics.snapshot()})
        n_ev = len(obs.get_tracer().snapshot()["events"])
        print(f"# wrote {n_ev} trace events to {args.trace}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
