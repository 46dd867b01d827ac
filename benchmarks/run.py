"""Benchmark harness: one module per paper table/figure (+ framework perf).

Prints ``name,us_per_call,derived`` CSV per row and dumps the full records
to results/bench.json. The default set is the fast model-free suites;
``--all`` adds the serving benchmarks that build and drive real models
through the coded runtime (``serve_throughput``, ``chaos_resilience``) —
their ``run()`` entries also refresh the committed artifacts
(``BENCH_serve.json``, ``BENCH_chaos.json``) and append one trajectory
snapshot per bench/arch to ``BENCH_history.jsonl``, so ONE command
regenerates every artifact the CI perf-trajectory gate checks.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.launch.compile_cache import REPO_ROOT, enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true",
                    help="include the runtime serving benchmarks "
                         "(serve_throughput, chaos_resilience)")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (coded_overhead, fig2_data_loss, fig12_recovery,
                            fig16_straggler, fig17_coverage, multi_failure,
                            roofline_table, tab1_suitability)

    suites = [
        ("fig2_data_loss", fig2_data_loss.run),
        ("fig12_recovery", fig12_recovery.run),
        ("fig16_straggler", fig16_straggler.run),
        ("fig17_coverage", fig17_coverage.run),
        ("tab1_suitability", tab1_suitability.run),
        ("coded_overhead", coded_overhead.run),
        ("coded_overhead_kernels", coded_overhead.run_kernels),
        ("multi_failure", multi_failure.run),
        ("roofline_table", roofline_table.run),
    ]
    if args.all:
        from benchmarks import chaos_resilience, serve_throughput
        suites += [
            ("serve_throughput", serve_throughput.run),
            ("chaos_resilience", chaos_resilience.run),
        ]

    all_results = {}
    print("name,us_per_call,derived")
    for name, fn in suites:
        t0 = time.perf_counter()
        rows = fn()
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        all_results[name] = rows
        for row in rows:
            us_val = next((row[k] for k in row
                           if isinstance(row.get(k), (int, float))
                           and str(k).startswith("us_")), round(us, 1))
            derived = {k: v for k, v in row.items()
                       if not str(k).startswith("us_")}
            print(f"{name},{us_val},\"{derived}\"")

    out_dir = os.path.join(REPO_ROOT, "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench.json"), "w") as f:
        json.dump(all_results, f, indent=1, default=str)
    print(f"# wrote results/bench.json with {len(all_results)} suites")


if __name__ == '__main__':
    main()
