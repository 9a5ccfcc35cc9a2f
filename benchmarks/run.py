"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, where
``derived`` is the benchmark's headline statistic (JSON-encoded).
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import use_compile_cache


BENCHES = [
    ("convergence", "benchmarks.bench_convergence"),     # Fig. 7-10
    ("strategies", "benchmarks.bench_strategies"),       # Fig. 11
    ("synopsis", "benchmarks.bench_synopsis"),           # Fig. 12-13
    ("utilization", "benchmarks.bench_utilization"),     # Fig. 14
    ("bounds_mc", "benchmarks.bench_bounds_mc"),         # Table 3
    ("kernels", "benchmarks.bench_kernels"),             # EXTRACT hot spot
    ("slot_kernel", "benchmarks.bench_slot_kernel"),     # fused round extract
    ("ola_eval", "benchmarks.bench_ola_eval"),           # beyond-paper eval
    ("workload", "benchmarks.bench_workload"),           # shared-scan serving
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced repetitions (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    print("name,us_per_call,derived")
    failures = []
    for name, module in BENCHES:
        if args.only and name != args.only:
            continue
        mod = __import__(module, fromlist=["run"])
        t0 = time.perf_counter()
        try:
            derived = mod.run(fast=args.fast)
        except Exception as e:  # noqa: BLE001 — keep the harness going
            failures.append((name, repr(e)))
            print(f"{name},ERROR,{e!r}", flush=True)
            continue
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
