"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  * bench_cholesky — Fig. 5 (naive O(n^3) vs lazy O(n^2) factorization)
  * bench_levy     — Tab. 1 (5-D Levy convergence, 1 vs 100 seeds)
  * bench_lag      — Fig. 6 (lagging-factor sweep)
  * bench_nn_hpo   — Fig. 1 + Tabs. 2/3 (network-trainer HPO overhead)
  * bench_parallel — Tab. 4 (top-t parallel suggestions)
  * bench_substrate — one BO step per (mode x linalg implementation),
                      emits BENCH_substrate.json
  * bench_pool     — multi-tenant StudyPool vs S sequential schedulers,
                      emits BENCH_pool.json
  * bench_shard    — device-mesh suggest-round scaling at 1/2/4/8 devices,
                      emits BENCH_shard.json
  * bench_serve    — coalesced ask–tell gateway vs per-client dispatches
                      at 16 concurrent clients, emits BENCH_serve.json
  * bench_mixed    — mixed (float/int/categorical) space through the
                      gateway + mixed-gram substrate parity at 1 and 8
                      virtual devices, emits BENCH_mixed.json
  * bench_tier     — saturation escalation tier: suggest latency past
                      n_max (lazy-GP quadratic vs flat neural-basis) and
                      EI-per-unit-cost vs plain EI at a fixed evaluation
                      cost budget, emits BENCH_tier.json

`python -m benchmarks.run [--full] [--only NAME]`.  The roofline analysis
(§Roofline) is separate: `python -m benchmarks.roofline results/*.jsonl`
over the dry-run output.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale iteration counts (slow)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_cholesky, bench_lag, bench_levy,
                            bench_mixed, bench_nn_hpo, bench_parallel,
                            bench_pool, bench_serve, bench_shard,
                            bench_substrate, bench_tier)
    suites = {
        "cholesky": lambda: bench_cholesky.run(full=args.full),
        "levy": lambda: bench_levy.run(full=args.full),
        "lag": lambda: bench_lag.run(full=args.full),
        "nn_hpo": lambda: bench_nn_hpo.run(full=args.full),
        "parallel": lambda: bench_parallel.run(full=args.full),
        "substrate": lambda: bench_substrate.run(full=args.full),
        "pool": lambda: bench_pool.run(full=args.full),
        "shard": lambda: bench_shard.run(full=args.full),
        "serve": lambda: bench_serve.run(full=args.full),
        "mixed": lambda: bench_mixed.run(full=args.full),
        "tier": lambda: bench_tier.run(full=args.full),
    }
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
        except Exception as e:  # pragma: no cover
            print(f"{name}_FAILED,,{type(e).__name__}: {e}", flush=True)
            raise
        print(f"# {name} suite: {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
