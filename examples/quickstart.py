"""Quickstart: lazy-GP Bayesian optimization of the 5-D Levy function.

Reproduces the paper's core loop in ~a minute on CPU:

    python examples/quickstart.py [--iterations 120] [--mode lazy|naive]

The lazy GP (paper Alg. 3) does one O(n^2) incremental Cholesky append per
iteration; `--mode naive` refits the kernel and refactorizes fully (O(n^3))
every iteration, which is the baseline the paper beats.
"""
import argparse
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax.numpy as jnp  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import levy_bounds, neg_levy, run_bo  # noqa: E402


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iterations", type=int, default=120)
    ap.add_argument("--mode", default="lazy", choices=["lazy", "naive"])
    ap.add_argument("--lag", type=int, default=0,
                    help="lazy mode: full kernel refit every LAG steps")
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--implementation", default="auto",
                    choices=["auto", "pallas", "xla", "ref"],
                    help="linalg substrate: auto picks Pallas on TPU, XLA "
                         "elsewhere")
    args = ap.parse_args()

    objective = lambda x: np.asarray(neg_levy(jnp.asarray(x)))
    lo, hi = levy_bounds(5)
    _, hist = run_bo(objective, lo, hi, args.iterations, dim=5,
                     mode=args.mode, lag=args.lag, n_seed=args.seeds,
                     n_max=args.iterations + args.seeds + 8, seed=0,
                     implementation=args.implementation)

    print(f"\nmode={args.mode} lag={args.lag}")
    for frac in (0.25, 0.5, 0.75, 1.0):
        i = max(0, int(len(hist.best_y) * frac) - 1)
        print(f"  after {i + 1:4d} evals: best = {hist.best_y[i]:9.4f}")
    x, y = hist.best()
    print(f"  optimum found: f = {y:.4f} at x = {np.round(x, 3)}"
          f"   (true optimum: 0 at [1 1 1 1 1])")
    print(f"  mean GP update: {1e3 * np.mean(hist.gp_seconds):.2f} ms; "
          f"mean suggestion: {1e3 * np.mean(hist.acq_seconds):.2f} ms")


if __name__ == "__main__":
    main()
