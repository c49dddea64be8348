"""Paper-fidelity accuracy regression: lazy GP vs the exact-GP baseline.

The paper's core claim (abstract, Sec. 4) is that the lazy GP reaches the
baseline's optimization accuracy — "outperforming the previous approach
regarding optimization accuracy" — while decoupling the O(n^3)
factorization from the iteration loop.  This suite pins that claim as a
tier-1 regression on the paper's own synthetic benchmark (Levy, Sec. 4.1):

  * `mode="lazy"`  — frozen kernel params, O(n^2) incremental appends
    (the contribution);
  * `mode="naive"` — per-iteration full refactorization with kernel
    hyper-parameter refit (the "previous approach" baseline).

Both run the identical suggestion machinery with pinned seeds, so the runs
are deterministic (plain pytest asserts, no property machinery — they pass
identically under real hypothesis or the conftest fallback).  Regret is
measured against the known optimum f* = 0 at x* = 1 (maximization of the
negative Levy function).

Budgets are tuned to keep the whole file in single-digit seconds of
tier-1 time while separating the two modes' behavior.
"""
import numpy as np
import pytest

from repro.core import levy_bounds, neg_levy, run_bo
from repro.core.acquisition import AcqConfig

DIM = 4
SEEDS = (0, 1, 2)
ITERATIONS = 30
N_SEED = 8                 # random seed trials before BO rounds
OPTIMUM = 0.0              # max of -levy at the all-ones vector
ACQ = AcqConfig(restarts=24, ascent_steps=12)


def _objective(x: np.ndarray) -> np.ndarray:
    return np.asarray(neg_levy(x))


def _regret(mode: str, seed: int, lag: int = 0) -> float:
    lo, hi = levy_bounds(DIM)
    _, hist = run_bo(_objective, lo, hi, iterations=ITERATIONS, dim=DIM,
                     mode=mode, lag=lag, n_max=ITERATIONS + N_SEED + 2,
                     n_seed=N_SEED, seed=seed, acq=ACQ)
    return OPTIMUM - hist.best_y[-1]


@pytest.fixture(scope="module")
def regrets():
    """One (mode x seed) sweep shared by every assertion below."""
    return {mode: [_regret(mode, s) for s in SEEDS]
            for mode in ("lazy", "naive")}


def test_lazy_matches_exact_gp_accuracy_per_seed(regrets):
    """The paper's accuracy claim, per pinned seed: the lazy GP's best-value
    regret at a fixed step budget is no worse than the exact baseline's,
    up to a float/trajectory tolerance."""
    for lz, nv in zip(regrets["lazy"], regrets["naive"]):
        assert lz <= nv + 0.75, (regrets["lazy"], regrets["naive"])


def test_lazy_matches_exact_gp_accuracy_mean(regrets):
    """Aggregate form (tighter): mean regret over the pinned seeds."""
    mean_lazy = float(np.mean(regrets["lazy"]))
    mean_naive = float(np.mean(regrets["naive"]))
    assert mean_lazy <= mean_naive + 0.25, (mean_lazy, mean_naive)


def test_lazy_absolute_quality(regrets):
    """The lazy GP actually optimizes (regret far below a random-search
    floor — random uniform on [-10,10]^4 leaves regret ~15+ at this
    budget), so the comparative test above can't pass vacuously."""
    assert float(np.mean(regrets["lazy"])) < 3.0, regrets["lazy"]
    assert min(regrets["lazy"]) < 1.5, regrets["lazy"]


def test_lagged_refit_tracks_fully_lazy():
    """Lag-l refits (the paper's middle ground) stay within the same
    accuracy envelope as the fully lazy run on a pinned seed."""
    lazy = _regret("lazy", seed=0)
    lagged = _regret("lazy", seed=0, lag=10)
    assert lagged <= lazy + 1.5, (lagged, lazy)


def test_runs_are_deterministic():
    """Pinned seeds => bitwise-identical best values (the regression is
    meaningful because reruns cannot drift)."""
    assert _regret("lazy", seed=0) == _regret("lazy", seed=0)


# -- young studies: grown from n = 0 by EI appends alone ----------------------

def _bench_reference():
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), os.pardir, "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from reference.gp import Posterior, matern52
    from reference.objective import neg_levy_unit
    return Posterior, matern52, neg_levy_unit


@pytest.mark.parametrize("seed", [1, 2])
def test_young_study_tracks_exact_gp_mean(seed):
    """A study grown from n = 0 by its own EI suggestions alone (clustered
    points, the service's GP settings: rho 0.25, noise2 1e-5, lag 0, a
    re-anchor at 128 appends) serves a posterior mean within 1.5e-5 of the
    float64 exact GP, over its history's std, up to n 130: ten times
    inside the service's 1.5e-4 margin.  The served covariances are
    evaluated on differences (DESIGN.md §4): these studies read at most
    7.4e-6, and 3.2e-5 and 6.2e-5 with the expanded squared distance."""
    from repro.hpo.pool import SchedulerConfig, StudyPool
    from repro.hpo.space import Dim, SearchSpace
    Posterior, matern52, neg_levy_unit = _bench_reference()
    S, n_grow = 12, 130
    space = SearchSpace(tuple(Dim(f"x{i}", 0.0, 1.0) for i in range(5)))
    cfg = SchedulerConfig(
        n_max=256, rho0=0.25, noise2=1e-5, lag=0, inv_refresh=128, seed=seed,
        acq=AcqConfig(name="ei", xi=0.01, restarts=48, ascent_steps=20,
                      lr=0.05, dedup_radius=0.08))
    pool = StudyPool([space] * S, cfg)
    shifts = np.random.default_rng(seed).uniform(-2.0, 2.0, (S, 5))
    hist = [[] for _ in range(S)]
    pending = pool.advance_round([])
    worst = 0.0
    for n in range(1, n_grow + 1):
        events = []
        for s in range(S):
            tr = pending[s][0]
            v = neg_levy_unit(tr.unit, shifts[s], -10.0, 10.0, 50.0)
            events.append((s, tr, v))
            hist[s].append((np.asarray(tr.unit, np.float64), v))
        pending = pool.advance_round(events)
        if n < 8 or (n % 6 and n < 126):
            continue
        for s in range(S):
            st = pool.engine.study_state(s)
            x = np.asarray([u for u, _ in hist[s]])
            y = np.asarray([v for _, v in hist[s]])
            xq = np.concatenate([x[-8:], np.random.default_rng(
                [seed, s, n]).uniform(0.0, 1.0, (16, 5))])
            sigma2, rho = float(st.params.sigma2), float(st.params.rho)
            want, _ = Posterior(x, y, sigma2, rho, 1e-5)(xq)
            # the served mean, read in float64 as the benchmark's check does
            ks = matern52(np.asarray(st.x_buf)[:n], xq, sigma2, rho)
            got = ks.T @ np.asarray(st.alpha, np.float64)[:n] + float(
                np.mean(np.asarray(st.y_buf, np.float64)[:n]))
            worst = max(worst, float(np.max(np.abs(got - want)) / np.std(y)))
    assert worst < 1.5e-5, worst
