"""Multi-tenant HPO service: a request-driven suggest/report loop over a
StudyPool (the ROADMAP's "serve heavy traffic" shape, in miniature).

    python examples/hpo_service.py [--studies 8] [--budget 12] [--workers 8] \
        [--mesh auto]          # shard the suggest path over a device mesh (§8)
        [--categorical-tenant]  # last tenant optimizes a Categorical space (§10)

S tenants run concurrent HPO studies against one batched lazy-GP engine:
each service round issues ONE fused `advance_round` dispatch — the masked
absorb of every drained completion AND the batched suggest for every
tenant with an open request run in a single jitted program with donated
state buffers (DESIGN.md §8).  Suggestions go to worker threads (the
"trainers"); results are absorbed in completion order, so a slow tenant
never blocks a fast one.  With --mesh the suggest path shards over a
device mesh; with --ckpt-dir the whole pool rides one atomic checkpoint
and a second invocation resumes every tenant's posterior.

Each tenant optimizes its own synthetic objective (a shifted smooth bowl on
the unit cube, distinct optimum per tenant) so per-study convergence is
visible in the final report.  With --categorical-tenant the last tenant
runs a MIXED space (a 3-way categorical choice, same encoded width as the
float tenants' ResNet space) through the very same batched rounds —
heterogeneous type layouts share one stacked program (DESIGN.md §10).
"""
import argparse
import pathlib
import sys
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro import compile_cache  # noqa: E402
from repro.hpo.pool import SchedulerConfig, StudyPool  # noqa: E402
from repro.hpo.space import (Categorical, RESNET_SPACE,  # noqa: E402
                             SearchSpace)

# Same encoded width (3) as RESNET_SPACE, so both layouts stack in one
# rectangular pool; the engine's per-study type descriptor keeps the
# categorical tenant's suggestions on its one-hot lattice.
CATEGORICAL_SPACE = SearchSpace((
    Categorical("optimizer", ("sgd", "adam", "rmsprop")),
))
CATEGORICAL_SCORE = {"sgd": -0.3, "adam": 0.0, "rmsprop": -0.6}


def make_objective(sid: int, latency: float, space=None):
    """Tenant sid's trainer: smooth bowl with a per-tenant optimum (float
    tenants) or a per-choice score table (the categorical tenant)."""
    center = 0.15 + 0.7 * ((sid * 0.37) % 1.0)

    def objective(unit: np.ndarray) -> float:
        time.sleep(latency * (1.0 + 0.5 * ((sid + 1) % 3)))  # uneven tenants
        if space is not None and space.has_discrete:
            return CATEGORICAL_SCORE[
                space.to_hparams(np.asarray(unit))["optimizer"]]
        return float(-np.sum((np.asarray(unit) - center) ** 2))

    return objective


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--studies", type=int, default=8)
    ap.add_argument("--budget", type=int, default=12,
                    help="observations to absorb per study")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--latency", type=float, default=0.02,
                    help="simulated per-trial train time (s)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--implementation", default="auto",
                    choices=["auto", "pallas", "xla", "ref"])
    ap.add_argument("--mesh", default="none",
                    help="device mesh for the batched suggest path "
                         "(DESIGN.md §8): none | auto | SxR (e.g. 4x2). "
                         "On CPU, export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 first")
    ap.add_argument("--categorical-tenant", action="store_true",
                    help="give the last tenant a mixed (categorical) "
                         "search space (DESIGN.md §10)")
    args = ap.parse_args()

    spaces = [RESNET_SPACE] * args.studies
    if args.categorical_tenant:
        spaces[-1] = CATEGORICAL_SPACE
    cfg = SchedulerConfig(n_max=args.budget + 8, seed=0,
                          implementation=args.implementation,
                          mesh=args.mesh,
                          ckpt_dir=args.ckpt_dir)
    pool = StudyPool(spaces, cfg,
                     names=[f"tenant{i}" for i in range(args.studies)])
    if args.ckpt_dir and pool.restore():
        print("resumed pool: " + ", ".join(
            f"{h.name} n={pool.engine.n(h.study_id)}"
            for h in pool.studies))

    objectives = [make_objective(s, args.latency, spaces[s])
                  for s in range(args.studies)]
    t0 = time.perf_counter()
    suggested = 0
    with ThreadPoolExecutor(args.workers) as workers:
        inflight = {}   # Future -> (study_id, Trial)
        events = []     # drained completions awaiting absorption

        def open_requests():
            """Tenants below budget with no trial in flight this round
            (counting completions about to be absorbed)."""
            busy = {sid for sid, _ in inflight.values()}
            incoming: dict[int, int] = {}
            for sid, _, _ in events:
                incoming[sid] = incoming.get(sid, 0) + 1
            return [s for s in range(args.studies)
                    if s not in busy
                    and pool.engine.n(s) + incoming.get(s, 0) < args.budget]

        while True:
            ready = open_requests()
            if events or ready:
                # ONE fused dispatch absorbs every drained completion and
                # serves every open suggest request (advance_round; tenants
                # at budget absorb without drawing a new trial).
                suggestions = pool.advance_round(events, studies=ready)
                events = []
                for sid, trs in suggestions.items():
                    tr = trs[0]
                    tr.status = "running"
                    tr.started = time.time()
                    fut = workers.submit(objectives[sid], tr.unit)
                    inflight[fut] = (sid, tr)
                    suggested += 1
            if not inflight:
                break
            done, _ = wait(inflight, return_when=FIRST_COMPLETED)
            for fut in done:            # completion order, any tenant mix
                sid, tr = inflight.pop(fut)
                try:
                    events.append((sid, tr, float(fut.result())))
                except Exception as e:  # noqa: BLE001 — tenant fault
                    retry = pool.record_failure(sid, tr,
                                                f"{type(e).__name__}: {e}")
                    if retry is not None:
                        fut2 = workers.submit(objectives[sid], retry.unit)
                        inflight[fut2] = (sid, retry)

    elapsed = time.perf_counter() - t0
    total = sum(pool.engine.n(s) for s in range(args.studies))
    print(f"\nserved {suggested} suggestions / absorbed {total} results "
          f"for {args.studies} tenants in {elapsed:.2f}s "
          f"({total / elapsed:.1f} results/s)")
    for h in pool.studies:
        best = pool.best(h.study_id)
        extra = ""
        if h.space.has_discrete and best is not None:
            hp = h.space.to_hparams(best.unit)
            extra = " " + " ".join(f"{k}={v}" for k, v in hp.items())
        print(f"  {h.name}: n={pool.engine.n(h.study_id)} "
              f"best={best.value:+.4f} "
              f"clamps={pool.engine.clamp_count(h.study_id)}{extra}")


if __name__ == "__main__":
    main()
