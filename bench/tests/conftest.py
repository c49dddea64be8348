"""The benchmark's own tests: run with `python -m pytest bench/tests`.

They put `bench/` and `src/` on the path, as `bench/run.py` does.  Runs of
the harness here skip its look for a chip and use tiny sizes on the CPU.
"""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# Tiny sizes for harness runs on the CPU (`--set` overrides).
TINY = ["config.gateway.slots=8", "config.scheduler.n_max=32",
        "config.scheduler.acq.restarts=8",
        "config.scheduler.acq.ascent_steps=4",
        "config.scheduler.inv_refresh=8",
        "traffic.warmup_s=1.0", "traffic.check.candidates=64"]
CELL = "svc-resident-sat"
TINY_CELL = {
    # one client, study after study, each closed study's state kept
    "sequential": ["traffic.tenants=1", "traffic.history_tenants=0",
                   "traffic.history=[0,0]", "traffic.workers=1",
                   "traffic.study_obs=25", "traffic.capture_closed=true"],
    # the same configuration under a many-tenant churned traffic mix: more
    # tenants than slots (eviction and restore), a Zipf hot set, think times
    "churn": ["traffic.tenants=16", "traffic.history_tenants=8",
              "traffic.history=[4,12]", "traffic.workers=6",
              "traffic.study_obs=24",
              'traffic.think={"dist": "lognormal", "median_s": 0.02, '
              '"sigma": 1.0}',
              'traffic.session={"dist": "geometric", "mean": 8}',
              'traffic.pick={"dist": "zipf", "s": 1.1, '
              '"max_workers_per_tenant": 4}',
              'traffic.hot_shift={"every_s": 0.5, "share": 0.1}',
              "traffic.capture_closed=false"],
    # every tenant resident and asking on every tick, its studies closing
    # at 28 observations (n_max 32 less one ask in flight, and some room)
    "resident": ["traffic.tenants=8", "traffic.history_tenants=8",
                 "traffic.history=[4,12]", "traffic.workers=8",
                 "traffic.study_obs=28", "traffic.capture_closed=false"],
}


def tiny_argv(case: str, seed: int, seconds: float = 2.0, extra=()):
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds",
            str(seconds)]
    for s in TINY + TINY_CELL[case]:
        argv += ["--set", s]
    return argv + list(extra)
