"""Parallel HPO of a real trainer with fault injection (paper Sec. 3.4/4.4).

    python examples/parallel_hpo.py [--budget 16] [--parallel 4] [--faults]

t worker lanes train the tiny LM with different (lr, wd, momentum); the lazy
GP suggests the top-t EI local maxima and absorbs results in completion
order (stragglers never block).  With --faults, every 5th trial crashes to
demonstrate the retry + penalized-region path, and the GP checkpoint in
--ckpt-dir lets a second invocation resume the exact posterior.
"""
import argparse
import pathlib
import sys
import threading

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from benchmarks.bench_nn_hpo import make_objective  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.hpo.scheduler import SchedulerConfig, TrialScheduler  # noqa: E402
from repro.hpo.space import RESNET_SPACE  # noqa: E402


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--parallel", type=int, default=4)
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--implementation", default="auto",
                    choices=["auto", "pallas", "xla", "ref"],
                    help="linalg substrate for the GP math")
    args = ap.parse_args()

    base = make_objective(steps=args.train_steps)
    counter = {"n": 0}
    lock = threading.Lock()

    def objective(hp: dict) -> float:
        with lock:
            counter["n"] += 1
            n = counter["n"]
        if args.faults and n % 5 == 0:
            raise RuntimeError(f"injected fault in trial call #{n}")
        return float(base(RESNET_SPACE.to_unit(hp))[0])

    sched = TrialScheduler(
        RESNET_SPACE,
        SchedulerConfig(n_max=max(64, args.budget + 16),
                        parallel=args.parallel, seed=0,
                        implementation=args.implementation,
                        max_retries=2, ckpt_dir=args.ckpt_dir))
    if args.ckpt_dir and sched.restore():
        print(f"resumed GP with n={int(sched.state.n)} observations")

    best = sched.run(objective, budget=args.budget, n_seed=4)
    n_fail = sum(t.status == "failed" for t in sched.trials)
    print(f"\nabsorbed {int(sched.state.n)} observations "
          f"({n_fail} injected failures recovered)")
    print(f"best accuracy {best.value:.3f} with:")
    for k, v in best.hparams.items():
        print(f"  {k:14s} = {v:.5g}")


if __name__ == "__main__":
    main()
