"""Cross-process HPO serving: shard workers behind the socket front end.

    python examples/serve_cluster.py [--studies 8] [--shards 2] \
        [--budget 6] [--latency 0.01] [--kill] [--ckpt-dir /tmp/fed]

The ROADMAP's cross-host deployment shape (DESIGN.md §14): a
`TransportFederation` front end spawns one `repro.hpo.shard_worker`
process per shard (one per host in a real cluster, `TransportConfig.connect`
adopts operator-started workers), and every `ask`/`tell` crosses a socket
as length-prefixed JSON frames.  Routing, migration, and epoch recovery
are the same contracts as the in-memory `FederatedGateway` — the shards
just live in other processes, so their fused tick programs stop sharing
one interpreter.

With `--kill` the demo SIGKILLs shard 0 mid-serve: parked asks on that
shard fail with `ShardConnectionError`, the health sweep marks it dead,
and `revive_shard` respawns a fresh worker that restores from its own
latest committed epoch — clients resume and only the uncommitted round is
lost (re-derived bitwise from the persisted per-study PRNG streams).

With `--ckpt-dir` pointing at a persistent directory a second invocation
restores the whole federation (registry epoch first, then every shard
from its own store) and each tenant resumes exactly where it stopped.
"""
import argparse
import asyncio
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro import compile_cache  # noqa: E402
from repro.core.acquisition import AcqConfig  # noqa: E402
from repro.hpo.federation import FederationConfig  # noqa: E402
from repro.hpo.gateway import GatewayConfig  # noqa: E402
from repro.hpo.pool import SchedulerConfig  # noqa: E402
from repro.hpo.space import RESNET_SPACE  # noqa: E402
from repro.hpo.transport import (ShardConnectionError,  # noqa: E402
                                 TransportConfig, TransportFederation)


def make_objective(sid: int, latency: float):
    center = 0.15 + 0.7 * ((sid * 0.37) % 1.0)

    async def objective(unit: np.ndarray) -> float:
        await asyncio.sleep(latency * (1.0 + 0.5 * ((sid + 1) % 3)))
        return float(-np.sum((np.asarray(unit) - center) ** 2))

    return objective


async def client(tf: TransportFederation, sid: int, budget: int,
                 latency: float) -> int:
    """One tenant's serving loop; survives its shard dying mid-ask by
    waiting for the supervisor to revive it."""
    objective = make_objective(sid, latency)
    done = retried = 0
    while done < budget:
        try:
            trial = await tf.ask(sid)
            await tf.tell(sid, trial, await objective(trial.unit))
        except (ShardConnectionError, asyncio.CancelledError,
                RuntimeError):
            # shard died under us (parked asks cancel with kill_shard
            # semantics; calls routed to a down shard fail loudly) —
            # back off and retry once the supervisor revives it
            retried += 1
            if retried > 50:
                raise
            await asyncio.sleep(0.2)
            continue
        done += 1
    return retried


async def supervisor(tf: TransportFederation, kill_after: float) -> None:
    """Checkpoint, SIGKILL shard 0, observe the health sweep declare it
    dead, respawn it from its committed epoch."""
    await asyncio.sleep(kill_after)
    epoch = await tf.checkpoint()
    tf.kill_shard(0)
    print(f"  [supervisor] shard 0 SIGKILLed after epoch {epoch}")
    assert await tf.check_health() == []   # already marked dead by kill
    await tf.revive_shard(0)
    print("  [supervisor] shard 0 respawned + reconciled")


async def serve(args, root: str) -> None:
    cfg = SchedulerConfig(n_max=args.budget + 8, seed=0,
                          ckpt_dir=root, ckpt_every=10 ** 9,
                          acq=AcqConfig(restarts=16, ascent_steps=8))
    tf = TransportFederation(
        RESNET_SPACE, cfg,
        GatewayConfig(slots=max(2, args.studies // args.shards)),
        FederationConfig(n_shards=args.shards),
        TransportConfig(heartbeat_s=0.0))
    restored = await tf.start()
    if restored:
        sids = tf.study_ids()
        print(f"resumed federation: {len(sids)} tenants across "
              f"{args.shards} worker processes")
    else:
        sids = [await tf.create_study(name=f"tenant{i}")
                for i in range(args.studies)]

    tasks = [client(tf, s, args.budget, args.latency) for s in sids]
    if args.kill:
        tasks.append(supervisor(tf, kill_after=args.kill_after))
    t0 = time.perf_counter()
    results = await asyncio.gather(*tasks)
    await tf.drain()
    elapsed = time.perf_counter() - t0

    summary = await tf.summary()
    retries = sum(r for r in results if isinstance(r, int))
    served = args.budget * len(sids)
    await tf.checkpoint()
    print(f"\nserved {served} suggestions for {len(sids)} tenants on "
          f"{args.shards} worker processes in {elapsed:.2f}s "
          f"({served / max(elapsed, 1e-9):.1f} suggestions/s, "
          f"{retries} failover retries)")
    worst_p95 = max((s["p95_tick_ms"]
                     for s in summary["per_shard"].values()), default=0.0)
    print(f"ticks={summary['ticks']} "
          f"evictions={summary['evictions']} "
          f"worst_shard_p95_tick={worst_p95:.1f}ms")
    for s in sids:
        info = await tf.study_info(s)
        line = (f"  {info['name']}: shard {info['shard']} "
                f"n={info['n_obs']}")
        if info["best_value"] is not None:
            line += f" best={info['best_value']:+.4f}"
        print(line)
    await tf.aclose()


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--studies", type=int, default=8,
                    help="concurrent logical studies (clients)")
    ap.add_argument("--shards", type=int, default=2,
                    help="worker processes (one per host in production)")
    ap.add_argument("--budget", type=int, default=6,
                    help="observations per study")
    ap.add_argument("--latency", type=float, default=0.01,
                    help="simulated per-trial train time (s)")
    ap.add_argument("--kill", action="store_true",
                    help="SIGKILL + revive shard 0 mid-serve")
    ap.add_argument("--kill-after", type=float, default=1.0,
                    help="seconds before the supervisor kills shard 0")
    ap.add_argument("--ckpt-dir", default=None,
                    help="persistent shared store root: a 2nd run "
                         "resumes every tenant")
    args = ap.parse_args()

    if args.ckpt_dir:
        asyncio.run(serve(args, args.ckpt_dir))
    else:
        with tempfile.TemporaryDirectory() as d:
            asyncio.run(serve(args, d))


if __name__ == "__main__":
    main()
