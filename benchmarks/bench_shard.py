"""Device-mesh scaling benchmark: the paper's parallel-environment figure.

The paper's final claim is a further speedup from running the lazy-GP
optimizer "in a parallel environment".  This bench measures the repro's
version of that figure: **suggest-round throughput of the sharded engine
at 1/2/4/8 devices** for S in {8, 64} concurrent studies.

Method (see DESIGN.md §8):

  * The environment is FIXED at 8 virtual devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the CI
    recipe); the scaling variable is how many of them the mesh uses —
    exactly how a pod-scaling benchmark uses 1/2/4/8 chips of a slice.
    Each cell runs in its own subprocess because the device count must be
    pinned before jax initializes.
  * Every cell drives the same code path: `StudyEngine.advance` — the
    fused masked-absorb + batched-suggest serving round with donated
    state.  The 1-device cell resolves ``mesh="auto"`` to the unsharded
    program (mesh=none), so the baseline is the production single-device
    path, not a 1-device shard_map curiosity.
  * Rounds are timed individually (blocking); the per-cell statistic is
    the median round of the faster of two subprocess runs (hyperfine-style
    best-of-N, applied identically to every cell) — robust to the
    noisy-neighbor phases a shared host produces.

Emits `name,us_per_call,derived` CSV rows for `benchmarks.run` and writes
`BENCH_shard.json` with the full scaling table plus `speedup_8v1_S64`,
the headline ratio (acceptance: >= 2x on a machine with >= 2 cores; the
ratio on accelerator chips is not measured).

Numerical parity of mesh=none vs the sharded path is a test, not a bench
(`tests/test_shard.py`, all three substrates).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

JSON_PATH = "BENCH_shard.json"
ENV_DEVICES = 8
MESH_SIZES = (1, 2, 4, 8)
STUDY_SIZES = (64, 8)
CELL_REPEATS = 2        # subprocess runs per cell; keep the faster median
SETTLE_S = 3.0          # pause between cells (allocator/cache settle)

# The workload (chosen so a 64-study round is compute-meaningful but each
# device shard stays cache-resident on CPU hosts; see DESIGN.md §8):
N_MAX = 128
DIM = 3
RESTARTS = 16
ASCENT_STEPS = 16
N0 = 64           # observations prefilled per study before timing
TOP_T = 1         # suggestions per study per round


def _cell(n_studies: int, mesh_devices: int, rounds: int) -> dict:
    """One (S, device-count) measurement; runs inside the subprocess."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gp as gp_mod
    from repro.core.acquisition import AcqConfig
    from repro.hpo import mesh as mesh_mod
    from repro.hpo.engine import StudyEngine
    from repro.hpo.pool import SchedulerConfig

    devices = jax.devices()[:mesh_devices]
    hpo_mesh = mesh_mod.build("auto", n_studies, RESTARTS, devices=devices)
    spec = (f"{hpo_mesh.study_shards}x{hpo_mesh.restart_shards}"
            if hpo_mesh else "none")
    cfg = SchedulerConfig(n_max=N_MAX, seed=0, mesh=spec,
                          acq=AcqConfig(restarts=RESTARTS,
                                        ascent_steps=ASCENT_STEPS))
    engine = StudyEngine(DIM, cfg, n_studies)

    # Untimed prefill: N0 observations per study through the batched append.
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.uniform(size=(n_studies, N0, DIM)), jnp.float32)
    ys = jnp.asarray(rng.uniform(size=(n_studies, N0)), jnp.float32)
    engine.state = engine.place(
        gp_mod.append_batch(engine.state, engine.kernel, xs, ys,
                            implementation=cfg.implementation))
    jax.block_until_ready(engine.state.l_buf)

    # The timed quantity is the device-side suggest round: absorb last
    # round's values, suggest the next point for all S studies.  Suggested
    # units stay device-resident between rounds (the sharded output feeds
    # the next round's absorb directly); the per-round host traffic is the
    # trainer values + flags, pre-staged outside the timer.  Host-side
    # trial materialization is a constant measured by bench_pool.
    keys = jax.random.split(jax.random.PRNGKey(0), n_studies)
    sharding = hpo_mesh.study_sharding() if hpo_mesh else None
    if sharding is not None:
        keys = jax.device_put(keys, sharding)
    flags = np.ones((n_studies,), bool)
    units = jnp.asarray(rng.uniform(size=(n_studies, DIM)), jnp.float32)
    if sharding is not None:
        units = jax.device_put(units, sharding)
    all_vals = [jnp.asarray(rng.uniform(size=(n_studies,)), jnp.float32)
                for _ in range(rounds + 2)]
    if sharding is not None:
        all_vals = [jax.device_put(v, sharding) for v in all_vals]

    def one_round(units, vals):
        u, _ = engine.advance(flags, units, vals, keys, top_t=TOP_T)
        u = u[:, 0, :]
        jax.block_until_ready(u)
        return u

    for r in range(2):                       # compile + first-exec warmup
        units = one_round(units, all_vals[r])
    times = []
    for r in range(rounds):
        t0 = time.perf_counter()
        units = one_round(units, all_vals[2 + r])
        times.append(time.perf_counter() - t0)
    times.sort()
    med = times[len(times) // 2]
    return {
        "n_studies": n_studies,
        "mesh_devices": mesh_devices,
        "mesh": spec,
        "rounds": rounds,
        "round_us_median": 1e6 * med,
        "round_us_p25": 1e6 * times[len(times) // 4],
        "rounds_per_sec": 1.0 / med,
        "suggestions_per_sec": n_studies / med,
    }


def virtual_cpu_env() -> dict:
    """Environment for a subprocess cell on ENV_DEVICES virtual CPU devices.

    Such cells are CPU-only by design (the device count is a host-platform
    flag), so they refuse to start on a TPU host: a child reaching for the
    chips would contend with its parent and its siblings."""
    from repro.hpo.transport import tpu_chips_on_host
    if tpu_chips_on_host():
        raise RuntimeError(
            "this cell runs on virtual CPU devices only; refusing to spawn "
            "it on a host with TPU chips")
    env = dict(os.environ)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={ENV_DEVICES}"] + kept)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_cell_subprocess(n_studies: int, mesh_devices: int,
                         rounds: int) -> dict:
    """Pin the virtual device count before jax init: one CPU-only process
    per cell."""
    env = virtual_cpu_env()
    code = (
        "import json, benchmarks.bench_shard as b;"
        f"print('CELL::' + json.dumps(b._cell({n_studies}, {mesh_devices}, "
        f"{rounds})))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    for line in out.stdout.splitlines():
        if line.startswith("CELL::"):
            return json.loads(line[len("CELL::"):])
    raise RuntimeError(
        f"bench cell S={n_studies} d={mesh_devices} produced no result "
        f"(exit {out.returncode}): {out.stderr[-500:]}")


def run(full: bool = False, json_path: str = JSON_PATH):
    rounds = 40 if full else 30
    cells = []
    out = []
    for s in STUDY_SIZES:
        for nd in MESH_SIZES:
            runs = []
            for _ in range(CELL_REPEATS):
                time.sleep(SETTLE_S)
                runs.append(_run_cell_subprocess(s, nd, rounds))
            rec = min(runs, key=lambda r: r["round_us_median"])
            cells.append(rec)
            out.append(
                f"shard_S{s}_d{nd},{rec['round_us_median']:.0f},"
                f"mesh={rec['mesh']} "
                f"suggest_per_s={rec['suggestions_per_sec']:.1f}")
    by = {(c["n_studies"], c["mesh_devices"]): c for c in cells}
    speedup = (by[(64, 1)]["round_us_median"] /
               by[(64, 8)]["round_us_median"])
    payload = {
        "env_devices": ENV_DEVICES,
        "n_max": N_MAX,
        "dim": DIM,
        "restarts": RESTARTS,
        "ascent_steps": ASCENT_STEPS,
        "top_t": TOP_T,
        "n0": N0,
        "rounds": rounds,
        "results": cells,
        "speedup_8v1_S64": speedup,
        "speedup_8v1_S8": (by[(8, 1)]["round_us_median"] /
                           by[(8, 8)]["round_us_median"]),
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    out.append(f"shard_speedup_S64,,8dev_vs_1dev={speedup:.2f}x")
    out.append(f"shard_json,,path={json_path}")
    return out


if __name__ == "__main__":
    print("\n".join(run(full="--full" in sys.argv)))
