"""Mixed-space benchmark: discrete HPO through the serving stack.

The paper only exercises all-continuous spaces; this bench pins the
beyond-paper mixed workload (DESIGN.md §10) end to end:

  * **Optimization** — a mixed synthetic objective (Levy over 2 floats +
    1 int + a 3-way categorical branch, global optimum 0 at
    x1 = x2 = 1, k = 1, branch = "b") served through `StudyGateway`
    ask–tell traffic.  Acceptance: the study reaches the known optimum
    *cell* (k = 1, branch = "b") within the trial budget; the JSON
    records the first-hit trial index and the final best value.
  * **Gram parity** — the mixed kernel must match the ref substrate to
    ≤ 1e-5 on all three substrates, at 1 device (inline) AND at 8
    virtual devices (subprocess, the CI mesh environment), where the
    sharded mixed suggest round must also agree with mesh="none".
  * **Throughput** — the mixed suggest round vs an all-continuous round
    of the same encoded width (the projection + categorical factor
    overhead, S = 8 studies).

Emits `name,us_per_call,derived` CSV rows for `benchmarks.run` and writes
`BENCH_mixed.json` (rendered into README.md by `benchmarks.report`).
"""
from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import tempfile
import time

JSON_PATH = "BENCH_mixed.json"
BUDGET = 48             # gateway tells for the optimization section
PARITY_POINTS = 48      # gram sample size for the parity section
BRANCH_OFFSET = {"a": -4.0, "b": 0.0, "c": -2.0}
# The discrete optimum cell of the objective below (Levy optimum at the
# all-ones vector -> k = 1; branch "b" has the zero offset).  Rendered
# into the README by report.py, so it lives in the JSON, not in the table
# template.
OPTIMUM_CELL = {"k": 1, "branch": "b"}


def _mixed_space():
    from repro.hpo.space import Categorical, Dim, Int, SearchSpace
    return SearchSpace((
        Dim("x1", -10.0, 10.0),
        Dim("x2", -10.0, 10.0),
        Int("k", -3, 3),                       # third Levy coordinate
        Categorical("branch", ("a", "b", "c")),
    ))


def _objective(hp) -> float:
    import numpy as np

    from repro.core.levy import levy
    x = np.asarray([hp["x1"], hp["x2"], float(hp["k"])], np.float32)
    return float(-levy(x)) + BRANCH_OFFSET[hp["branch"]]


def _optimize_cell(seed: int = 0) -> dict:
    """Drive the mixed study through StudyGateway ask–tell traffic."""
    from repro.core.acquisition import AcqConfig
    from repro.hpo.gateway import GatewayConfig, StudyGateway
    from repro.hpo.pool import SchedulerConfig

    space = _mixed_space()
    with tempfile.TemporaryDirectory() as td:
        cfg = SchedulerConfig(
            n_max=BUDGET + 8, seed=seed, ckpt_dir=td,
            acq=AcqConfig(restarts=32, ascent_steps=16))
        gw = StudyGateway(space, cfg, GatewayConfig(slots=1))

        async def drive():
            sid = gw.create_study(name="mixed-levy")
            best, hit_at = -float("inf"), None
            t0 = time.perf_counter()
            for i in range(BUDGET):
                tr = await gw.ask(sid)
                hp = space.to_hparams(tr.unit)
                val = _objective(hp)
                gw.tell(sid, tr, val)
                in_cell = all(hp[k] == v for k, v in OPTIMUM_CELL.items())
                if in_cell and hit_at is None:
                    hit_at = i
                best = max(best, val)
            await gw.drain()
            return best, hit_at, time.perf_counter() - t0

        best, hit_at, elapsed = asyncio.run(drive())
    return {
        "budget": BUDGET,
        "best_value": best,
        "optimum_cell_hit": hit_at is not None,
        "first_cell_hit_trial": hit_at,
        "elapsed_s": elapsed,
        "tells_per_sec": BUDGET / elapsed,
    }


def _gram_parity() -> list[dict]:
    """Max |mixed_gram(impl) - mixed_gram(ref)| on a feasible sample —
    runs under whatever device count the calling process pinned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    space = _mixed_space()
    desc = space.descriptor()
    rng = np.random.default_rng(0)
    x = jnp.asarray(space.sample(rng, PARITY_POINTS))
    want = np.asarray(ops.mixed_gram(x, x, 1.0, 0.4, desc.cont_mask,
                                     desc.cat_mask, implementation="ref"))
    rows = []
    for impl in ("ref", "xla", "pallas"):
        got = np.asarray(ops.mixed_gram(x, x, 1.0, 0.4, desc.cont_mask,
                                        desc.cat_mask, implementation=impl))
        rows.append({
            "implementation": impl,
            "devices": len(jax.devices()),
            "max_abs_err": float(np.abs(got - want).max()),
            "pass_1e5": bool(np.abs(got - want).max() <= 1e-5),
        })
    return rows


def _sharded_round_parity() -> dict:
    """mesh='auto' vs mesh='none' mixed advance rounds (8-device cell).

    Gates, across device layouts: (a) every sharded suggestion is a
    FEASIBLE lattice point, (b) a given mesh spec is bitwise
    DETERMINISTIC run-to-run, (c) the sharded round's chosen suggestions
    score the same acquisition VALUE as the unsharded round's, and
    (d) cell IDENTITY — since the selection tie-break quantization in
    `optimize_acquisition`, restarts whose EI values differ only by
    cross-layout ulps collapse into one quantization bucket, so every
    layout picks the same winning restart and `identical_suggestion_frac`
    must be 1.0 (it was informational before that fix: exactly-tied
    local maxima at small n used to flip cells across layouts).
    """
    import jax
    import numpy as np

    from repro.core.acquisition import AcqConfig
    from repro.hpo.pool import SchedulerConfig, StudyPool

    space = _mixed_space()

    def drive(mesh: str) -> tuple[np.ndarray, np.ndarray]:
        cfg = SchedulerConfig(n_max=16, seed=0, mesh=mesh,
                              acq=AcqConfig(restarts=16, ascent_steps=8))
        pool = StudyPool([space] * 8, cfg)
        out = pool.advance_round([])
        pool.absorb_many([(s, out[s][0],
                           float(-np.sum(out[s][0].unit ** 2)))
                          for s in range(8)])
        units, vals = pool.engine.suggest_all(
            jax.vmap(jax.random.PRNGKey)(np.arange(8)), top_t=1)
        return np.asarray(units)[:, 0, :], np.asarray(vals)[:, 0]

    u_none, v_none = drive("none")
    u_auto, v_auto = drive("auto")
    u_auto2, v_auto2 = drive("auto")
    feasible = bool(np.allclose(space.project(u_auto), u_auto, atol=1e-6))
    deterministic = bool((u_auto == u_auto2).all()
                         and (v_auto == v_auto2).all())
    value_err = float(np.abs(v_none - v_auto).max())
    agree = float((np.abs(u_none - u_auto).max(axis=1) < 1e-5).mean())
    return {
        "feasible": feasible,
        "deterministic": deterministic,
        "acq_value_max_err": value_err,
        "acq_value_pass_1e4": value_err <= 1e-4,
        "identical_suggestion_frac": agree,
        # Hard gate (layout-stable top-t selection): every study's sharded
        # cell must match the unsharded one.
        "cell_identity_pass": bool(agree == 1.0),
    }


def _throughput() -> dict:
    """Mixed vs all-continuous suggest round at the same encoded width."""
    import jax
    import numpy as np

    from repro.core.acquisition import AcqConfig
    from repro.hpo.pool import SchedulerConfig, StudyPool
    from repro.hpo.space import Dim, SearchSpace

    mixed = _mixed_space()
    cont = SearchSpace(tuple(Dim(f"f{i}", 0.0, 1.0)
                             for i in range(mixed.dim)))

    def time_rounds(space) -> float:
        cfg = SchedulerConfig(n_max=64, seed=0,
                              acq=AcqConfig(restarts=16, ascent_steps=16))
        pool = StudyPool([space] * 8, cfg)
        out = pool.advance_round([])
        times = []
        for r in range(12):
            ev = [(s, out[s][0], float(-np.sum(out[s][0].unit ** 2)))
                  for s in range(8)]
            t0 = time.perf_counter()
            out = pool.advance_round(ev)
            jax.block_until_ready(pool.engine.state.l_buf)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]          # median; first rounds warm

    mixed_s = time_rounds(mixed)
    cont_s = time_rounds(cont)
    return {
        "n_studies": 8,
        "mixed_round_us": 1e6 * mixed_s,
        "continuous_round_us": 1e6 * cont_s,
        "mixed_overhead": mixed_s / cont_s,
    }


def _cell_8dev() -> dict:
    """The 8-virtual-device parity cell (runs inside the subprocess)."""
    return {"gram_parity": _gram_parity(),
            "sharded_round": _sharded_round_parity()}


def _run_8dev_subprocess() -> dict:
    """The 8-virtual-device cell in a CPU-only child process."""
    from benchmarks.bench_shard import virtual_cpu_env
    env = virtual_cpu_env()
    code = ("import json, benchmarks.bench_mixed as b;"
            "print('CELL::' + json.dumps(b._cell_8dev()))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    for line in out.stdout.splitlines():
        if line.startswith("CELL::"):
            return json.loads(line[len("CELL::"):])
    raise RuntimeError(
        f"8-device mixed cell produced no result (exit {out.returncode}): "
        f"{out.stderr[-500:]}")


def run(full: bool = False, json_path: str = JSON_PATH):
    del full  # budgets are already tier-1-sized
    opt = _optimize_cell()
    parity_1 = _gram_parity()
    cell8 = _run_8dev_subprocess()
    thr = _throughput()
    payload = {
        "space": "levy2f + int[-3,3] + cat3 (encoded width 7)",
        "budget": BUDGET,
        "optimum_cell": ", ".join(f"{k} = {v}"
                                  for k, v in OPTIMUM_CELL.items()),
        "optimize": opt,
        "gram_parity_1dev": parity_1,
        "gram_parity_8dev": cell8["gram_parity"],
        "sharded_round_8dev": cell8["sharded_round"],
        "throughput": thr,
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    worst = max(r["max_abs_err"]
                for r in parity_1 + cell8["gram_parity"])
    sh = cell8["sharded_round"]
    return [
        f"mixed_gateway_levy,,best={opt['best_value']:.3f} "
        f"cell_hit={opt['optimum_cell_hit']} "
        f"first_hit_trial={opt['first_cell_hit_trial']}",
        f"mixed_gram_parity,,max_err={worst:.2e} (floor 1e-5, 1+8 devices)",
        f"mixed_sharded_round,,feasible={sh['feasible']} "
        f"deterministic={sh['deterministic']} "
        f"acq_value_err={sh['acq_value_max_err']:.2e} "
        f"cell_identity={sh['cell_identity_pass']} "
        f"identical_frac={sh['identical_suggestion_frac']:.2f}",
        f"mixed_round,{thr['mixed_round_us']:.0f},"
        f"overhead_vs_continuous={thr['mixed_overhead']:.2f}x",
        f"mixed_json,,path={json_path}",
    ]


if __name__ == "__main__":
    print("\n".join(run(full="--full" in sys.argv)))
