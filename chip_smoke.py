"""Bring-up smoke run of the ask–tell gateway on TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # study-axis mesh over four chips

One process drives the served path through its user entry points:
`StudyGateway.ask`/`tell` over `StudyPool` and `StudyEngine.advance`, with
`implementation="auto"` (the Pallas kernels on TPU), R=64 restarts and 25
ascent steps.  96 logical studies share 64 resident slots at n_max=1024:
64 on the three-float `RESNET_SPACE` (48 slots) and 32 on the mixed
`MIXED_DEMO_SPACE` (16 slots), so studies are evicted and restored.

Phases, in order:
  fill       every study is told 256 seeded observations of a synthetic
             objective; crossing inv_refresh=128 runs the re-anchor
             kernels (Cholesky + triangular inverse).
  serve      every tenant runs ask/tell rounds; the first round compiles.
  ask_q      one tenant asks for q=8 suggestions at once (qEI fantasies).
  posterior  for 4 studies the served posterior matches the dense exact
             GP (`gp.dense_posterior`) at highest matmul precision.
Checks: every ask is served, no fantasy row is left, the posterior is within
tolerance, and the compiled `advance` program holds Pallas kernels
(`tpu_custom_call`).  Any failed check or raised phase exits non-zero.

`--four-chips` runs the same traffic with the same seed under
`mesh="4x1"` over four chips and under `mesh="none"` on chip 0, and
nothing else.  It checks that each chip holds a quarter of the stacked
state and that both layouts serve the same suggestions; a suggestion that
differs must be a tie between basins of equal expected improvement (the
mesh parity probe and its tie caveat, DESIGN.md §10).

The last line of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Without a TPU, or without the repository's `src/` beside this file, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import sys
import tempfile
import time
import warnings

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
N_MAX = 1024
FILL_N = 256              # observations told per study before serving
ROUNDS = 3                # ask/tell rounds of every tenant
Q = 8                     # width of the single q-ask
POSTERIOR_STUDIES = 4     # studies whose posterior is checked
QUERIES = 64              # query points per checked study
IMPLEMENTATION = "auto"
# (name, template space, logical studies, resident slots): 96 studies on
# 64 slots, a third of them mixed.
GATEWAYS = (("float", "RESNET_SPACE", 64, 48),
            ("mixed", "MIXED_DEMO_SPACE", 32, 16))
# Posterior tolerance, relative to the posterior's own scale (the spread of
# the observed values for the mean, the prior variance for the variance).
# The served state is float32 and carries up to inv_refresh - 1 = 127
# bordered-inverse appends since its last re-anchor, through a Gram whose
# condition number reaches ~sigma2/noise2 = 1e5; that leaves round-off in
# the 1e-3 range.  A wrong factor, inverse or alpha errs by O(1).
MEAN_TOL = 1e-2
VAR_TOL = 1e-2
# Suggestions of the two mesh layouts that differ by more than this (unit
# cube) must be a tie in expected improvement within EI_TIE_RTOL.
UNIT_ATOL = 1e-4
EI_TIE_RTOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < count:
        fail(f"needs {count} TPU chips, JAX found {len(devs)}")
    return devs


class CompileClock:
    """Sums the lowering and XLA backend-compile durations JAX reports
    through `jax.monitoring` (tracing is left out: nested traces would be
    counted twice)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += secs


class Phases:
    """Wall and compile seconds of each named phase."""

    def __init__(self, clock: CompileClock):
        self.clock = clock
        self.rows: list[tuple[str, float, float]] = []

    def run(self, name: str, fn, *args):
        c0, t0 = self.clock.seconds, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        comp = self.clock.seconds - c0
        self.rows.append((name, wall, comp))
        print(f"phase {name}: wall {wall:.3f} s = compile {comp:.3f} s "
              f"+ run {wall - comp:.3f} s", flush=True)
        return out


def objective(sid: int, unit) -> float:
    """Seeded smooth bowl on the unit cube, optimum placed per study."""
    c = 0.15 + 0.7 * ((sid * 0.37) % 1.0)
    return float(-np.sum((np.asarray(unit, np.float64) - c) ** 2))


class Tenants:
    """One gateway, its studies, and every observation each was told."""

    def __init__(self, gid, name, space, n_studies, slots, mesh, store):
        from repro.core.acquisition import AcqConfig
        from repro.hpo.gateway import GatewayConfig, StudyGateway
        from repro.hpo.pool import SchedulerConfig
        self.gid, self.name, self.space = gid, name, space
        cfg = SchedulerConfig(n_max=N_MAX, seed=SEED, ckpt_dir=store,
                              implementation=IMPLEMENTATION, mesh=mesh,
                              acq=AcqConfig())
        self.gw = StudyGateway(space, cfg,
                               GatewayConfig(slots=slots, max_inflight=Q))
        self.sids = [self.gw.create_study(name=f"{name}{i}")
                     for i in range(n_studies)]
        self.history = {s: [] for s in self.sids}   # [(unit, value)]
        self.served = {s: [] for s in self.sids}    # units handed out

    def fill(self) -> None:
        """Tell FILL_N seeded observations to every study, one slot-sized
        batch per tick (a tick's tells must all be resident)."""
        from repro.hpo.pool import Trial
        slots = self.gw.gw.slots
        for b in range(0, len(self.sids), slots):
            for sid in self.sids[b:b + slots]:
                rng = np.random.default_rng([SEED, self.gid, sid])
                for i, u in enumerate(self.space.sample(rng, FILL_N)):
                    y = objective(sid, u)
                    self.gw.tell(sid, Trial(10 ** 6 + i, u, {}), y)
                    self.history[sid].append((u, y))
            self.gw.tick()

    async def client(self, sid: int) -> None:
        tr = await self.gw.ask(sid)
        y = objective(sid, tr.unit)
        self.served[sid].append(np.asarray(tr.unit).copy())
        self.history[sid].append((np.asarray(tr.unit).copy(), y))
        self.gw.tell(sid, tr, y)

    async def ask_q(self, sid: int) -> None:
        trials = await self.gw.ask(sid, q=Q)
        for tr in trials:
            y = objective(sid, tr.unit)
            self.history[sid].append((np.asarray(tr.unit).copy(), y))
            self.gw.tell(sid, tr, y)
        await self.gw.drain()


async def serve_round(tenants: list[Tenants]) -> None:
    await asyncio.gather(*(t.client(s) for t in tenants for s in t.sids))
    for t in tenants:
        await t.gw.drain()


def run_traffic(tenants: list[Tenants], phases: Phases, tag: str = ""):
    """fill -> ROUNDS ask/tell rounds -> one q-ask; returns asks issued."""
    loop = asyncio.new_event_loop()
    for t in tenants:
        phases.run(f"{tag}fill[{t.name}]", t.fill)
    for r in range(ROUNDS):
        phases.run(f"{tag}serve round {r + 1}", loop.run_until_complete,
                   serve_round(tenants))
    phases.run(f"{tag}ask_q (q={Q})", loop.run_until_complete,
               tenants[0].ask_q(tenants[0].sids[0]))
    for t in tenants:
        loop.run_until_complete(t.gw.aclose())
    loop.close()
    return sum(len(t.sids) for t in tenants) * ROUNDS + Q


def kernel_for(t: Tenants):
    from repro.core.kernels import KERNELS, make_mixed_kernel
    if t.space.has_discrete:
        desc = t.space.descriptor()
        return make_mixed_kernel(desc.cont_mask, desc.cat_mask)
    return KERNELS["matern52"]


def reference_posterior(t: Tenants, history, xq):
    """Dense exact GP over the told `history` [(unit, value)], at highest
    matmul precision (the plain reference of the served lazy posterior)."""
    import jax
    import jax.numpy as jnp
    from repro.core import gp
    from repro.core.kernels import KernelParams
    x = jnp.asarray(np.stack([u for u, _ in history]), jnp.float32)
    y = jnp.asarray([v for _, v in history], jnp.float32)
    cfg = t.gw.cfg
    params = KernelParams(jnp.float32(1.0), jnp.float32(cfg.rho0),
                          jnp.float32(cfg.noise2))
    with jax.default_matmul_precision("highest"):
        mean, var = gp.dense_posterior(x, y, xq, kernel_for(t), params,
                                       implementation="xla")
    return np.asarray(mean), np.asarray(var), np.asarray(y)


def check_posterior(t: Tenants, sid: int, failures: list) -> None:
    """Served lazy posterior (default precision, the serving state as the
    chip holds it) against the dense reference."""
    import jax.numpy as jnp
    from repro.core import gp
    info = t.gw.study_info(sid)
    eng = t.gw.pool.engine
    st = eng.study_state(info["slot"])
    n = int(st.n)
    if n != len(t.history[sid]):
        failures.append(f"{t.name} study {sid}: n={n}, told "
                        f"{len(t.history[sid])}")
        return
    rng = np.random.default_rng([SEED, t.gid, sid, 1])
    xq = jnp.asarray(t.space.sample(rng, QUERIES))
    mean, var = gp.posterior(st, kernel_for(t), xq,
                             implementation=IMPLEMENTATION)
    rmean, rvar, y = reference_posterior(t, t.history[sid], xq)
    scale = float(np.std(y)) or 1.0
    emean = float(np.max(np.abs(np.asarray(mean) - rmean))) / scale
    evar = float(np.max(np.abs(np.asarray(var) - rvar))) / float(
        st.params.sigma2)
    ok = emean <= MEAN_TOL and evar <= VAR_TOL
    finite = (bool(np.isfinite(np.asarray(mean)).all()
                   and np.isfinite(np.asarray(var)).all()),
              bool(np.isfinite(rmean).all() and np.isfinite(rvar).all()))
    print(f"posterior {t.name} study {sid} finite (served, reference) "
          f"{finite}, clamp_count {int(st.clamp_count)}", flush=True)
    print(f"posterior {t.name} study {sid} (n={n}, since re-anchor "
          f"{eng.since_refit(info['slot'])}): max|mean err|/std(y) "
          f"{emean:.3e} (tol {MEAN_TOL}), max|var err|/sigma2 {evar:.3e} "
          f"(tol {VAR_TOL}) {'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append(f"posterior of {t.name} study {sid} out of "
                        "tolerance")


def advance_custom_calls(t: Tenants) -> int:
    """`tpu_custom_call`s (Pallas kernels) in the compiled fused round."""
    import jax.numpy as jnp
    eng = t.gw.pool.engine
    s, d = eng.n_studies, eng.gp_cfg.dim
    args = (eng.state, *eng._desc_args(), jnp.zeros((s, d), jnp.float32),
            jnp.zeros((s,), jnp.float32), jnp.zeros((s,), bool),
            jnp.zeros((s, 2), jnp.uint32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # donation of an unused buffer
        hlo = eng._advance_all.lower(*args, top_t=1).compile().as_text()
    return hlo.count("tpu_custom_call")


def check_served(tenants: list[Tenants], asks: int, failures: list) -> None:
    served = sum(t.gw.summary()["asks_served"] for t in tenants)
    fantasy = sum(t.gw.summary()["fantasy_active"] for t in tenants)
    print(f"asks issued {asks}, served {served}; fantasy_active {fantasy}",
          flush=True)
    if served != asks:
        failures.append(f"{asks} asks issued but {served} served")
    if fantasy:
        failures.append(f"fantasy_active={fantasy} after every tell")
    for t in tenants:
        s = t.gw.summary()
        eng = t.gw.pool.engine
        resident = [i for i in range(eng.n_studies) if eng.n(i) > 0]
        anchored = sum(1 for i in resident
                       if eng.since_refit(i) < eng.n(i))
        print(f"gateway {t.name}: ticks {s['ticks']}, evictions "
              f"{s['evictions']}, restores {s['restores']}, resident "
              f"studies re-anchored {anchored}/{len(resident)}, "
              f"clamp_count {int(eng.clamp_counts().sum())}", flush=True)
        if not s["evictions"] or not s["restores"]:
            failures.append(f"gateway {t.name} evicted or restored nothing")
        if anchored != len(resident):
            failures.append(f"gateway {t.name}: a study crossed "
                            "inv_refresh without a re-anchor")
        for sid in t.sids:
            n = t.gw.study_info(sid)["n_obs"]
            if n != len(t.history[sid]):
                failures.append(f"{t.name} study {sid}: absorbed {n}, "
                                f"told {len(t.history[sid])}")


def print_tiles() -> None:
    from repro.kernels import ops
    for key, cfg in sorted(ops.tuned_acq_tiles().items(), key=str):
        n_pad, d, s, sub = key
        print(f"fused EI tile (n_pad={n_pad}, d={d}, S={s}, {sub}): "
              f"block_r={cfg.block_r} d_pad={cfg.d_pad} "
              f"measured={cfg.measured}", flush=True)


def one_chip(devs, phases: Phases, store: str) -> list[str]:
    from repro.hpo import space as space_mod
    failures: list[str] = []
    tenants = [Tenants(gid, name, getattr(space_mod, sp), n, slots, "none",
                       f"{store}/{name}")
               for gid, (name, sp, n, slots) in enumerate(GATEWAYS)]
    asks = run_traffic(tenants, phases)
    print_tiles()
    check_served(tenants, asks, failures)
    for t in tenants:
        n_calls = phases.run(f"compile advance[{t.name}] for its HLO",
                             advance_custom_calls, t)
        print(f"advance[{t.name}]: {n_calls} tpu_custom_call(s)", flush=True)
        if n_calls == 0:
            failures.append(f"advance[{t.name}] holds no Pallas kernel")
    checked = [(t, sid) for t in tenants
               for sid in [s for s in t.sids
                           if t.gw.study_info(s)["resident"]][:2]]
    for t, sid in checked[:POSTERIOR_STUDIES]:
        phases.run(f"posterior[{t.name} {sid}]", check_posterior, t, sid,
                   failures)
    return failures


def ei_reference(t: Tenants, sid: int, upto: int, units) -> np.ndarray:
    """Exact EI of candidate units given the first `upto` observations."""
    import jax.numpy as jnp
    from repro.core.acquisition import AcqConfig, expected_improvement
    mean, var, y = reference_posterior(t, t.history[sid][:upto],
                                       jnp.asarray(units))
    return np.asarray(expected_improvement(mean, var, float(y.max()),
                                           AcqConfig().xi))


def four_chips(devs, phases: Phases, store: str) -> list[str]:
    """Same traffic under mesh="4x1" (four chips) and mesh="none" (chip 0):
    quarter-state placement, then suggestion parity with the tie caveat."""
    from repro.hpo import space as space_mod
    failures: list[str] = []
    runs = {}
    for mesh in ("4x1", "none"):
        tenants = [Tenants(gid, name, getattr(space_mod, sp), n, slots,
                           mesh, f"{store}/{mesh}/{name}")
                   for gid, (name, sp, n, slots) in enumerate(GATEWAYS)]
        if mesh != "none":
            for t in tenants:
                leaf = t.gw.pool.engine.state.li_buf
                shards = leaf.addressable_shards
                rows = sorted((sh.device.id, sh.data.shape[0])
                              for sh in shards)
                want = leaf.shape[0] // 4
                print(f"mesh {mesh} [{t.name}]: li_buf {leaf.shape} -> "
                      f"{rows} (device id, studies)", flush=True)
                if len({d for d, _ in rows}) != 4 or \
                        any(r != want for _, r in rows):
                    failures.append(f"{t.name}: state not split in "
                                    "quarters over four chips")
        asks = run_traffic(tenants, phases, tag=f"[{mesh}] ")
        check_served(tenants, asks, failures)
        runs[mesh] = tenants
    exact = ties = 0
    for a, b in zip(runs["4x1"], runs["none"]):
        for sid in a.sids:
            for k, (ua, ub) in enumerate(zip(a.served[sid], b.served[sid])):
                if np.allclose(ua, ub, atol=UNIT_ATOL):
                    exact += 1
                    continue
                # first divergence: both layouts saw the same observations
                upto = FILL_N + k
                ei = ei_reference(b, sid, upto, np.stack([ua, ub]))
                if abs(ei[0] - ei[1]) <= EI_TIE_RTOL * max(abs(ei).max(),
                                                          1e-12):
                    ties += 1
                    print(f"tie: {a.name} study {sid} round {k + 1}: "
                          f"EI {ei[0]:.6e} vs {ei[1]:.6e}", flush=True)
                else:
                    failures.append(
                        f"mesh parity: {a.name} study {sid} round {k + 1} "
                        f"suggests {ua} vs {ub}, EI {ei[0]:.6e} vs "
                        f"{ei[1]:.6e}")
                break
    print(f"mesh parity: {exact} suggestions equal, {ties} tie(s)",
          flush=True)
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="mesh='4x1' over four chips vs mesh='none'")
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devs = require_tpu(count)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repository's src/repro is not beside {__file__}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    from repro.kernels.ops import KernelFallbackWarning
    # A Pallas kernel that quietly hands its work to XLA fails the run.
    warnings.simplefilter("error", KernelFallbackWarning)
    d0 = devs[0]
    print(f"device: {d0.platform} {d0.device_kind}, {len(devs)} visible, "
          f"using {count}", flush=True)
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    phases = Phases(CompileClock())
    with tempfile.TemporaryDirectory() as store:
        run = four_chips if args.four_chips else one_chip
        failures = run(devs, phases, store)
    total = sum(w for _, w, _ in phases.rows)
    comp = sum(c for _, _, c in phases.rows)
    print(f"total: wall {total:.3f} s, compile {comp:.3f} s", flush=True)
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
