"""Builds the system under test from a configuration file, and the
benchmark's own instrumentation around the calls into its layers."""
from __future__ import annotations

import functools

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums lowering and backend-compile seconds that JAX reports through
    `jax.monitoring`, and counts backend compiles."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs
        if event == BACKEND_COMPILE:
            self.compiles += 1


def build_gateway(cfg: dict, seed: int, store: str):
    """A `StudyGateway` with every SchedulerConfig/GatewayConfig value
    taken from the configuration file."""
    from repro.core.acquisition import AcqConfig
    from repro.hpo.gateway import GatewayConfig, StudyGateway
    from repro.hpo.pool import SchedulerConfig
    from repro.hpo.space import space_from_dicts
    sch = dict(cfg["scheduler"])
    acq = AcqConfig(**sch.pop("acq"))
    scfg = SchedulerConfig(**sch, acq=acq, seed=seed, ckpt_dir=store)
    return StudyGateway(space_from_dicts(cfg["space"]), scfg,
                        GatewayConfig(**cfg["gateway"]))


def instrument(gw, annotate, work: dict) -> None:
    """Wrap the gateway's tick halves, the engine's advance and re-anchor
    policy, and the eviction store in host spans; count the EI and re-anchor
    work the engine dispatches.  Only the traced run does this."""
    eng = gw.pool.engine
    r = int(eng.cfg.acq.restarts)
    steps = int(eng.cfg.acq.ascent_steps)
    d = int(eng.gp_cfg.dim)
    inv_refresh = int(getattr(eng.cfg, "inv_refresh", 0))
    ei_calls = work.setdefault("ei_calls", [])
    reanchor_n = work.setdefault("reanchor_n", [])
    window = work.setdefault("window", [False])

    def span(name, fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with annotate(name):
                return fn(*a, **k)
        return wrapped

    def record_ei(n_vec):
        if window[0]:
            for n in n_vec[n_vec > 0]:
                ei_calls.append((steps + 1, int(n), r, d))

    advance = eng.advance

    def advance_counted(flags, xs, ys, keys, top_t=1, costs=None):
        record_ei(eng._n_host + np.asarray(flags, bool))
        return advance(flags, xs, ys, keys, top_t=top_t, costs=costs)

    suggest_all = eng.suggest_all

    def suggest_all_counted(keys, top_t=1):
        record_ei(eng._n_host.copy())
        return suggest_all(keys, top_t=top_t)

    refit = eng._refit_flagged

    def refit_counted(flagged):
        if window[0] and eng.cfg.lag <= 0 and inv_refresh > 0:
            for s in flagged:
                if eng.since_refit(s) >= inv_refresh:
                    reanchor_n.append(eng.n(s))
        return refit(flagged)

    eng.advance = span("bench.engine.advance", advance_counted)
    eng.suggest_all = span("bench.engine.suggest_all", suggest_all_counted)
    eng._refit_flagged = span("bench.engine.refit_flagged", refit_counted)
    gw._tick_stage = span("bench.gateway.tick_stage", gw._tick_stage)
    gw._tick_finish = span("bench.gateway.tick_finish", gw._tick_finish)
    gw._evict = span("bench.store.evict", gw._evict)
    gw._ensure_resident = span("bench.store.ensure_resident",
                               gw._ensure_resident)


def plant_fault(gw, fault: str, seed: int) -> None:
    """Break the timed path underneath the harness (for the fault tests):
    `answer_altered` replaces every suggestion by a random point where the
    pool mints it; `absorb_dropped` leaves every other told observation out
    of the fused absorb."""
    pool, eng = gw.pool, gw.pool.engine
    if fault == "answer_altered":
        rng = np.random.default_rng([seed, 77])
        make = pool._make_trial

        def altered(study_id, unit):
            return make(study_id, rng.uniform(0.0, 1.0, np.shape(unit))
                        .astype(np.float32))
        pool._make_trial = altered
    elif fault == "absorb_dropped":
        advance = eng.advance
        count = [0]

        def dropped(flags, xs, ys, keys, top_t=1, costs=None):
            flags = np.asarray(flags, bool).copy()
            for s in np.flatnonzero(flags):
                count[0] += 1
                if count[0] % 2 == 0:
                    flags[s] = False
            return advance(flags, xs, ys, keys, top_t=top_t, costs=costs)
        eng.advance = dropped
    else:
        raise ValueError(f"unknown fault {fault!r}")
