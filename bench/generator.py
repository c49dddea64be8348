"""The one traffic generator: closed-loop HPO workers driven by a data file.

A traffic file (`bench/traffic/<name>.json`) sets every parameter:

  tenants          logical tenants; each holds one open study at a time
  history_tenants  how many tenants start with a told history (<= slots)
  history          [lo, hi]: that history's length; the lengths are spread
                   evenly over lo..hi across the history tenants, in an
                   order drawn from the seed, so every seed tells the same
                   number of observations of each length
  workers          closed-loop clients (each waits for its suggestion,
                   evaluates it for its think time, then tells it)
  think            {"dist": "none"} or {"dist": "lognormal", "median_s",
                   "sigma"}
  session          trials a worker runs on one tenant before it picks
                   again: {"dist": "forever"} or {"dist": "geometric",
                   "mean"}
  pick             {"dist": "own"} (worker i serves tenant i) or
                   {"dist": "zipf", "s", "max_workers_per_tenant"}: a Zipf
                   rank among tenants with fewer workers than the cap
  hot_shift        null or {"every_s", "share"}: that share of the hot
                   ranks is permuted at that period
  study_obs        observations a study holds, its starting history
                   included: once history + asks issued reach it, the study
                   closes (after every tell is absorbed) and its tenant
                   opens a new one.  `check_params` holds it to
                   history[1] < study_obs <= n_max - w (w: the most workers
                   one tenant can have), so no study can escalate
  warmup_s         traffic served before the window opens
  warmup_ticks     gateway ticks that also have to finish before it opens
                   (a cold compile cache makes the first ticks long)
  capture_closed   keep each closed study's served state for the check

Every draw comes from generators seeded by (seed, stream, index), so one
seed gives the same tenants, histories, sessions, think times and hot-set
moves.  The order in which the workers' asks meet the gateway depends on
the times the system takes, as in any closed loop.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from reference.objective import neg_levy_unit

# Seed streams, one per kind of draw.
_TENANT, _WORKER, _SHIFT, _FILL = 1, 2, 3, 4


def check_params(params: dict, n_max: int) -> None:
    """Refuse a traffic whose studies could escalate past `n_max`, at any
    speed of the served path.  Every ask issued to a study counts toward
    `study_obs`, so the rows the gateway counts against `n_max` (told
    observations, asks in flight, the ask being served) stay within
    `study_obs` plus one ask for each of the tenant's w workers.  Each
    history tenant has to get at least one ask."""
    pick = params["pick"]
    w = 1 if pick["dist"] == "own" else int(pick["max_workers_per_tenant"])
    top, obs = int(params["history"][1]), int(params["study_obs"])
    if not top < obs <= n_max - w:
        raise ValueError(
            f"study_obs {obs} has to exceed the longest history ({top}) and "
            f"be at most n_max {n_max} less {w} worker(s) per tenant "
            f"({n_max - w}): otherwise a study can escalate past n_max")


@dataclasses.dataclass
class Tenant:
    idx: int
    shift: np.ndarray
    sid: int = -1
    held: int = 0              # history + asks issued to the open study
    workers: int = 0
    rotation: asyncio.Future | None = None


@dataclasses.dataclass
class Ask:
    t_issue: float
    t_reply: float
    ok: bool
    sid: int
    n_cond: int                # observations the suggestion conditioned on
    unit: np.ndarray | None


class Traffic:
    """Drives one `StudyGateway` with the traffic a data file describes."""

    def __init__(self, gw, params: dict, objective: dict, seed: int,
                 capture=None):
        self.gw = gw
        self.p = params
        self.obj = objective
        self.seed = int(seed)
        self.capture = capture     # fn(gw, sid) -> host copy of its state
        self.dim = gw.pool.engine.gp_cfg.dim
        n = int(params["tenants"])
        rng = np.random.default_rng([self.seed, _TENANT])
        shifts = rng.uniform(-1.0, 1.0, (n, self.dim)) * objective[
            "tenant_shift"]
        self.tenants = [Tenant(i, shifts[i]) for i in range(n)]
        self.hist: dict[int, list] = {}    # sid -> [(unit, y)] in tell order
        self.asks: list[Ask] = []
        self.think_late: list[float] = []
        self.tells = 0
        self.tell_failures = 0
        self.captured: dict[int, dict] = {}
        self.closes: list[tuple[float, int, int]] = []  # (time, tick, tenant)
        self.stopping = False
        pick = params["pick"]
        if pick["dist"] == "zipf":
            ranks = np.arange(1, n + 1, dtype=np.float64)
            w = ranks ** -float(pick["s"])
            self._cdf = np.cumsum(w / w.sum())
            self._rank_tenant = np.arange(n)
        for t in self.tenants:
            self._open(t)

    # -- studies ------------------------------------------------------------
    def _open(self, t: Tenant) -> None:
        t.sid = self.gw.create_study(name=f"t{t.idx}")
        t.held = 0
        self.hist[t.sid] = []

    def objective(self, t: Tenant, unit) -> float:
        return neg_levy_unit(unit, t.shift, self.obj["lo"], self.obj["hi"],
                             self.obj["scale"])

    def fill(self) -> int:
        """Tell every history tenant its starting history in one tick."""
        from repro.hpo.pool import Trial
        lo, hi = self.p["history"]
        rng = np.random.default_rng([self.seed, _FILL])
        n = int(self.p["history_tenants"])
        mid = 2 * np.arange(n) + 1        # the midpoints of n equal strata
        lengths = rng.permutation(lo + mid * (hi - lo + 1) // (2 * n))
        told = 0
        for t, h in zip(self.tenants[:n], lengths.tolist()):
            for i, u in enumerate(rng.uniform(0.0, 1.0, (h, self.dim))
                                  .astype(np.float32)):
                y = self.objective(t, u)
                self.gw.tell(t.sid, Trial(10 ** 6 + i, u, {}), y)
                self.hist[t.sid].append((u, y))
            t.held = h
            told += h
        if told:
            self.gw.tick()
        return told

    def warm_store(self) -> None:
        """Run one eviction and one restore before the window, so their
        programs are compiled: tenant 0's study is evicted, asked once
        (which restores it) and told the suggestion."""
        if len(self.tenants) <= int(self.gw.gw.slots):
            return
        t = self.tenants[0]
        if not self.gw.study_info(t.sid)["resident"]:
            return
        self.gw.export_for_migration(t.sid)
        self.gw.ask_nowait(t.sid)
        self.gw.tick()
        slot = self.gw.study_info(t.sid)["slot"]
        trial = self.gw.pool.studies[slot].trials[-1]
        unit = np.asarray(trial.unit, np.float32).copy()
        y = self.objective(t, unit)
        self.gw.tell(t.sid, trial, y)
        self.hist[t.sid].append((unit, y))
        t.held += 1
        self.gw.tick()

    async def _study_for(self, t: Tenant) -> int:
        """The open study of tenant `t`, rotating it once it holds
        `study_obs` observations: the first worker to find it full waits
        until every suggestion of the old study is told and absorbed,
        closes it, and opens a new one; the tenant's other workers wait
        for that."""
        while True:
            if t.rotation is not None:
                await t.rotation
                continue
            if t.held < int(self.p["study_obs"]):
                t.held += 1
                return t.sid
            t.rotation = asyncio.get_running_loop().create_future()
            old = t.sid
            while True:
                info = self.gw.study_info(old)
                if info["inflight"] == 0 and \
                        info["n_obs"] == len(self.hist[old]):
                    break
                await asyncio.sleep(0.0005)
            if self.capture is not None and self.p.get("capture_closed"):
                self.captured[old] = self.capture(self.gw, old)
            self.gw.close_study(old)
            self.closes.append((time.perf_counter(), self.gw._tick_count,
                                t.idx))
            self._open(t)
            fut, t.rotation = t.rotation, None
            fut.set_result(None)

    # -- workers ------------------------------------------------------------
    def _pick(self, w: int, rng: np.random.Generator) -> Tenant:
        pick = self.p["pick"]
        if pick["dist"] == "own":
            return self.tenants[w]
        cap = int(pick["max_workers_per_tenant"])
        n = len(self.tenants)
        r = int(np.searchsorted(self._cdf, rng.uniform()))
        for k in range(n):
            t = self.tenants[self._rank_tenant[(min(r, n - 1) + k) % n]]
            if t.workers < cap:
                return t
        raise RuntimeError("every tenant has its cap of workers")

    def _session(self, rng: np.random.Generator) -> float:
        s = self.p["session"]
        if s["dist"] == "forever":
            return float("inf")
        return float(rng.geometric(1.0 / float(s["mean"])))

    def _think(self, rng: np.random.Generator) -> float:
        th = self.p["think"]
        if th["dist"] == "none":
            return 0.0
        return float(rng.lognormal(np.log(th["median_s"]), th["sigma"]))

    async def _worker(self, w: int, spans) -> None:
        rng = np.random.default_rng([self.seed, _WORKER, w])
        while not self.stopping:
            t = self._pick(w, rng)
            t.workers += 1
            try:
                left = self._session(rng)
                while left > 0 and not self.stopping:
                    left -= 1
                    sid = await self._study_for(t)
                    t0 = time.perf_counter()
                    try:
                        trial = await self.gw.ask(sid)
                    except Exception:  # noqa: BLE001 — counted as failed
                        self.asks.append(Ask(t0, time.perf_counter(), False,
                                             sid, -1, None))
                        t.held -= 1
                        await asyncio.sleep(0.01)
                        continue
                    t1 = time.perf_counter()
                    with spans("bench.record"):
                        n_cond = self.gw.study_info(sid)["n_obs"]
                        unit = np.asarray(trial.unit, np.float32).copy()
                        self.asks.append(Ask(t0, t1, True, sid, n_cond,
                                             unit))
                    think = self._think(rng)
                    if think > 0 and not self.stopping:
                        s0 = time.perf_counter()
                        await asyncio.sleep(think)
                        self.think_late.append(
                            time.perf_counter() - s0 - think)
                    with spans("bench.evaluate"):
                        y = self.objective(t, unit)
                    with spans("bench.tell"):
                        try:
                            self.gw.tell(sid, trial, y)
                            self.hist[sid].append((unit, y))
                            self.tells += 1
                        except Exception:  # noqa: BLE001 — counted
                            self.tell_failures += 1
            finally:
                t.workers -= 1

    async def _hot_shift(self) -> None:
        hs = self.p.get("hot_shift")
        if not hs or self.p["pick"]["dist"] != "zipf":
            return
        rng = np.random.default_rng([self.seed, _SHIFT])
        n = len(self.tenants)
        k = max(2, int(round(float(hs["share"]) * n)))
        while not self.stopping:
            await asyncio.sleep(float(hs["every_s"]))
            ranks = rng.choice(n, size=k, replace=False)
            self._rank_tenant[ranks] = self._rank_tenant[
                rng.permutation(ranks)]

    async def serve(self, warmup_s: float, seconds: float, *,
                    warmup_ticks: int = 0, on_open=None, on_close=None,
                    spans=None):
        """Warm up for `warmup_s` and at least `warmup_ticks` gateway
        ticks, then measure `seconds`; returns (t_open, t_close).

        After the close no worker issues a new ask; every outstanding ask
        is answered and told back (at once, without its think time) and
        the gateway is drained before this returns."""
        spans = spans or _no_span
        workers = [asyncio.ensure_future(self._worker(w, spans))
                   for w in range(int(self.p["workers"]))]
        shifter = asyncio.ensure_future(self._hot_shift())
        tick0 = self.gw._tick_count
        await asyncio.sleep(warmup_s)
        while self.gw._tick_count - tick0 < warmup_ticks:
            await asyncio.sleep(0.01)
        if on_open is not None:
            on_open()
        t_open = time.perf_counter()
        await asyncio.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
        self.stopping = True
        if on_close is not None:
            on_close()
        shifter.cancel()
        await asyncio.gather(*workers)
        await self.gw.drain()
        return t_open, t_close


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(_name):
    return _NoSpan()
