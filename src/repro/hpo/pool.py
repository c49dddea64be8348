"""Multi-tenant StudyPool: S concurrent HPO studies on one accelerator.

The paper's O(n^2) lazy append makes a *single* study cheap enough that the
device idles between absorptions; the next scaling axis (ROADMAP: serve
heavy traffic) is running **many concurrent studies**.  `StudyPool`
multiplexes S studies over one `StudyEngine` (a stacked `LazyGPState`,
DESIGN.md §7):

  * **batched suggest** — `suggest_all` advances every study's EI
    optimization in ONE jitted vmapped dispatch instead of S sequential
    program launches (the multi-tenant throughput win, `bench_pool`).
  * **completion-order absorb** — results are routed to the owning study as
    they arrive (`absorb`), or drained in masked batched rounds
    (`absorb_many`) of at most one observation per study per dispatch.
  * **fused serving rounds** — `advance_round` absorbs the last round's
    completions AND suggests the next batch in ONE jitted program with
    donated state buffers (the request-driven service hot path).
  * **device mesh** — with `cfg.mesh` set, the batched rounds run as
    `shard_map` programs over a (study x restart) mesh (DESIGN.md §8);
    `mesh="none"` is the degenerate single-device case of the same code.
  * **per-study everything** — trial ledgers, PRNG streams, capacity
    guards, fault policy (retry / penalized pseudo-observation), lag
    counters, and clamp telemetry are tracked per tenant; one study filling
    up or crashing never corrupts a neighbor.
  * **pool checkpointing** — the stacked GP state and every study's ledger
    ride one atomic `checkpoint.store` snapshot; a restarted pool resumes
    all S posteriors identically.

`TrialScheduler` is the S = 1 degenerate case: it wraps a one-study pool,
so the scheduler and the pool share exactly one suggest/absorb code path.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_mod
from repro.core import acquisition as acq_mod
from repro.core import gp as gp_mod
from repro.core import neural_basis as nb_mod
from repro.core.kernels import KernelParams
from repro.hpo.engine import StudyEngine
from repro.hpo.space import SearchSpace
from repro.hpo.telemetry import span


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Shared study/pool configuration (one GP shape for every tenant)."""

    n_max: int = 512
    kernel: str = "matern52"
    lag: int = 0                 # 0 = fully lazy (paper's main mode)
    parallel: int = 1            # t (elastic; re-read each round)
    rho0: float = 0.25
    noise2: float = 1e-5
    seed: int = 0
    implementation: str = "auto"  # linalg substrate (auto|pallas|xla|ref)
    mixed: bool = False          # force mixed-space closures (DESIGN.md
    # §10) even when every constructor space is all-continuous — a gateway
    # that must admit int/categorical tenants later sets this; pools whose
    # constructor spaces already carry discrete dims enable it implicitly
    mesh: str = "none"           # device mesh for the batched suggest path
    # (DESIGN.md §8): "none" = single program on one device (default);
    # "auto" = factor all visible devices into study x restart shards;
    # "SxR" (e.g. "4x2") = explicit shard counts.  Threaded to StudyEngine
    # exactly like `implementation`; "none" is the degenerate case of the
    # same closures.
    failure_penalty: float | None = None  # None: drop; else pseudo-y
    max_retries: int = 1
    ckpt_dir: str | None = None
    ckpt_every: int = 1          # absorptions between pool checkpoints; a
    # many-tenant pool should raise this — every snapshot serializes the
    # whole stacked state (2 S n_max^2 floats) plus all S ledgers
    inv_refresh: int = 128       # fully-lazy mode (lag=0): rebuild the
    # factor + maintained inverse from the Gram every `inv_refresh` appends
    # per study, re-anchoring float32 drift without touching the kernel
    # params (0 = never; lag > 0 supersedes it — see DESIGN.md §4)
    acq: acq_mod.AcqConfig = dataclasses.field(
        default_factory=lambda: acq_mod.AcqConfig(restarts=48,
                                                  ascent_steps=20))
    fantasy: gp_mod.FantasyConfig = dataclasses.field(
        default_factory=gp_mod.FantasyConfig)  # liar policy for q-asks
    # (DESIGN.md §12): "mean" = kriging believer, "pessimistic" = constant
    # liar.  A Python constant inside the engine's q-ask closures.
    neural: nb_mod.NeuralConfig = dataclasses.field(
        default_factory=nb_mod.NeuralConfig)  # escalated-tier model
    # (DESIGN.md §15): MLP feature width/depth, Bayesian-linear-head noise,
    # and the refit cadence (the NB tier's `lag`) used when a saturated
    # study is promoted off the fixed-shape lazy GP.


@dataclasses.dataclass
class Trial:
    trial_id: int
    unit: np.ndarray
    hparams: dict
    status: str = "pending"      # pending | running | told | done | failed
    value: float | None = None
    error: str | None = None
    started: float = 0.0
    finished: float = 0.0
    retries: int = 0
    clamp_count: int | None = None  # cumulative GP conditioning-floor hits
    # at absorb time (ill-conditioning telemetry, DESIGN.md §6)
    cost: float = 1.0            # tell-time observation cost (DESIGN.md
    # §15): training-set row of the escalated tier's log-cost head and the
    # denominator of EI-per-unit-cost acquisition


def _trial_from_dict(t: dict) -> Trial:
    """Rebuild a ledger Trial from its checkpoint/export dict form."""
    return Trial(t["trial_id"], np.asarray(t["unit"], np.float32),
                 t["hparams"], t["status"], t["value"], t["error"],
                 t["started"], t["finished"], t["retries"],
                 t.get("clamp_count"), t.get("cost", 1.0))


def _materialize(x) -> np.ndarray:
    """Host fetch of a staged round's device outputs.

    Module-level so fault tests can inject a device-side failure at the
    materialization boundary — the point where a pipelined tick's in-flight
    runtime error actually surfaces to the host.
    """
    return np.asarray(x)


class _PendingRound:
    """A dispatched-but-unmaterialized fused serving round.

    `advance_round(...)` == `advance_round_begin(...).finish()`.  Every
    device dispatch is ISSUED at begin time in exactly the serial order
    (fantasy rollback, overflow drain, fused advance, clamp copy,
    refantasize), so the device program stream — and therefore the final
    state bits — are identical whether or not the host defers `finish()`.
    `finish()` only does host work: materialize the suggestions (the
    `pool.materialize` span, where the host waits for the device), flip the
    absorbed trials' ledger status, and mint the ledger Trial objects
    (`pool.mint`), timing both into the round's `phases` tally.

    The pending record holds ONLY fresh dispatch outputs (`units`, a
    copied clamp vector) — never a reference into `engine.state`, whose
    buffers the NEXT staged round consumes by donation.
    """

    __slots__ = ("_pool", "_first", "_ids", "_need_seed", "_t",
                 "_units", "_clamps", "_nb_units", "_phases", "_finished")

    def __init__(self, pool: "StudyPool", first: dict, ids: list,
                 need_seed: set, t: int, units, clamps, nb_units=None,
                 phases: dict | None = None):
        self._pool = pool
        self._first = first
        self._ids = ids
        self._need_seed = need_seed
        self._t = t
        self._units = units
        self._clamps = clamps
        self._nb_units = nb_units or {}
        self._phases = phases
        self._finished = False

    def finish(self) -> dict[int, list[Trial]]:
        """Materialize the round: commit ledger flips, mint suggestions."""
        if self._finished:
            raise RuntimeError("pending round already finished")
        self._finished = True
        pool = self._pool
        with span("pool.materialize", self._phases):
            units = None if self._units is None else \
                _materialize(self._units)
            clamps = np.asarray(self._clamps) if self._first else None
        out: dict[int, list[Trial]] = {}
        with span("pool.mint", self._phases):
            # "done" only after the fused round committed (see absorb())
            for sid, (tr, val) in self._first.items():
                tr.status = "done"
                tr.value = float(val)
                tr.finished = time.time()
                tr.clamp_count = int(clamps[sid])
            pool._n_done += len(self._first)
            for s in self._ids:
                if s in self._need_seed:
                    out[s] = pool.seed_trials(s, self._t)
                elif s in self._nb_units:
                    # escalated tenants: their suggestions come off the NB
                    # posterior's own staged dispatch, not the GP stack's
                    # lane
                    out[s] = [pool._make_trial(s, u)
                              for u in _materialize(self._nb_units[s])]
                else:
                    out[s] = [pool._make_trial(s, u) for u in units[s]]
        pool._maybe_checkpoint()
        return out


@dataclasses.dataclass
class StudyHandle:
    """Host-side per-tenant record: ledger, id counter, seed-trial stream
    (the study's PRNG key is its row of the pool's key table)."""

    study_id: int
    space: SearchSpace
    name: str
    trials: list[Trial] = dataclasses.field(default_factory=list)
    next_id: int = 0
    rng: np.random.Generator | None = None  # seed-trial stream; persistent
    # so repeated seeding draws fresh points, never the same batch twice


# One program splits the whole (S, 2) key table; run on the host's CPU
# device, it is bit-identical to a per-study `jax.random.split`.
_split_table = jax.jit(jax.vmap(jax.random.split))


class StudyPool:
    """S concurrent studies multiplexed over one batched lazy-GP engine.

    All studies share the GP shape (`cfg.n_max`, `space.dim`) — the stacked
    buffers are one rectangular block — but own independent posteriors,
    ledgers, and fault state.  Spaces may differ per study as long as their
    dimensionality matches.
    """

    def __init__(self, spaces: Sequence[SearchSpace], cfg: SchedulerConfig,
                 names: Sequence[str] | None = None):
        spaces = list(spaces)
        if not spaces:
            raise ValueError("StudyPool needs at least one study")
        dims = {sp.dim for sp in spaces}
        if len(dims) != 1:
            raise ValueError(
                f"all studies must share one dimensionality, got {dims} "
                "(the stacked (S, n_max, d) buffers are rectangular)")
        names = list(names) if names is not None else [
            f"study{i}" for i in range(len(spaces))]
        if len(names) != len(spaces):
            raise ValueError("len(names) != len(spaces)")
        self.cfg = cfg
        # Descriptors are only materialized (S x 5 device arrays) when the
        # engine will actually thread them — all-continuous pools keep the
        # pre-§10 constructor cost.
        descs = [sp.descriptor() for sp in spaces] \
            if cfg.mixed or any(sp.has_discrete for sp in spaces) else None
        self.engine = StudyEngine(spaces[0].dim, cfg, len(spaces),
                                  descs=descs)
        self.studies = [
            StudyHandle(i, sp, names[i],
                        rng=np.random.default_rng(cfg.seed + i))
            for i, sp in enumerate(spaces)]
        # Per-study PRNG streams: one host uint32 row per slot, advanced by
        # `_advance_keys` (the split program is compiled here, once per S).
        self._cpu = jax.devices("cpu")[0]
        self._keys = np.stack([self._seed_key(cfg.seed + i)
                               for i in range(len(spaces))])
        _split_table(jax.device_put(self._keys, self._cpu))
        self._done_at_last_ckpt = 0
        self._n_done = 0  # absorptions ever (ckpt cadence + monotonic step;
        # counts absorbs into since-evicted slots, unlike total_done())
        self.last_restore_meta: dict | None = None  # set by restore()
        # Fantasy protocol (DESIGN.md §12): per-slot pending fantasy points,
        # in append order.  The slot's device n exceeds its real ledger by
        # exactly len(self._fantasies[slot]); every real absorb first rolls
        # the fantasy rows back (bitwise truncate), then re-fantasizes the
        # survivors.  `fantasy_rollbacks` counts truncations performed.
        self._fantasies: list[list[np.ndarray]] = [[] for _ in spaces]
        self.fantasy_rollbacks = 0

    @property
    def n_studies(self) -> int:
        return len(self.studies)

    # -- ledger -------------------------------------------------------------
    def _make_trial(self, study_id: int, unit: np.ndarray) -> Trial:
        h = self.studies[study_id]
        tr = Trial(h.next_id, unit.astype(np.float32),
                   h.space.to_hparams(unit))
        h.next_id += 1
        h.trials.append(tr)
        return tr

    def _seed_key(self, seed: int) -> np.ndarray:
        """`jax.random.PRNGKey(seed)` as a host `(2,)` uint32 row."""
        with jax.default_device(self._cpu):
            return np.asarray(jax.random.PRNGKey(seed))

    def _advance_keys(self, ids: Sequence[int]) -> np.ndarray:
        """Advance the PRNG streams of the studies in `ids`.

        Splits the whole key table in one fixed-shape call on the host's
        CPU device, whatever `ids` holds (so a new count of asking studies
        compiles nothing), keeps the new key only in the rows of `ids`, and
        returns their subkeys as a host `(len(ids), 2)` uint32 array:
        bit-identical to a `jax.random.split` of each study's key.
        """
        new = np.asarray(_split_table(jax.device_put(self._keys, self._cpu)))
        mask = np.zeros((self.n_studies, 1), bool)
        mask[ids] = True
        self._keys = np.where(mask, new[:, 0], self._keys)
        return new[ids, 1]

    def _split(self, study_id: int) -> np.ndarray:
        return self._advance_keys([study_id])[0]

    def _split_many(self, ids: Sequence[int],
                    phases: dict | None = None) -> np.ndarray:
        """Advance several studies' PRNG streams at once (`_advance_keys`),
        timed as `pool.split_keys`."""
        if not ids:
            return np.zeros((0, 2), np.uint32)
        with span("pool.split_keys", phases):
            return self._advance_keys(ids)

    def state(self, study_id: int) -> gp_mod.LazyGPState:
        """Unstacked single-study GP view."""
        return self.engine.study_state(study_id)

    # -- saturation escalation (DESIGN.md §15) ------------------------------
    def tier(self, study_id: int) -> int:
        """0 = lazy GP, 1 = neural basis (escalated past n_max)."""
        return self.engine.tier(study_id)

    def promote(self, study_id: int) -> None:
        """Escalate a saturated study to the neural-basis tier.

        Pending fantasy rows are first rolled back (bitwise GP truncate) so
        the NB model trains on the REAL ledger + tell costs only; the
        survivors are then re-fantasized against the escalated posterior —
        outstanding q-asks keep repelling their regions across the
        promotion, exactly as they would across a tell.
        """
        pend = self._fantasies[study_id]
        if pend:
            self.engine.truncate_slot(
                study_id, self.engine.n(study_id) - len(pend))
            self.fantasy_rollbacks += 1
        self.engine.promote_slot(study_id, self._split(study_id))
        if pend:
            self.engine.nb_refantasize(study_id, np.stack(pend))

    # -- suggest ------------------------------------------------------------
    def seed_trials(self, study_id: int, n: int) -> list[Trial]:
        h = self.studies[study_id]
        return [self._make_trial(study_id, u)
                for u in h.space.sample(h.rng, n)]

    def suggest(self, study_id: int, t: int | None = None) -> list[Trial]:
        """Top-t distinct EI local maxima from one study's posterior."""
        t = t or self.cfg.parallel
        if self.engine.tier(study_id):
            units, _ = self.engine.nb_suggest(study_id,
                                              self._split(study_id), top_t=t)
        elif self.engine.n(study_id) == 0:
            return self.seed_trials(study_id, t)
        else:
            units, _ = self.engine.suggest(study_id, self._split(study_id),
                                           top_t=t)
        return [self._make_trial(study_id, np.asarray(u)) for u in units]

    # -- fantasy protocol: batched q-suggestion (DESIGN.md §12) -------------
    def fantasy_active(self, study_id: int) -> int:
        """Pending fantasy rows currently appended to this slot's factor."""
        return len(self._fantasies[study_id])

    def n_real(self, study_id: int) -> int:
        """Real-ledger active count (model n minus pending fantasy rows)."""
        n = self.engine.nb_n(study_id) if self.engine.tier(study_id) \
            else self.engine.n(study_id)
        return n - len(self._fantasies[study_id])

    def ask_q(self, study_id: int, q: int) -> list[Trial]:
        """q distinct suggestions through the fantasy fast path.

        ONE jitted dispatch (engine `ask_q`) runs q rounds of
        suggest-then-fantasize; the q fantasy rows PERSIST in the slot's
        factor — later asks (any width) see the collapsed variance at the
        outstanding points — until a real observation arrives and the
        absorb paths roll them back (bitwise truncate + replay).  Studies
        still empty of observations get q random seed trials instead
        (host-side, mirroring `suggest`).
        """
        if q < 1:
            raise ValueError(f"q must be >= 1, got {q}")
        if self.engine.tier(study_id):
            # escalated tier: the NB ledger doubles instead of filling, so
            # q-asks never hit a capacity guard
            units, _ = self.engine.nb_ask_q(study_id,
                                            self._split(study_id), q)
        else:
            if self.engine.n(study_id) == 0:
                return self.seed_trials(study_id, q)
            gp_mod.ensure_capacity(self.engine.n(study_id),
                                   self.cfg.n_max, q)
            units, _ = self.engine.ask_q(study_id, self._split(study_id), q)
        units = np.asarray(units)
        self._fantasies[study_id].extend(u.copy() for u in units)
        return [self._make_trial(study_id, u) for u in units]

    def _rollback_for_events(
            self, events: Sequence[tuple[int, Trial, float]]) -> None:
        """Truncate every fantasy-active study named in `events` back to its
        real ledger (bitwise — `engine.truncate_slot`), dropping each told
        trial's point from that study's pending list.  Told points that were
        never fantasies (plain `suggest` trials, foreign tells) trigger the
        same rollback: the real append must never land on fantasized rows.
        """
        by_sid: dict[int, list[Trial]] = {}
        for sid, tr, _ in events:
            by_sid.setdefault(sid, []).append(tr)
        for sid, trs in by_sid.items():
            pend = self._fantasies[sid]
            if not pend:
                continue
            if self.engine.tier(sid):
                # NB rank-1 updates are not bitwise-reversible: rollback is
                # a pre-fantasy snapshot restore (exact by construction)
                self.engine.nb_rollback(sid)
            else:
                self.engine.truncate_slot(sid,
                                          self.engine.n(sid) - len(pend))
            self.fantasy_rollbacks += 1
            for tr in trs:
                for i, u in enumerate(pend):
                    if np.array_equal(u, tr.unit):
                        del pend[i]
                        break

    def release_fantasies(self, study_id: int, units) -> int:
        """Drop abandoned fantasy rows (failed or cancelled asks whose tell
        will never come): one bitwise truncate + one batched replay of the
        survivors.  Each unit releases at most one pending row; unknown
        units are ignored.  Returns the number of rows released."""
        pend = self._fantasies[study_id]
        if not pend:
            return 0
        drop: list[int] = []
        for u in units:
            for i, p in enumerate(pend):
                if i not in drop and np.array_equal(p, u):
                    drop.append(i)
                    break
        if not drop:
            return 0
        if self.engine.tier(study_id):
            self.engine.nb_rollback(study_id)
        else:
            self.engine.truncate_slot(
                study_id, self.engine.n(study_id) - len(pend))
        self.fantasy_rollbacks += 1
        self._fantasies[study_id] = [
            p for i, p in enumerate(pend) if i not in drop]
        self._refantasize_pending([study_id])
        return len(drop)

    def _refantasize_pending(self, sids) -> None:
        """Re-append each study's surviving fantasy points in ONE batched
        `lazy_append_rows` dispatch per study (liar values recomputed
        against the now-updated real posterior — fresher than the originals,
        which is fine: fantasy rows are scratch)."""
        for sid in sorted(set(sids)):
            pend = self._fantasies[sid]
            if pend:
                if self.engine.tier(sid):
                    self.engine.nb_refantasize(sid, np.stack(pend))
                else:
                    self.engine.refantasize(sid, np.stack(pend))

    def _check_capacity(self,
                        events: Sequence[tuple[int, Trial, float]]) -> None:
        """All-or-nothing capacity contract: validate the WHOLE queue
        (per-study multiplicity included) BEFORE mutating any ledger, so a
        `GPCapacityError` from one full study never leaves a neighbor's
        trial marked done without its observation absorbed.  Surviving
        fantasy rows count against capacity too: they are re-appended after
        the absorb, so `n_real + events + pending` must fit (callers run
        the fantasy rollback first, making `engine.n` the real count)."""
        counts: dict[int, int] = {}
        for sid, _, _ in events:
            counts[sid] = counts.get(sid, 0) + 1
        for sid, c in counts.items():
            if self.engine.tier(sid):
                continue   # escalated ledgers double instead of filling
            gp_mod.ensure_capacity(self.engine.n(sid), self.cfg.n_max,
                                   incoming=c + len(self._fantasies[sid]))

    def _staged_keys(self, ei_ids: Sequence[int],
                     phases: dict | None = None) -> jax.Array:
        """(S, 2) key batch: fresh subkeys for `ei_ids` (their streams
        advance, one batched split), dummy zeros for everyone else (their
        lane computes alongside but the result is discarded)."""
        subs = self._split_many(list(ei_ids), phases)
        keys_np = np.zeros((self.n_studies, 2), np.uint32)
        keys_np[list(ei_ids)] = subs
        return jnp.asarray(keys_np)

    def suggest_all(self, t: int = 1,
                    studies: Sequence[int] | None = None
                    ) -> dict[int, list[Trial]]:
        """Batched suggestion round: ONE vmapped dispatch for all studies.

        Studies still empty of observations get random seed trials instead
        (host-side); everyone else shares the single batched EI program.
        Returns {study_id: [t trials]} for the requested studies (default
        all).
        """
        ids = list(studies) if studies is not None else \
            list(range(self.n_studies))
        nb_set = {s for s in ids if self.engine.tier(s)}
        need_ei = sorted(s for s in ids
                         if s not in nb_set and self.engine.n(s) > 0)
        ei_set = set(need_ei)
        units_all = None
        if need_ei:
            units_all = np.asarray(self.engine.suggest_all(
                self._staged_keys(need_ei), top_t=t)[0])
        out: dict[int, list[Trial]] = {}
        for s in ids:
            if s in ei_set:
                out[s] = [self._make_trial(s, u) for u in units_all[s]]
            elif s in nb_set:
                # escalated tenants route through their own NB dispatch
                # (cached by shape + static config, never re-traced per slot)
                units, _ = self.engine.nb_suggest(s, self._split(s), top_t=t)
                out[s] = [self._make_trial(s, u)
                          for u in np.asarray(units)]
            else:
                out[s] = self.seed_trials(s, t)
        return out

    def advance_round_begin(self,
                            events: Sequence[tuple[int, Trial, float]],
                            t: int = 1,
                            studies: Sequence[int] | None = None,
                            phases: dict | None = None
                            ) -> _PendingRound:
        """Stage a fused serving round: dispatch everything, defer commits.

        Issues the round's whole device program stream (fantasy rollback,
        overflow drain, fused donated advance, refantasize) in the serial
        order and returns a `_PendingRound` whose `finish()` performs the
        host-side half — materialize suggestions, flip told trials to
        "done", mint ledger Trials.  The pipelined gateway stages tick t+1
        while tick t's program is still in flight on the device; calling
        `finish()` immediately is exactly `advance_round`.  The staging is
        timed as `pool.round_begin` into `phases` (the tick's tally, which
        the returned round's `finish()` adds its own phases to).

        All-or-nothing guards run at STAGE time: a capacity error raises
        here with no ledger or buffer mutated (beyond the fantasy rollback,
        which is bitwise-restorable by re-fantasizing).  Once staged, the
        only failure left is a device runtime fault, which surfaces at
        `finish()` before any ledger flip.
        """
        with span("pool.round_begin", phases):
            ids = list(studies) if studies is not None else \
                list(range(self.n_studies))
            nb_set = {s for s in range(self.n_studies)
                      if self.engine.tier(s)}
            if not events:
                # deferred suggest_all: same stream staging and seed
                # routing, with the materialization/minting left to finish()
                need_ei = sorted(s for s in ids
                                 if s not in nb_set and self.engine.n(s) > 0)
                units = None
                if need_ei:
                    units = self.engine.suggest_all(
                        self._staged_keys(need_ei, phases), top_t=t)[0]
                nb_units = {s: self.engine.nb_suggest(s, self._split(s),
                                                      top_t=t)[0]
                            for s in ids if s in nb_set}
                return _PendingRound(self, {}, ids,
                                     set(ids) - set(need_ei) - nb_set,
                                     t, units, None, nb_units, phases)
            if not ids:
                self.absorb_many(events)
                return _PendingRound(self, {}, [], set(), t, None, None,
                                     phases=phases)
            # Escalated tenants' completions take the routed NB absorb
            # (their ledger doubles instead of filling — no fused GP lane to
            # share); the GP-tier events keep the one-per-study fused-round
            # split.
            nb_events = [e for e in events if e[0] in nb_set]
            gp_events = [e for e in events if e[0] not in nb_set]
            first: dict[int, tuple[Trial, float]] = {}
            overflow = []
            for sid, tr, val in gp_events:
                if sid in first:
                    overflow.append((sid, tr, val))
                else:
                    first[sid] = (tr, val)
            # Fantasy rollback BEFORE the capacity check and any absorb:
            # told studies are truncated to their real ledger (bitwise), so
            # every append below lands exactly where a never-fantasized run
            # would put it; survivors are re-fantasized after the round.
            self._rollback_for_events(events)
            self._check_capacity(events)
            if nb_events:
                self.absorb_many(nb_events, _fantasies_handled=True)
            if overflow:
                self.absorb_many(overflow, _fantasies_handled=True)
            dim = self.engine.gp_cfg.dim
            flags = np.zeros((self.n_studies,), bool)
            xs = np.zeros((self.n_studies, dim), np.float32)
            ys = np.zeros((self.n_studies,), np.float32)
            costs = np.ones((self.n_studies,), np.float32)
            for sid, (tr, val) in first.items():
                flags[sid] = True
                xs[sid] = tr.unit
                ys[sid] = float(val)
                costs[sid] = tr.cost
            # Studies that will still be empty after this absorb get seed
            # trials; only requested non-seed studies advance their streams.
            need_seed = {s for s in ids if s not in nb_set
                         and self.engine.n(s) == 0 and not flags[s]}
            ei_ids = [s for s in ids
                      if s not in need_seed and s not in nb_set]
            units, _ = self.engine.advance(
                flags, xs, ys, self._staged_keys(ei_ids, phases), top_t=t,
                costs=costs)
            # Clamp telemetry is copied into a FRESH device array before the
            # refantasize (serial read point) — holding `state.clamp_count`
            # itself would break when the next staged round donates it.
            clamps = self.engine.state.clamp_count + 0
            nb_units = {s: self.engine.nb_suggest(s, self._split(s),
                                                  top_t=t)[0]
                        for s in ids if s in nb_set}
            self._refantasize_pending(sid for sid, _, _ in events)
            return _PendingRound(self, first, ids, need_seed, t, units,
                                 clamps, nb_units, phases)

    def advance_round(self, events: Sequence[tuple[int, Trial, float]],
                      t: int = 1,
                      studies: Sequence[int] | None = None
                      ) -> dict[int, list[Trial]]:
        """Fused serving round: absorb completions + suggest in ONE dispatch.

        The hot path of a request-driven service (`examples/hpo_service.py`,
        `benchmarks/bench_shard.py`): one jitted program absorbs at most
        one completed trial per study and suggests the next t points from
        the updated posteriors (state buffers donated — no copy of the
        stacked factors per round).  Suggestions are materialized as ledger
        trials only for `studies` (default all) — e.g. tenants that hit
        their budget absorb results without drawing new trials.  Events
        beyond one per study fall back to an `absorb_many` drain first;
        studies still empty after the absorb get host-side seed trials
        instead of their EI lane's output, exactly like `suggest_all`.
        Rounds with nothing to absorb skip the absorb half and delegate to
        `suggest_all`; rounds with nobody to suggest for delegate to
        `absorb_many`.

        Implemented as `advance_round_begin(...).finish()` — the pipelined
        gateway (DESIGN.md §13) drives the two halves separately.
        """
        return self.advance_round_begin(events, t=t, studies=studies).finish()

    # -- absorb -------------------------------------------------------------
    def absorb(self, study_id: int, trial: Trial, value: float,
               cost: float | None = None) -> None:
        """Completion-order absorb routed to the owning study."""
        if cost is not None:
            trial.cost = float(cost)
        self._rollback_for_events([(study_id, trial, value)])
        if self.engine.tier(study_id):
            self.engine.nb_absorb(study_id, trial.unit, float(value),
                                  cost=trial.cost)
        else:
            gp_mod.ensure_capacity(
                self.engine.n(study_id), self.cfg.n_max,
                incoming=1 + len(self._fantasies[study_id]))
            self.engine.absorb(study_id, jnp.asarray(trial.unit),
                               jnp.asarray(value, jnp.float32),
                               cost=trial.cost)
        # status flips to "done" only once the append committed: callers
        # (the gateway's fault unwind) rely on it to mean "in the GP"
        trial.status = "done"
        trial.value = float(value)
        trial.finished = time.time()
        trial.clamp_count = self.engine.clamp_count(study_id)
        self._refantasize_pending([study_id])
        self._n_done += 1
        self._maybe_checkpoint()

    def absorb_many(self,
                    events: Sequence[tuple[int, Trial, float]],
                    _fantasies_handled: bool = False) -> None:
        """Drain a completion queue in masked batched rounds.

        Events may arrive in any completion order and any per-study
        multiplicity; each round takes at most one event per study and runs
        ONE vmapped masked append, so k completions across S studies cost
        ceil(max per-study count) dispatches instead of k.

        `_fantasies_handled` is the `advance_round` overflow path: the
        caller already rolled fantasy rows back for every event and will
        re-fantasize after its own fused round — this drain must not
        re-append pending rows mid-protocol.
        """
        queue = list(events)
        dim = self.engine.gp_cfg.dim
        if not _fantasies_handled:
            self._rollback_for_events(queue)
        self._check_capacity(queue)
        # Escalated tenants drain through the routed NB absorb (rank-1
        # append, flat in n) — they have no lane in the masked GP round.
        nb_queue = [e for e in queue if self.engine.tier(e[0])]
        queue = [e for e in queue if not self.engine.tier(e[0])]
        for sid, tr, val in nb_queue:
            self.engine.nb_absorb(sid, tr.unit, float(val), cost=tr.cost)
            tr.status = "done"
            tr.value = float(val)
            tr.finished = time.time()
            tr.clamp_count = self.engine.clamp_count(sid)
            self._n_done += 1
        while queue:
            round_events: dict[int, tuple[Trial, float]] = {}
            rest = []
            for sid, tr, val in queue:
                if sid in round_events:
                    rest.append((sid, tr, val))
                else:
                    round_events[sid] = (tr, val)
            queue = rest
            flags = np.zeros((self.n_studies,), bool)
            xs = np.zeros((self.n_studies, dim), np.float32)
            ys = np.zeros((self.n_studies,), np.float32)
            costs = np.ones((self.n_studies,), np.float32)
            for sid, (tr, val) in round_events.items():
                flags[sid] = True
                xs[sid] = tr.unit
                ys[sid] = float(val)
                costs[sid] = tr.cost
            self.engine.absorb_round(flags, xs, ys, costs)
            clamps = self.engine.clamp_counts()   # one transfer for all S
            # "done" only after the round committed (see absorb())
            for sid, (tr, val) in round_events.items():
                tr.status = "done"
                tr.value = float(val)
                tr.finished = time.time()
                tr.clamp_count = int(clamps[sid])
            self._n_done += len(round_events)
        if not _fantasies_handled:
            self._refantasize_pending(sid for sid, _, _ in events)
        self._maybe_checkpoint()

    def record_failure(self, study_id: int, trial: Trial,
                       error: str) -> Trial | None:
        """Failed trial: retry (fresh suggestion) or penalize the region."""
        trial.status = "failed"
        trial.error = error
        trial.finished = time.time()
        if self.cfg.failure_penalty is not None:
            # Pseudo-observation keeps EI away from a crashing region.
            self._rollback_for_events([(study_id, trial, 0.0)])
            if self.engine.tier(study_id):
                self.engine.nb_absorb(study_id, trial.unit,
                                      float(self.cfg.failure_penalty),
                                      cost=trial.cost)
            else:
                gp_mod.ensure_capacity(
                    self.engine.n(study_id), self.cfg.n_max,
                    incoming=1 + len(self._fantasies[study_id]))
                self.engine.absorb(study_id, jnp.asarray(trial.unit),
                                   jnp.asarray(self.cfg.failure_penalty,
                                               jnp.float32),
                                   cost=trial.cost)
            trial.clamp_count = self.engine.clamp_count(study_id)
            self._refantasize_pending([study_id])
        elif any(np.array_equal(u, trial.unit)
                 for u in self._fantasies[study_id]):
            # No pseudo-observation lands, but the failed trial's fantasy
            # row must still be released: truncate + replay the survivors
            # so the slot stops repelling a region nobody is evaluating.
            self._rollback_for_events([(study_id, trial, 0.0)])
            self._refantasize_pending([study_id])
        if trial.retries < self.cfg.max_retries:
            nxt = self.suggest(study_id, 1)[0]
            nxt.retries = trial.retries + 1
            return nxt
        return None

    # -- inspection ---------------------------------------------------------
    def best(self, study_id: int) -> Trial | None:
        done = [t for t in self.studies[study_id].trials
                if t.status == "done"]
        return max(done, key=lambda t: t.value) if done else None

    def history(self, study_id: int) -> list[dict]:
        return [dataclasses.asdict(t) | {"unit": t.unit.tolist()}
                for t in self.studies[study_id].trials]

    def total_done(self) -> int:
        return sum(t.status == "done"
                   for h in self.studies for t in h.trials)

    # -- slot lifecycle (the gateway's evict/restore/reuse hooks, §9) -------
    def export_study(self, slot: int) -> dict:
        """Host-side snapshot of ONE slot: GP sub-state + handle metadata.

        The returned dict round-trips through `import_study` (and through
        `checkpoint.save_study`) bitwise: float32 buffers are exported as
        numpy arrays and re-written into the stack elementwise, so an
        evicted-and-restored study continues exactly where it left off.

        Fantasy-pinned slots refuse to export: snapshots must hold only
        real state (DESIGN.md §12) — the gateway keeps such studies
        non-evictable, so reaching this guard means a protocol bug.
        """
        if self._fantasies[slot]:
            raise RuntimeError(
                f"slot {slot} has {len(self._fantasies[slot])} active "
                "fantasy rows; eviction snapshots must see only real state "
                "(resolve or roll back the pending q-ask first)")
        h = self.studies[slot]
        tree = jax.tree.map(np.asarray,
                            dataclasses.asdict(self.engine.study_state(slot)))
        meta = {"name": h.name, "next_id": h.next_id,
                "trials": self.history(slot),
                "key": self._keys[slot].tolist(),
                "rng_state": h.rng.bit_generator.state,
                # escalation tier (DESIGN.md §15): the tag, the per-row
                # tell costs (float32 -> float64 -> JSON is exact), and —
                # for escalated slots — the NB state itself.  These ride
                # the snapshot as metadata because the checkpoint store
                # shape-validates `tree` against the fixed GP layout.
                "tier": self.engine.tier(slot),
                "costs": self.engine.cost_row(slot).tolist()}
        if self.engine.tier(slot):
            meta["nb"] = nb_mod.nb_to_json(self.engine.nb_state(slot))
        return {"tree": tree, "meta": meta}

    def import_study(self, slot: int, tree: dict, meta: dict,
                     space: SearchSpace | None = None) -> None:
        """Load an exported study into `slot` (inverse of `export_study`)."""
        tree = dict(tree)
        tree["params"] = KernelParams(**tree["params"])
        self.engine.load_slot(slot, gp_mod.LazyGPState(**tree))
        self.engine.clear_nb_slot(slot)
        if "costs" in meta:          # after clear (clear resets the row)
            self.engine.set_cost_row(slot, meta["costs"])
        if meta.get("tier"):
            self.engine.load_nb_slot(slot, nb_mod.nb_from_json(meta["nb"]))
        self._fantasies[slot] = []   # snapshots hold only real state
        h = self.studies[slot]
        if space is not None:
            h.space = space
            if self.engine.mixed or space.has_discrete:
                # (the has_discrete arm lets a non-mixed engine raise the
                # explanatory set_desc error instead of mis-serving)
                self.engine.set_desc(slot, space.descriptor())
        h.name = meta["name"]
        h.next_id = int(meta["next_id"])
        self._keys[slot] = np.asarray(meta["key"], np.uint32)
        h.rng = np.random.default_rng()
        h.rng.bit_generator.state = meta["rng_state"]
        h.trials = [_trial_from_dict(t) for t in meta["trials"]]

    def reset_study(self, slot: int, space: SearchSpace | None = None,
                    name: str | None = None, seed: int | None = None) -> None:
        """Blank a slot for a new tenant: fresh GP state, ledger, PRNGs.

        `seed` defaults to the constructor's `cfg.seed + slot`; the gateway
        passes `cfg.seed + logical_id` instead, so a tenant's random streams
        are a function of WHO it is, not of which slot it lands in.
        """
        if space is not None and space.dim != self.engine.gp_cfg.dim:
            raise ValueError(
                f"space dim {space.dim} != pool dim {self.engine.gp_cfg.dim}")
        self.engine.reset_slot(slot)
        self._fantasies[slot] = []
        h = self.studies[slot]
        seed = self.cfg.seed + slot if seed is None else seed
        if space is not None:
            h.space = space
            if self.engine.mixed or space.has_discrete:
                # descriptor arrays are only built when the engine threads
                # them — all-continuous slot churn stays transfer-free
                self.engine.set_desc(slot, space.descriptor())
        h.name = name if name is not None else f"study{slot}"
        h.trials = []
        h.next_id = 0
        self._keys[slot] = self._seed_key(seed)
        h.rng = np.random.default_rng(seed)

    # -- checkpointing (the whole pool rides one atomic snapshot) -----------
    def _maybe_checkpoint(self) -> None:
        """Snapshot every `ckpt_every` absorptions (each snapshot serializes
        the full stacked state + every ledger, so many-tenant pools batch)."""
        if not self.cfg.ckpt_dir:
            return
        if self._n_done - self._done_at_last_ckpt >= max(1, self.cfg.ckpt_every):
            self.checkpoint()

    def checkpoint(self, extra: dict | None = None) -> str | None:
        """Atomic whole-pool snapshot; `extra` metadata (JSON-serializable)
        rides along and comes back in `last_restore_meta` — the gateway
        stores its logical-study registry there.

        Checkpoints see only real state (DESIGN.md §12): fantasy-active
        slots are truncated to their real ledger (bitwise) for the
        snapshot and re-fantasized right after — a restored pool holds the
        exact never-fantasized buffers, and the crash-orphaned pending
        asks are re-served by the gateway, never replayed from disk."""
        if not self.cfg.ckpt_dir:
            return None
        active = [s for s in range(self.n_studies) if self._fantasies[s]]
        for sid in active:
            if self.engine.tier(sid):
                self.engine.nb_rollback(sid)
            else:
                self.engine.truncate_slot(
                    sid, self.engine.n(sid) - len(self._fantasies[sid]))
            self.fantasy_rollbacks += 1
        self._done_at_last_ckpt = self._n_done
        meta = {
            "n_studies": self.n_studies,
            "studies": json.dumps([
                {"study_id": h.study_id, "name": h.name,
                 "next_id": h.next_id, "trials": self.history(h.study_id),
                 # per-study PRNG streams ride the snapshot so a restored
                 # pool never re-draws batches it already drew pre-crash
                 "key": self._keys[h.study_id].tolist(),
                 "rng_state": h.rng.bit_generator.state}
                for h in self.studies]),
            # Escalated-tier state (DESIGN.md §15) rides the snapshot as
            # metadata: the store shape-validates the main tree against
            # the fixed GP layout, and NB ledgers have per-study caps.
            "escalated": json.dumps({
                str(s): nb_mod.nb_to_json(self.engine.nb_state(s))
                for s in range(self.n_studies) if self.engine.tier(s)}),
            "cost_rows": json.dumps({
                str(s): self.engine.cost_row(s).tolist()
                for s in range(self.n_studies)}),
        }
        if extra:
            meta.update(extra)
        path = ckpt_mod.save(self.cfg.ckpt_dir, self._n_done,
                             dataclasses.asdict(self.engine.state),
                             metadata=meta)
        self._refantasize_pending(active)
        return path

    def restore(self) -> bool:
        if not self.cfg.ckpt_dir:
            return False
        out = ckpt_mod.restore_latest(self.cfg.ckpt_dir,
                                      dataclasses.asdict(self.engine.state))
        if out is None:
            return False
        step, tree, meta = out
        self.last_restore_meta = meta
        if int(meta.get("n_studies", -1)) != self.n_studies:
            raise ValueError(
                f"checkpoint holds {meta.get('n_studies')} studies, "
                f"pool has {self.n_studies}")
        tree["params"] = KernelParams(**tree["params"])
        # Re-place on the configured device mesh: a restored pool resumes
        # with the same sharding layout the closures were built for.
        self.engine.state = self.engine.place(gp_mod.LazyGPState(**tree))
        # Snapshots hold only real state; pending q-asks died with the
        # crash and are re-served upstream, so no fantasy rows survive.
        self._fantasies = [[] for _ in range(self.n_studies)]
        esc = json.loads(meta.get("escalated", "{}"))
        rows = json.loads(meta.get("cost_rows", "{}"))
        for s in range(self.n_studies):
            self.engine.clear_nb_slot(s)
            if str(s) in rows:
                self.engine.set_cost_row(s, rows[str(s)])
            if str(s) in esc:
                self.engine.load_nb_slot(s, nb_mod.nb_from_json(esc[str(s)]))
        for rec in json.loads(meta["studies"]):
            h = self.studies[rec["study_id"]]
            h.name = rec["name"]
            h.next_id = int(rec["next_id"])
            if "key" in rec:
                self._keys[h.study_id] = np.asarray(rec["key"], np.uint32)
            if "rng_state" in rec:
                h.rng = np.random.default_rng()
                h.rng.bit_generator.state = rec["rng_state"]
            h.trials = [_trial_from_dict(t) for t in rec["trials"]]
        # The step counter resumes from the snapshot's own step, NOT from
        # total_done(): under a gateway, absorbed trials of evicted studies
        # live in per-study partial snapshots rather than any resident
        # ledger, so total_done() under-counts — a later checkpoint would
        # then be written at a LOWER step than the one just restored and be
        # shadowed by it forever (restore_latest picks the max step).
        self._n_done = int(step)
        self._done_at_last_ckpt = self._n_done
        return True
