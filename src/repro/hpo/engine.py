"""The batched GP suggest/absorb engine shared by every HPO orchestrator.

`StudyEngine` owns ONE stacked `LazyGPState` with a leading study axis
(DESIGN.md §7) and the jitted closures that advance it.  It is the single
suggest/absorb compute path: `TrialScheduler` drives it with S = 1 (the
degenerate case) and `StudyPool` multiplexes S concurrent studies over the
same closures — there is no separate single-study math anywhere above the
policy layer.

Dispatch shapes (all jitted once per configuration):

  * `suggest_all`    — vmapped acquisition over every study: one program
    advances S EI optimizations at once (the multi-tenant hot path).
  * `suggest_at`     — dynamic-index one study out of the stack, run the
    single-study acquisition (used for routed, per-study requests; `i` is
    traced, so any study id hits the same compilation).
  * `append_at`      — completion-order absorb routed to the owning study:
    extract study i, fused O(n_max^2) lazy append, scatter back.
  * `append_masked`  — one vmapped dispatch absorbing at most one new
    observation per study (flagged), for draining a completion queue in
    rounds instead of S sequential dispatches.
  * `advance_all`    — the fused serving round: masked absorb of last
    round's completions + batched suggest from the updated posteriors in
    ONE jitted program (state buffers donated, so the stacked factors are
    updated in place instead of copied every round).
  * `refit_at`       — lag-event hyper-parameter refit + refactor of a
    single study (rare, O(G n^3); per-study lag counters decide when).

**Device mesh** (DESIGN.md §8): with `cfg.mesh` set ("auto" or "SxR"),
the stacked state is placed on a (study x restart) `jax.sharding.Mesh`
(`repro.hpo.mesh`) and the batched closures (`suggest_all`,
`append_masked`, `advance_all`) become `shard_map` programs — studies
split across devices, restarts split within a study when shards remain.
`mesh="none"` (default) is the degenerate unsharded case of the same
closures; the routed single-study paths (`suggest_at`/`append_at`/
`refit_at`) stay plain jit and read the sharded state through GSPMD.

**Mixed spaces** (DESIGN.md §10): when any study's space carries discrete
dims (or `cfg.mixed` forces it), every closure additionally threads the
stacked per-study `TypeDescriptor` — array DATA, vmapped/sharded along the
study axis with the state — and builds the mixed Matérn x categorical
kernel per study inside the vmap, so stacked studies with *different*
type layouts advance in one program and a gateway slot swap to a new
layout is a descriptor row write (`set_desc`), never a re-trace.
All-continuous engines build the exact pre-§10 closures.

Host-side per-study telemetry: `n` and `since_refit` are mirrored in host
numpy arrays (they evolve deterministically with the appends the engine
itself dispatches), so capacity guards and the lag policy never sync the
device state; `clamp_count` is data-dependent and reads the device
(`clamp_counts()` fetches all studies in one transfer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import acquisition as acq_mod
from repro.core import descriptor as desc_mod
from repro.core import gp as gp_mod
from repro.core import neural_basis as nb_mod
from repro.core.kernels import KERNELS, make_mixed_kernel
from repro.hpo import mesh as mesh_mod
from repro.hpo.telemetry import span

Array = jax.Array


def _index_state(state: gp_mod.LazyGPState, i: Array) -> gp_mod.LazyGPState:
    """Single-study view at a *traced* index (dynamic gather per leaf)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), state)


def _write_state(state: gp_mod.LazyGPState, i: Array,
                 sub: gp_mod.LazyGPState) -> gp_mod.LazyGPState:
    """Scatter a single-study state back into the stack at a traced index."""
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, i, axis=0),
        state, sub)


class StudyEngine:
    """Stacked lazy-GP state + the jitted batched suggest/absorb closures.

    `cfg` is duck-typed (SchedulerConfig works): needs n_max, kernel, lag,
    rho0, noise2, implementation, acq; optionally mesh (default "none").
    """

    def __init__(self, dim: int, cfg, n_studies: int,
                 descs: "list[desc_mod.TypeDescriptor] | None" = None):
        if n_studies < 1:
            raise ValueError(f"n_studies must be >= 1, got {n_studies}")
        self.cfg = cfg
        self.n_studies = n_studies
        # Mixed-space mode (DESIGN.md §10): enabled when any study's space
        # has discrete dims, or forced by cfg.mixed so a gateway built on
        # an all-continuous template can still admit discrete tenants
        # later (the closures are traced once, at construction).
        self.mixed = bool(getattr(cfg, "mixed", False)) or (
            descs is not None and any(d.has_discrete for d in descs))
        if self.mixed and cfg.kernel != "matern52":
            raise ValueError(
                "mixed spaces require kernel='matern52', got "
                f"{cfg.kernel!r}")
        self.kernel = KERNELS[cfg.kernel]
        self.gp_cfg = gp_mod.GPConfig(
            n_max=cfg.n_max, dim=dim, kernel=cfg.kernel, lag=cfg.lag,
            noise2=cfg.noise2, rho0=cfg.rho0,
            implementation=cfg.implementation)
        self.mesh = mesh_mod.build(getattr(cfg, "mesh", "none"),
                                   n_studies, cfg.acq.restarts)
        self.state = self.place(gp_mod.init_pool_state(self.gp_cfg,
                                                       n_studies))
        # Stacked per-study type descriptor: DATA, not a closure constant —
        # a gateway slot swap (new tenant, different layout) is an array
        # row update, never a re-trace.  None in the all-continuous case,
        # where the closures below collapse to the exact pre-mixed trace.
        if self.mixed:
            if descs is None:
                descs = [desc_mod.all_continuous(dim)] * n_studies
            if len(descs) != n_studies:
                raise ValueError(
                    f"got {len(descs)} descriptors for {n_studies} studies")
            self.desc = self.place(desc_mod.stack_descriptors(list(descs)))
        else:
            self.desc = None
        self._lo = jnp.zeros((dim,))
        self._hi = jnp.ones((dim,))
        # The substrate knob is a Python constant inside the jitted closures:
        # one compilation per configured implementation.  Likewise the mesh:
        # the shard_map wrapping happens at trace time, once per top_t.
        impl = cfg.implementation
        mixed = self.mixed
        hpo_mesh = self.mesh
        r_shards = hpo_mesh.restart_shards if hpo_mesh else 1
        r_axis = mesh_mod.RESTART_AXIS if r_shards > 1 else None

        def kern_for(dsc):
            # Per-study kernel: inside the vmapped closures `dsc` is one
            # study's (d,) descriptor row (traced), so stacked studies
            # with different type layouts share one program.
            if not mixed:
                return self.kernel
            return make_mixed_kernel(dsc.cont_mask, dsc.cat_mask)

        def suggest_one(st, dsc, key, top_t, sharded):
            return acq_mod.optimize_acquisition(
                st, kern_for(dsc), self._lo, self._hi, key, cfg.acq, top_t,
                implementation=impl,
                restart_axis=r_axis if sharded else None,
                restart_shards=r_shards if sharded else 1,
                desc=dsc if mixed else None)

        def append_one(st, dsc, x, y):
            return gp_mod.append(st, kern_for(dsc), x, y,
                                 implementation=impl)

        def masked_append_one(st, dsc, x, y, flag):
            new = append_one(st, dsc, x, y)
            return jax.tree.map(lambda o, n_: jnp.where(flag, n_, o), st, new)

        def advance_one(st, dsc, x, y, flag, key, top_t, sharded):
            # Fused serving round: masked absorb, then suggest from the
            # updated posterior — one program residency for both.
            st = masked_append_one(st, dsc, x, y, flag)
            units, vals = suggest_one(st, dsc, key, top_t, sharded)
            return st, units, vals

        def refit_one(st, dsc):
            kern = kern_for(dsc)
            params = gp_mod.refit_params(st, kern, implementation=impl)
            return gp_mod.refactor(st, kern, params, implementation=impl)

        def reanchor_one(st, dsc):
            # Fully-lazy drift guard: rebuild factor + maintained inverse
            # from the Gram under the CURRENT params (no grid refit).
            return gp_mod.refactor(st, kern_for(dsc), implementation=impl)

        # Fantasy protocol (DESIGN.md §12): liar policy is a Python
        # constant inside the jitted q-ask closures (one compilation per
        # configured liar, exactly like the substrate knob).
        fantasy_liar = getattr(cfg, "fantasy", gp_mod.FantasyConfig()).liar

        def ask_q_one(st, dsc, key, q):
            return acq_mod.suggest_q(
                st, kern_for(dsc), self._lo, self._hi, key, cfg.acq, q,
                liar=fantasy_liar, implementation=impl,
                desc=dsc if mixed else None)

        def fantasize_one(st, dsc, xs):
            return gp_mod.fantasize(st, kern_for(dsc), xs, fantasy_liar,
                                    implementation=impl)

        # In mixed mode every jitted closure takes the stacked descriptor
        # as a runtime argument right after the state (vmapped/sharded
        # along the study axis with it); otherwise the argument is absent
        # and the traces are identical to the all-continuous stack.
        if hpo_mesh is None:
            if mixed:
                self._suggest_all = jax.jit(
                    lambda state, dsc, keys, *, top_t: jax.vmap(
                        lambda st, dc, k: suggest_one(
                            st, dc, k, top_t, False))(state, dsc, keys),
                    static_argnames=("top_t",))
                self._append_masked = jax.jit(jax.vmap(masked_append_one))
                self._advance_all = jax.jit(
                    lambda state, dsc, xs, ys, flags, keys, *, top_t:
                    jax.vmap(
                        lambda st, dc, x, y, f, k: advance_one(
                            st, dc, x, y, f, k, top_t, False))(
                        state, dsc, xs, ys, flags, keys),
                    static_argnames=("top_t",), donate_argnums=(0,))
            else:
                self._suggest_all = jax.jit(
                    lambda state, keys, *, top_t: jax.vmap(
                        lambda st, k: suggest_one(
                            st, None, k, top_t, False))(state, keys),
                    static_argnames=("top_t",))
                self._append_masked = jax.jit(jax.vmap(
                    lambda st, x, y, f: masked_append_one(st, None, x, y,
                                                          f)))
                self._advance_all = jax.jit(
                    lambda state, xs, ys, flags, keys, *, top_t: jax.vmap(
                        lambda st, x, y, f, k: advance_one(
                            st, None, x, y, f, k, top_t, False))(
                        state, xs, ys, flags, keys),
                    static_argnames=("top_t",), donate_argnums=(0,))
        else:
            # Sharded variants: studies split over the mesh's study axis,
            # restarts split over the restart axis inside each suggest.
            if mixed:
                self._suggest_all = jax.jit(
                    lambda state, dsc, keys, *, top_t: hpo_mesh.shard(
                        lambda st, dc, ks: jax.vmap(
                            lambda s, d_, k: suggest_one(
                                s, d_, k, top_t, True))(st, dc, ks),
                        n_in=3)(state, dsc, keys),
                    static_argnames=("top_t",))
                self._append_masked = jax.jit(hpo_mesh.shard(
                    lambda st, dc, x, y, f: jax.vmap(masked_append_one)(
                        st, dc, x, y, f), n_in=5))
                self._advance_all = jax.jit(
                    lambda state, dsc, xs, ys, flags, keys, *, top_t:
                    hpo_mesh.shard(
                        lambda st, dc, x, y, f, k: jax.vmap(
                            lambda s, d_, x_, y_, f_, k_: advance_one(
                                s, d_, x_, y_, f_, k_, top_t, True))(
                            st, dc, x, y, f, k),
                        n_in=6)(state, dsc, xs, ys, flags, keys),
                    static_argnames=("top_t",), donate_argnums=(0,))
            else:
                self._suggest_all = jax.jit(
                    lambda state, keys, *, top_t: hpo_mesh.shard(
                        lambda st, ks: jax.vmap(
                            lambda s, k: suggest_one(
                                s, None, k, top_t, True))(st, ks),
                        n_in=2)(state, keys),
                    static_argnames=("top_t",))
                self._append_masked = jax.jit(hpo_mesh.shard(
                    lambda st, x, y, f: jax.vmap(
                        lambda s, x_, y_, f_: masked_append_one(
                            s, None, x_, y_, f_))(st, x, y, f),
                    n_in=4))
                self._advance_all = jax.jit(
                    lambda state, xs, ys, flags, keys, *, top_t:
                    hpo_mesh.shard(
                        lambda st, x, y, f, k: jax.vmap(
                            lambda s, x_, y_, f_, k_: advance_one(
                                s, None, x_, y_, f_, k_, top_t, True))(
                            st, x, y, f, k),
                        n_in=5)(state, xs, ys, flags, keys),
                    static_argnames=("top_t",), donate_argnums=(0,))
        # Routed single-study paths: plain jit; with a mesh active the
        # sharded state flows through GSPMD's auto-partitioner (these are
        # the rare paths — lag events and per-study routing), and the
        # indexed study's compute runs replicated on every device (a
        # Pallas kernel cannot be auto-partitioned).  The mixed variants
        # index the stacked descriptor at the same traced index.
        def routed(fn):
            return fn if hpo_mesh is None else hpo_mesh.replicated(fn)

        suggest_one_r = lambda top_t: routed(
            lambda st, dsc, key: suggest_one(st, dsc, key, top_t, False))
        append_one_r = routed(append_one)
        fantasize_one_r = routed(fantasize_one)
        refit_one_r = routed(refit_one)
        reanchor_one_r = routed(reanchor_one)

        def ask_q_route(state, dsc, i, key, q):
            # q-suggestion fast path, routed to one slot: extract, run the
            # scan-of-(suggest + fantasize) program, scatter the fantasized
            # state back.  q is static — one compilation per distinct q.
            xs, vals, sub = routed(
                lambda st, d_, k: ask_q_one(st, d_, k, q))(
                _index_state(state, i), dsc, key)
            return xs, vals, _write_state(state, i, sub)

        if mixed:
            self._suggest_at = jax.jit(
                lambda state, dsc, i, key, *, top_t: suggest_one_r(top_t)(
                    _index_state(state, i),
                    desc_mod.index_descriptor(dsc, i), key),
                static_argnames=("top_t",))
            self._append_at = jax.jit(
                lambda state, dsc, i, x, y: _write_state(
                    state, i, append_one_r(
                        _index_state(state, i),
                        desc_mod.index_descriptor(dsc, i), x, y)))
            self._ask_q_at = jax.jit(
                lambda state, dsc, i, key, *, q: ask_q_route(
                    state, desc_mod.index_descriptor(dsc, i), i, key, q),
                static_argnames=("q",))
            self._refantasize_at = jax.jit(
                lambda state, dsc, i, xs: _write_state(
                    state, i, fantasize_one_r(
                        _index_state(state, i),
                        desc_mod.index_descriptor(dsc, i), xs)))
            self._refit_at = jax.jit(
                lambda state, dsc, i: _write_state(
                    state, i, refit_one_r(
                        _index_state(state, i),
                        desc_mod.index_descriptor(dsc, i))))
            self._reanchor_at = jax.jit(
                lambda state, dsc, i: _write_state(
                    state, i, reanchor_one_r(
                        _index_state(state, i),
                        desc_mod.index_descriptor(dsc, i))))
        else:
            self._suggest_at = jax.jit(
                lambda state, i, key, *, top_t: suggest_one_r(top_t)(
                    _index_state(state, i), None, key),
                static_argnames=("top_t",))
            self._append_at = jax.jit(
                lambda state, i, x, y: _write_state(
                    state, i, append_one_r(_index_state(state, i), None,
                                           x, y)))
            self._ask_q_at = jax.jit(
                lambda state, i, key, *, q: ask_q_route(
                    state, None, i, key, q),
                static_argnames=("q",))
            self._refantasize_at = jax.jit(
                lambda state, i, xs: _write_state(
                    state, i, fantasize_one_r(_index_state(state, i), None,
                                              xs)))
            self._refit_at = jax.jit(
                lambda state, i: _write_state(
                    state, i, refit_one_r(_index_state(state, i), None)))
            self._reanchor_at = jax.jit(
                lambda state, i: _write_state(
                    state, i, reanchor_one_r(_index_state(state, i),
                                             None)))
        # Fantasy rollback: re-pad every row >= n_real of one slot (kernel-
        # free, descriptor-free — identical trace in mixed mode).
        self._truncate_at = jax.jit(
            lambda state, i, n_real: _write_state(
                state, i, gp_mod.truncate(_index_state(state, i), n_real)))
        # Slot-level state swap (the gateway's evict/restore hook): scatter a
        # single-study state into the stack at a traced index — any slot hits
        # the same compilation, so serving-time restores never re-trace.
        self._load_at = jax.jit(_write_state)
        # -- saturation escalation tier (DESIGN.md §15) ----------------------
        # Per-slot tier tag: 0 = lazy GP (the stacked state above), 1 =
        # neural basis.  Like the descriptors, the tag is per-slot DATA —
        # heterogeneous tenants share one program per tier (the nb_* jitted
        # programs are cached by (cap, d) shape + the static NeuralConfig /
        # AcqConfig, never re-traced per slot).  Escalated slots keep their
        # frozen GP lane in the stack (it rides the batched programs as
        # dead weight and is exported untouched); their live model is the
        # NeuralBasisState held here.
        self.neural = getattr(cfg, "neural", None) or nb_mod.NeuralConfig()
        self._fantasy_liar = fantasy_liar
        self._tier = np.zeros((n_studies,), np.int8)
        self._nb: dict[int, nb_mod.NeuralBasisState] = {}
        # Pre-fantasy snapshots: the NB tier's rank-1 factor updates are
        # not bitwise-reversible, so fantasy rollback is a state-snapshot
        # restore (O(m^2) floats + the ledger views — cheap, exact).
        self._nb_shadow: dict[int, nb_mod.NeuralBasisState] = {}
        self._nb_n: dict[int, int] = {}   # host mirror incl. fantasy rows
        # Per-row observation costs (tell `cost=`, default 1.0) for the GP
        # tier — the training set of the promotion-time log-cost head.
        self._cost_host = np.ones((n_studies, cfg.n_max), np.float32)

    def place(self, state: gp_mod.LazyGPState) -> gp_mod.LazyGPState:
        """Put a stacked state onto the configured mesh (identity if none)."""
        return self.mesh.place(state) if self.mesh else state

    # -- state + host-side counter mirrors ----------------------------------
    # `n` and `since_refit` evolve deterministically (+1 per append, refits
    # reset since_refit), so the engine mirrors them in host numpy arrays:
    # the hot paths (capacity guards, lag policy, the pool's seed-vs-EI
    # routing) never sync the device state — on a sharded mesh a single
    # `int(state.n[s])` read is a cross-device gather, and S of them per
    # round would dominate the round itself.  Assigning `engine.state`
    # re-syncs the mirrors from the device (restore, tests, prefill).

    @property
    def state(self) -> gp_mod.LazyGPState:
        return self._state

    @state.setter
    def state(self, st: gp_mod.LazyGPState) -> None:
        self._state = st
        self._n_host = np.asarray(st.n).copy()
        self._sr_host = np.asarray(st.since_refit).copy()

    # -- per-study telemetry (host-side) ------------------------------------
    def n(self, study: int) -> int:
        return int(self._n_host[study])

    def since_refit(self, study: int) -> int:
        return int(self._sr_host[study])

    def clamp_count(self, study: int) -> int:
        return int(self.state.clamp_count[study])

    def clamp_counts(self) -> np.ndarray:
        """All studies' conditioning-floor counters in one transfer."""
        return np.asarray(self.state.clamp_count)

    def sync(self) -> None:
        """Block until every dispatched program has committed to the state.

        The pipelined serving layer (DESIGN.md §13) leaves fused rounds in
        flight while the host stages the next tick; timing code and
        migration/export paths call this to pin a quiescent point.
        """
        jax.block_until_ready(self._state)

    def study_state(self, study: int) -> gp_mod.LazyGPState:
        """Unstacked single-study view (static index)."""
        return gp_mod.unstack_state(self.state, study)

    # -- slot-level state swap (gateway evict/restore, DESIGN.md §9) --------
    def load_slot(self, slot: int, sub: gp_mod.LazyGPState) -> None:
        """Swap a single-study state INTO stack slot `slot`.

        One jitted scatter at a traced index (no re-trace per slot); the
        host mirrors are patched for that slot only, so loading a study
        never syncs the other S-1 lanes off the device.  The write is
        elementwise, so the restored lane is bitwise-identical to the
        exported one — the evict/restore-exactness contract.
        """
        self._state = self.place(self._load_at(
            self.state, jnp.asarray(slot, jnp.int32), sub))
        self._n_host[slot] = int(sub.n)
        self._sr_host[slot] = int(sub.since_refit)

    def reset_slot(self, slot: int) -> None:
        """Blank a slot for a new tenant (fresh empty single-study state)."""
        self.load_slot(slot, gp_mod.init_state(self.gp_cfg))
        self.clear_nb_slot(slot)

    def set_desc(self, slot: int, desc: desc_mod.TypeDescriptor) -> None:
        """Install a (possibly different) type layout for one slot.

        A row write into the stacked descriptor DATA — the closures take
        the descriptor as a runtime argument, so a tenant swap with a new
        layout never re-traces.  No-op outside mixed mode (where every
        slot is all-continuous by construction)."""
        if self.desc is None:
            if desc.has_discrete:
                raise ValueError(
                    "engine was built without mixed-space support; "
                    "construct it with a discrete space or cfg.mixed=True")
            return
        updated = jax.tree.map(lambda a, v: a.at[slot].set(v),
                               self.desc, desc)
        self.desc = self.place(updated)

    # -- suggest ------------------------------------------------------------
    def _desc_args(self) -> tuple:
        """The stacked descriptor, when the closures take it (mixed mode)."""
        return (self.desc,) if self.mixed else ()

    def suggest(self, study: int, key: Array,
                top_t: int = 1) -> tuple[Array, Array]:
        """Top-t EI local maxima for one study: ((top_t, d), (top_t,))."""
        return self._suggest_at(self.state, *self._desc_args(),
                                jnp.asarray(study, jnp.int32),
                                key, top_t=top_t)

    def suggest_all(self, keys: Array, top_t: int = 1) -> tuple[Array, Array]:
        """Batched suggestion for every study: ((S, top_t, d), (S, top_t))."""
        return self._suggest_all(self.state, *self._desc_args(), keys,
                                 top_t=top_t)

    # -- absorb -------------------------------------------------------------
    def absorb(self, study: int, x, y, cost: float = 1.0) -> None:
        """Routed completion-order absorb (+ per-study lag policy)."""
        gp_mod.ensure_capacity(self.n(study), self.cfg.n_max)
        self._cost_host[study, self.n(study)] = cost
        self._state = self._append_at(
            self.state, *self._desc_args(), jnp.asarray(study, jnp.int32),
            jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.float32))
        self._n_host[study] += 1
        self._sr_host[study] += 1
        self._refit_flagged([study])

    def absorb_round(self, flags, xs, ys, costs=None) -> None:
        """Masked batched absorb: at most one new observation per study.

        `flags (S,)` bool selects which studies actually append; `xs (S, d)`
        / `ys (S,)` carry the observations (ignored where flag is False).
        One dispatch replaces up to S routed appends.  `costs (S,)`
        (optional) records each flagged observation's tell cost.
        """
        flags = np.asarray(flags, bool)
        flagged = np.flatnonzero(flags)
        for s in flagged:
            gp_mod.ensure_capacity(self.n(s), self.cfg.n_max)
        self._record_costs(flagged, costs)
        self._state = self._append_masked(
            self.state, *self._desc_args(),
            jnp.asarray(xs, jnp.float32),
            jnp.asarray(ys, jnp.float32),
            jnp.asarray(flags))
        self._n_host[flagged] += 1
        self._sr_host[flagged] += 1
        self._refit_flagged(flagged)

    def _record_costs(self, flagged, costs) -> None:
        if costs is None:
            costs = np.ones((self.n_studies,), np.float32)
        costs = np.asarray(costs, np.float32)
        for s in flagged:
            self._cost_host[s, self.n(s)] = costs[s]

    # -- fused serving round ------------------------------------------------
    def advance(self, flags, xs, ys, keys,
                top_t: int = 1, costs=None) -> tuple[Array, Array]:
        """Masked absorb + batched suggest in ONE jitted dispatch.

        Absorbs at most one flagged observation per study (exactly like
        `absorb_round`), then suggests top-t points for EVERY study from
        the updated posteriors, returning `((S, top_t, d), (S, top_t))`.
        This is the serving-loop hot path: one program per round instead of
        an absorb dispatch + a suggest dispatch, with the stacked state
        buffers donated (updated in place, not copied).  The dispatch is
        the `engine.advance` span.

        The previous `self.state` is consumed by donation — callers must
        not hold references to its buffers across this call.  Pipelined
        callers (DESIGN.md §13) may defer fetching the RETURNED arrays —
        those are fresh outputs, not donated — but any host read of
        `self.state` leaves (or a copy taken for later, like the pool's
        clamp vector) must be a new dispatch output, never a buffer that a
        subsequent `advance` will donate.
        """
        flags = np.asarray(flags, bool)
        flagged = np.flatnonzero(flags)
        for s in flagged:
            gp_mod.ensure_capacity(self.n(s), self.cfg.n_max)
        self._record_costs(flagged, costs)
        with span("engine.advance"):
            self._state, units, vals = self._advance_all(
                self.state, *self._desc_args(),
                jnp.asarray(xs, jnp.float32),
                jnp.asarray(ys, jnp.float32),
                jnp.asarray(flags), keys, top_t=top_t)
        self._n_host[flagged] += 1
        self._sr_host[flagged] += 1
        self._refit_flagged(flagged)
        return units, vals

    # -- fantasy protocol (q-suggestion serving, DESIGN.md §12) -------------
    # Fantasy rows live in the same stacked buffers as real observations —
    # the host `n` mirror therefore tracks the *fantasized* count; callers
    # (StudyPool) own the real-ledger count and drive the rollback.

    def ask_q(self, study: int, key: Array, q: int) -> tuple[Array, Array]:
        """q-suggestion fast path: ((q, d) points, (q,) acq values).

        ONE jitted dispatch runs q rounds of suggest-then-fantasize against
        slot `study` (DESIGN.md §12) and leaves the slot *fantasized* (its
        device/host n grows by q).  The caller must roll the fantasy rows
        back (`truncate_slot`) before any real append lands.
        """
        gp_mod.ensure_capacity(self.n(study), self.cfg.n_max, q)
        xs, vals, self._state = self._ask_q_at(
            self.state, *self._desc_args(), jnp.asarray(study, jnp.int32),
            key, q=q)
        self._n_host[study] += q
        return xs, vals

    def truncate_slot(self, study: int, n_real: int) -> None:
        """Roll slot `study` back to its first `n_real` (real) rows.

        Bitwise-exact re-padding (`gp.truncate`): the factor/inverse rows
        being dropped are replaced by the identity rows they overwrote, so
        the slot is restored bit for bit to its pre-fantasy buffers.
        """
        self._state = self._truncate_at(
            self.state, jnp.asarray(study, jnp.int32),
            jnp.asarray(n_real, jnp.int32))
        self._n_host[study] = int(n_real)

    def refantasize(self, study: int, xs) -> None:
        """Re-append pending fantasy points in ONE `lazy_append_rows` dispatch.

        The tell-time replay: after `truncate_slot` + the real absorb, the
        still-pending fantasy points (q, d) are re-fantasized against the
        updated posterior — fresher liar values, one batched dispatch.
        """
        xs = jnp.asarray(xs, jnp.float32)
        gp_mod.ensure_capacity(self.n(study), self.cfg.n_max, xs.shape[0])
        self._state = self._refantasize_at(
            self.state, *self._desc_args(), jnp.asarray(study, jnp.int32),
            xs)
        self._n_host[study] += xs.shape[0]

    # -- neural-basis tier (saturation escalation, DESIGN.md §15) -----------
    def tier(self, study: int) -> int:
        """0 = lazy GP, 1 = neural basis (escalated)."""
        return int(self._tier[study])

    def cost_row(self, study: int) -> np.ndarray:
        """The GP tier's per-row tell costs (rides eviction snapshots so a
        near-saturation study promoted after a restore still trains its
        cost head on the full ledger)."""
        return self._cost_host[study].copy()

    def set_cost_row(self, study: int, costs) -> None:
        self._cost_host[study] = np.asarray(costs, np.float32)

    def promote_slot(self, slot: int, key: Array) -> None:
        """Escalate a saturated GP slot to the neural-basis tier.

        The NB model trains on the slot's FULL active ledger (the exact
        rows the GP absorbed, plus their tell costs) — the caller must
        have rolled back any fantasy rows first.  The GP lane stays
        frozen in the stack: exports keep round-tripping it bitwise, and
        its buffers are never touched again.
        """
        if self._tier[slot]:
            raise RuntimeError(f"slot {slot} is already escalated")
        n0 = self.n(slot)
        if n0 < 1:
            raise RuntimeError("cannot promote an empty slot")
        st = self.study_state(slot)
        xs = np.asarray(st.x_buf)[:n0]
        ys = np.asarray(st.y_buf)[:n0]
        logcs = np.log(np.maximum(self._cost_host[slot, :n0], 1e-12))
        self._nb[slot] = nb_mod.nb_from_data(xs, ys, logcs, key,
                                             self.neural)
        self._tier[slot] = 1
        self._nb_n[slot] = n0
        self._nb_shadow.pop(slot, None)

    def clear_nb_slot(self, slot: int) -> None:
        """Drop the escalated model (new tenant / detach): back to tier 0."""
        self._tier[slot] = 0
        self._nb.pop(slot, None)
        self._nb_shadow.pop(slot, None)
        self._nb_n.pop(slot, None)
        self._cost_host[slot] = 1.0

    def nb_state(self, slot: int) -> nb_mod.NeuralBasisState:
        return self._nb[slot]

    def load_nb_slot(self, slot: int, state: nb_mod.NeuralBasisState
                     ) -> None:
        """Install a restored/imported NB state (tier tag follows)."""
        self._tier[slot] = 1
        self._nb[slot] = state
        self._nb_n[slot] = int(state.n)
        self._nb_shadow.pop(slot, None)

    def nb_n(self, slot: int) -> int:
        """Fantasized row count of an escalated slot (host mirror)."""
        return self._nb_n[slot]

    def _nb_room(self, slot: int, incoming: int
                 ) -> nb_mod.NeuralBasisState:
        st = self._nb[slot]
        while self._nb_n[slot] + incoming > st.cap:
            st = nb_mod.nb_grow(st, self.neural)
        return st

    def nb_absorb(self, slot: int, x, y, cost: float = 1.0) -> None:
        """Escalated absorb: rank-1 append (ledger grows, never full) +
        the MLP refit cadence (`NeuralConfig.refit_every`, the tier's
        `lag`).  Must only run with no fantasy rows active (the pool rolls
        back first — same protocol as the GP tier)."""
        st = self._nb_room(slot, 1)
        st = nb_mod.nb_append(
            st, jnp.asarray(x, jnp.float32), jnp.float32(y),
            jnp.float32(np.log(max(float(cost), 1e-12))),
            ncfg=self.neural)
        if int(st.since_refit) >= self.neural.refit_every:
            st = nb_mod.nb_refit(st, ncfg=self.neural)
        self._nb[slot] = st
        self._nb_n[slot] += 1

    def _nb_desc(self, slot: int):
        if not self.mixed:
            return None
        return desc_mod.index_descriptor(self.desc,
                                         jnp.asarray(slot, jnp.int32))

    def nb_suggest(self, slot: int, key: Array,
                   top_t: int = 1) -> tuple[Array, Array]:
        """Escalated suggest: acquisition ascent against the O(m^2)
        neural-basis posterior — flat in n."""
        return nb_mod.nb_suggest(self._nb[slot], key, self._nb_desc(slot),
                                 acq=self.cfg.acq, top_t=top_t)

    def nb_ask_q(self, slot: int, key: Array, q: int
                 ) -> tuple[Array, Array]:
        """Escalated q-suggestion: snapshot the pre-fantasy state, then the
        qEI suggest-and-fantasize scan.  Rollback = `nb_rollback`."""
        if slot not in self._nb_shadow:
            self._nb_shadow[slot] = self._nb[slot]
        st = self._nb_room(slot, q)
        xs, vals, st = nb_mod.nb_ask_q(st, key, self._nb_desc(slot),
                                       ncfg=self.neural, acq=self.cfg.acq,
                                       q=q, liar=self._fantasy_liar)
        self._nb[slot] = st
        self._nb_n[slot] += q
        return xs, vals

    def nb_rollback(self, slot: int) -> None:
        """Drop every fantasy row of an escalated slot: restore the
        pre-fantasy snapshot — bitwise-exact by construction."""
        sh = self._nb_shadow.pop(slot, None)
        if sh is not None:
            self._nb[slot] = sh
            self._nb_n[slot] = int(sh.n)

    def nb_refantasize(self, slot: int, xs) -> None:
        """Re-append still-pending fantasy points against the updated
        posterior (tell-time replay, same protocol as `refantasize`)."""
        xs = jnp.asarray(xs, jnp.float32)
        self._nb_shadow[slot] = self._nb[slot]
        st = self._nb_room(slot, xs.shape[0])
        st = nb_mod.nb_fantasize(st, xs, ncfg=self.neural,
                                 liar=self._fantasy_liar)
        self._nb[slot] = st
        self._nb_n[slot] += int(xs.shape[0])

    def _refit_flagged(self, flagged) -> None:
        """Apply the per-study lag policy after an absorb (host mirrors).

        lag > 0: full hyper-parameter refit + refactor every `lag` appends.
        lag <= 0 (the paper's fully-lazy mode): no param refit, but every
        `inv_refresh` appends the factor and its maintained inverse are
        rebuilt from the Gram under the current params — re-anchoring the
        float32 drift the incremental bordered-inverse updates accumulate
        (DESIGN.md §4).  Both events are rare O(n_max^3) dispatches, each an
        `engine.reanchor` span carrying the study's n; the check itself
        reads only the host-side counter mirrors.
        """
        lag = self.cfg.lag
        inv_refresh = getattr(self.cfg, "inv_refresh", 0)
        if lag <= 0 and inv_refresh <= 0:
            return
        for s in flagged:
            if lag > 0:
                if self.since_refit(s) >= lag:
                    with span("engine.reanchor", n=self.n(s)):
                        self._state = self._refit_at(
                            self.state, *self._desc_args(),
                            jnp.asarray(s, jnp.int32))
                    self._sr_host[s] = 0
            elif self.since_refit(s) >= inv_refresh:
                with span("engine.reanchor", n=self.n(s)):
                    self._state = self._reanchor_at(
                        self.state, *self._desc_args(),
                        jnp.asarray(s, jnp.int32))
                self._sr_host[s] = 0
