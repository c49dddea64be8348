"""Lazy Gaussian process regression (the paper's surrogate model).

State machine per DESIGN.md §4: fixed-shape padded buffers hold the observed
points, observations, and the identity-padded Cholesky factor; `append` is the
paper's O(n^2) Alg. 3 step; `refit` is the lag-event full refactorization with
kernel hyper-parameter re-estimation via log-marginal-likelihood.

Everything here is shape-static and jit-able; the BO loop compiles once.
All linear algebra dispatches through the substrate (`repro.kernels.ops`) via
the `implementation` knob ("auto" | "pallas" | "xla" | "ref", DESIGN.md §5);
this module owns the padded-state policy only.

**Batched study axis** (DESIGN.md §7): every transition here is
rank-polymorphic.  A `LazyGPState` whose buffers carry a leading study axis
— `x_buf (S, n_max, d)`, `n (S,)` int32, params leaves `(S,)` — represents S
independent studies with *per-study* heterogeneous active counts, lag
counters, and clamp telemetry; `append`/`append_batch`/`posterior`/
`refactor`/`refit_params` detect the extra axis and vmap the single-study
path, so one jitted program advances all S posteriors at once.  A single
study is the S=1 degenerate case.  Build stacked states with
`init_pool_state`/`stack_states`; slice views with `unstack_state`.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from repro.core import cholesky as chol
from repro.core import descriptor as desc_mod
from repro.core.kernels import (KERNELS, KernelFn, KernelParams,
                                make_mixed_kernel)
from repro.kernels import ops

Array = jax.Array


class GPCapacityError(RuntimeError):
    """Base of the capacity-rejection taxonomy (kept as the catch-all for
    back-compat: every admission rejection still `isinstance`-matches it).

    Two subclasses carry the distinction a client needs to react correctly
    — `retryable` says whether waiting and retrying the SAME call can ever
    succeed:

      * `StudySaturatedError` — terminal: the study's lazy-GP slot is at
        `n_max` (pre-escalation).  Retrying never helps; the study must be
        promoted to the neural-basis tier (or its budget is spent).
      * `BackpressureError` — transient: queue depth / in-flight caps /
        slot contention.  Retry after the next tick or after results come
        back.

    The transport layer preserves the concrete type over the wire
    (repro.hpo.transport._WIRE_ERRORS) so remote clients see the same
    taxonomy as in-process ones.
    """

    retryable = False


class StudySaturatedError(GPCapacityError):
    """Terminal: an append/ask can never fit the study's fixed (n_max, …)
    buffers.  Without this guard the row write at index n == n_max would
    clamp and silently corrupt the last row of the factor."""

    retryable = False


class BackpressureError(GPCapacityError):
    """Transient admission rejection (queue full, in-flight cap, every slot
    busy): the same call can succeed after the next tick — retry."""

    retryable = True


def ensure_capacity(n: int, n_max: int, incoming: int = 1) -> None:
    """Host-side capacity guard: fail loudly *before* the buffer overflows."""
    if n + incoming > n_max:
        raise StudySaturatedError(
            f"GP buffer full: n={n} + {incoming} incoming observation(s) "
            f"exceeds n_max={n_max}; raise n_max (GPConfig/BOConfig/"
            f"SchedulerConfig) or stop absorbing")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LazyGPState:
    """Padded, fixed-shape GP state (see DESIGN.md §4).

    May carry a leading study axis (DESIGN.md §7): all buffer shapes below
    gain a leading S and the scalars become (S,) vectors.  `is_batched`
    distinguishes the two ranks.
    """

    x_buf: Array        # (n_max, d) observed points
    y_buf: Array        # (n_max,) observations
    l_buf: Array        # (n_max, n_max) identity-padded factor of K + noise I
    li_buf: Array       # (n_max, n_max) identity-padded inverse factor L^{-1},
    # maintained incrementally by the bordered-inverse append (DESIGN.md §4)
    # so every posterior/append is matmul-only (batchable, MXU-friendly)
    alpha: Array        # (n_max,) (K + noise I)^{-1} (y - mean), zero-padded
    n: Array            # () int32 active count
    since_refit: Array  # () int32 appends since last full refactor
    clamp_count: Array  # () int32 appends whose d^2 hit the conditioning floor
    params: KernelParams

    @property
    def is_batched(self) -> bool:
        return self.x_buf.ndim == 3

    @property
    def n_studies(self) -> int:
        return self.x_buf.shape[0] if self.is_batched else 1

    @property
    def n_max(self) -> int:
        return self.x_buf.shape[-2]

    @property
    def dim(self) -> int:
        return self.x_buf.shape[-1]


@dataclasses.dataclass(frozen=True)
class GPConfig:
    n_max: int = 1024
    dim: int = 5
    kernel: str = "matern52"
    lag: int = 0           # 0 = never refit (the fully lazy GP of the paper)
    noise2: float = 1e-6
    rho0: float = 0.25     # initial length scale on the unit box.  The paper
    # fixes rho = 1; on a normalized domain that over-smooths multimodal
    # targets, so the framework default is 0.25 (beyond-paper).  Paper-repro
    # benchmarks pass rho0 = 1.0 explicitly.
    implementation: str = "auto"   # linalg substrate (DESIGN.md §5)
    desc: desc_mod.TypeDescriptor | None = None  # mixed-space type
    # descriptor (DESIGN.md §10): when it carries discrete coordinates,
    # `kernel_fn` becomes the mixed Matérn x categorical kernel over the
    # encoded unit cube.  Travels from the typed SearchSpace through
    # BOConfig / StudyEngine exactly like the `implementation` knob.
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        ops.check_implementation(self.implementation)
        if self.desc is not None and self.desc.has_discrete \
                and self.kernel != "matern52":
            raise ValueError(
                f"mixed spaces require kernel='matern52' (the mixed kernel "
                f"is its Matérn x categorical product), got {self.kernel!r}")

    @property
    def kernel_fn(self) -> KernelFn:
        if self.desc is not None and self.desc.has_discrete:
            return make_mixed_kernel(self.desc.cont_mask, self.desc.cat_mask)
        return KERNELS[self.kernel]


def init_state(cfg: GPConfig, params: KernelParams | None = None) -> LazyGPState:
    params = params or KernelParams(sigma2=1.0, rho=cfg.rho0, noise2=cfg.noise2)
    return LazyGPState(
        x_buf=jnp.zeros((cfg.n_max, cfg.dim), cfg.dtype),
        y_buf=jnp.zeros((cfg.n_max,), cfg.dtype),
        l_buf=jnp.eye(cfg.n_max, dtype=cfg.dtype),
        li_buf=jnp.eye(cfg.n_max, dtype=cfg.dtype),
        alpha=jnp.zeros((cfg.n_max,), cfg.dtype),
        n=jnp.asarray(0, jnp.int32),
        since_refit=jnp.asarray(0, jnp.int32),
        clamp_count=jnp.asarray(0, jnp.int32),
        params=KernelParams(*[jnp.asarray(v, cfg.dtype)
                              for v in (params.sigma2, params.rho, params.noise2)]),
    )


# ---------------------------------------------------------------------------
# Batched study axis (DESIGN.md §7): stacked-state constructors and views.
# ---------------------------------------------------------------------------

def init_pool_state(cfg: GPConfig, n_studies: int,
                    params: KernelParams | None = None) -> LazyGPState:
    """Stacked state for `n_studies` independent studies (leading S axis).

    Every study starts empty with identical kernel params; per-study params
    diverge at lag events (`refit_params` on the stacked state returns
    `(S,)`-leaved params).
    """
    if n_studies < 1:
        raise ValueError(f"n_studies must be >= 1, got {n_studies}")
    st = init_state(cfg, params)
    return jax.tree.map(
        lambda a: jnp.repeat(a[None], n_studies, axis=0), st)


def stack_states(states: "list[LazyGPState]") -> LazyGPState:
    """Stack single-study states into one batched state (shared n_max/dim)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(state: LazyGPState, study: int) -> LazyGPState:
    """Single-study view of a stacked state (static index)."""
    return jax.tree.map(lambda a: a[study], state)


def _vmap_states(fn, state: LazyGPState, *batched_args):
    """Apply the single-study transition `fn` across the study axis."""
    return jax.vmap(fn)(state, *batched_args)


def _active_mask(state: LazyGPState) -> Array:
    return jnp.arange(state.n_max) < state.n


def _ymean(state: LazyGPState) -> Array:
    """Mean of the active observations (GP prior mean = running mean)."""
    m = _active_mask(state)
    cnt = jnp.maximum(state.n, 1)
    return jnp.sum(jnp.where(m, state.y_buf, 0.0)) / cnt


def _recompute_alpha(state: LazyGPState,
                     implementation: str = "auto") -> Array:
    """alpha = (K + noise I)^{-1} (y - mean) = L^{-T} (L^{-1} r).

    Two matvecs against the maintained inverse factor (padding-exact: rows
    >= n of `li_buf` are identity against a zero-padded residual).
    """
    del implementation  # matmul-only against the maintained inverse
    resid = jnp.where(_active_mask(state), state.y_buf - _ymean(state), 0.0)
    z = state.li_buf @ resid
    return jnp.where(_active_mask(state), z @ state.li_buf, 0.0)


def _centered_cov(kernel: KernelFn, x_buf: Array, x: Array, params,
                  implementation: str = "auto") -> Array:
    """k(x_buf, x) as an (n_max,) column, evaluated on the differences.

    Every kernel here is stationary, so k(x_i, x) = k(x_i - x, 0): the
    squared distances are then plain sums of squares.  The expanded
    |x|^2 + |y|^2 - 2 x.y^T cancels for near-duplicate points (EI clusters
    them) with a float32 error that, at a noise floor of 1e-5, perturbs the
    factor by more than the noise does (DESIGN.md §4).
    """
    origin = jnp.zeros((1, x.shape[-1]), x_buf.dtype)
    return ops.kernel_gram(kernel, x_buf - x[None, :], origin, params,
                           implementation=implementation)[:, 0]


def _cov_column(state: LazyGPState, kernel: KernelFn, x_new: Array,
                implementation: str = "auto") -> tuple[Array, Array]:
    """(p_pad, c): covariances of x_new against actives (padded) and itself."""
    p = _centered_cov(kernel, state.x_buf, x_new, state.params,
                      implementation)
    p_pad = jnp.where(_active_mask(state), p, 0.0)
    origin = jnp.zeros((1, x_new.shape[-1]), x_new.dtype)
    c = kernel(origin, origin, state.params)[0, 0] + state.params.noise2
    return p_pad, c


def _append_row_only(state: LazyGPState, kernel: KernelFn, x_new: Array,
                     y_new: Array, implementation: str) -> LazyGPState:
    """Row append with a *stale* alpha — the deferred-alpha batch path.

    Callers must refresh alpha (`_recompute_alpha`) before the state is used
    for posterior queries; `append_batch` does so once per batch.
    """
    p_pad, c = _cov_column(state, kernel, x_new, implementation)
    l_buf, li_buf, _, clamped = ops.padded_append_row(
        state.l_buf, state.li_buf, p_pad, c, state.n,
        implementation=implementation)
    x_buf = jax.lax.dynamic_update_slice(state.x_buf, x_new[None, :], (state.n, 0))
    y_buf = jax.lax.dynamic_update_slice(state.y_buf, y_new[None], (state.n,))
    return dataclasses.replace(
        state, x_buf=x_buf, y_buf=y_buf, l_buf=l_buf, li_buf=li_buf,
        n=state.n + 1, since_refit=state.since_refit + 1,
        clamp_count=state.clamp_count + clamped)


def append(state: LazyGPState, kernel: KernelFn, x_new: Array,
           y_new: Array, *, implementation: str = "auto") -> LazyGPState:
    """Absorb one observation in O(n_max^2) (paper Alg. 3).

    Traced-shape safe: can run under jit with n as a traced value.  Uses the
    substrate's fused append — the row solve and the alpha refresh share one
    factor residency (two passes instead of three independent solves).

    Batched: stacked state + `x_new (S, d)`, `y_new (S,)` appends one row to
    every study in one dispatch (per-study heterogeneous n).
    """
    if state.is_batched:
        return _vmap_states(
            lambda st, x, y: append(st, kernel, x, y,
                                    implementation=implementation),
            state, x_new, y_new)
    n_max = state.n_max
    p_pad, c = _cov_column(state, kernel, x_new, implementation)
    x_buf = jax.lax.dynamic_update_slice(state.x_buf, x_new[None, :], (state.n, 0))
    y_buf = jax.lax.dynamic_update_slice(state.y_buf, y_new[None], (state.n,))
    n_new = state.n + 1
    mask_new = jnp.arange(n_max) < n_new
    ymean = jnp.sum(jnp.where(mask_new, y_buf, 0.0)) / jnp.maximum(n_new, 1)
    resid = jnp.where(mask_new, y_buf - ymean, 0.0)
    l_buf, li_buf, alpha, _, clamped = ops.lazy_append(
        state.l_buf, state.li_buf, p_pad, c, resid, state.n,
        implementation=implementation)
    return dataclasses.replace(
        state, x_buf=x_buf, y_buf=y_buf, l_buf=l_buf, li_buf=li_buf,
        alpha=alpha, n=n_new, since_refit=state.since_refit + 1,
        clamp_count=state.clamp_count + clamped)


def append_batch(state: LazyGPState, kernel: KernelFn, xs: Array,
                 ys: Array, *, implementation: str = "auto") -> LazyGPState:
    """Absorb t observations as t sequential O(n^2) appends (paper Sec. 3.4).

    Under a frozen kernel the appends commute up to row order, so the HPO
    scheduler may feed results in *completion* order (async absorption).

    The alpha refresh is deferred to once per batch: each row append is a
    single forward solve, and the two alpha solves run once at the end —
    cutting 2(t-1) O(n_max^2) solves per parallel round vs. refreshing after
    every row.  The result is numerically equivalent (to solver round-off)
    to t sequential `append` calls: alpha depends only on the final factor
    and residual, though the fused sequential path accumulates rounding
    differently than the final two-solve refresh.

    Batched: stacked state + `xs (S, t, d)`, `ys (S, t)` absorbs t rows per
    study in one dispatch.
    """
    if state.is_batched:
        return _vmap_states(
            lambda st, x, y: append_batch(st, kernel, x, y,
                                          implementation=implementation),
            state, xs, ys)

    def body(i, st):
        return _append_row_only(st, kernel, xs[i], ys[i], implementation)

    st = jax.lax.fori_loop(0, xs.shape[0], body, state)
    return dataclasses.replace(
        st, alpha=_recompute_alpha(st, implementation))


# ---------------------------------------------------------------------------
# Fantasy rows: the q-suggestion protocol (DESIGN.md §12).
# ---------------------------------------------------------------------------

FANTASY_LIARS = ("mean", "pessimistic")


@dataclasses.dataclass(frozen=True)
class FantasyConfig:
    """Liar policy for pending-trial fantasies (Snoek et al. 2012).

    * "mean"        — kriging believer: the liar value is the posterior mean
                      at the fantasy point, so the mean surface is (nearly)
                      unchanged and only the variance collapses there.
    * "pessimistic" — constant liar: the worst (max) active observation, so
                      the fantasized point actively repels later suggestions.
    """

    liar: str = "mean"

    def __post_init__(self):
        if self.liar not in FANTASY_LIARS:
            raise ValueError(
                f"unknown fantasy liar {self.liar!r}; "
                f"expected one of {FANTASY_LIARS}")


def fantasy_values(state: LazyGPState, kernel: KernelFn, xs: Array,
                   liar: str = "mean", *,
                   implementation: str = "auto") -> Array:
    """Liar observations for fantasy points `xs (q, d)` against `state`.

    Computed against the *input* state for the whole batch (believer values
    do not see each other — exact for q = 1, the per-step path of the
    q-suggest loop; a constant-liar-per-batch approximation for the q > 1
    replay path, which is fine because fantasy rows are scratch state that
    never survives a tell).
    """
    if liar == "pessimistic":
        m = _active_mask(state)
        worst = jnp.max(jnp.where(m, state.y_buf, -jnp.inf))
        worst = jnp.where(state.n > 0, worst, 0.0)
        return jnp.full((xs.shape[0],), worst, state.y_buf.dtype)
    mean, _ = posterior(state, kernel, xs, implementation=implementation)
    return mean


def fantasize(state: LazyGPState, kernel: KernelFn, xs: Array,
              liar: str = "mean", *,
              implementation: str = "auto") -> LazyGPState:
    """Append q fantasy rows in ONE `lazy_append_rows` dispatch.

    Fantasy rows are full bordered appends — the factor, inverse, and alpha
    all see them, so EI ascent against the fantasized state is the ordinary
    ascent — but they deliberately do NOT touch `since_refit` or
    `clamp_count`: fantasies are scratch state (they must never trigger a
    lag-event refit, and their rollback must not have to un-count
    telemetry).  Rollback is `truncate(state, n_real)`.

    Batched: stacked state + `xs (S, q, d)` fantasizes q rows per study in
    one dispatch.
    """
    if state.is_batched:
        return _vmap_states(
            lambda st, x: fantasize(st, kernel, x, liar,
                                    implementation=implementation),
            state, xs)
    q = xs.shape[0]
    n_max = state.n_max
    ys = fantasy_values(state, kernel, xs, liar,
                        implementation=implementation)
    x_buf = jax.lax.dynamic_update_slice(state.x_buf, xs, (state.n, 0))
    y_buf = jax.lax.dynamic_update_slice(state.y_buf, ys, (state.n,))
    idx = jnp.arange(n_max)
    n_new = state.n + q
    # Column i covers actives + earlier fantasy rows: rows idx < n + i of
    # the final point buffer.
    p_all = jax.vmap(lambda x: _centered_cov(
        kernel, x_buf, x, state.params, implementation),
        out_axes=1)(xs)                                       # (n_max, q)
    cols = jnp.where(idx[:, None] < (state.n + jnp.arange(q))[None, :],
                     p_all, 0.0)
    origin = jnp.zeros((1, xs.shape[-1]), xs.dtype)
    cs = jnp.full((q,), kernel(origin, origin, state.params)[0, 0]
                  + state.params.noise2)
    mask_new = idx < n_new
    ymean = jnp.sum(jnp.where(mask_new, y_buf, 0.0)) / jnp.maximum(n_new, 1)
    resid = jnp.where(mask_new, y_buf - ymean, 0.0)
    l_buf, li_buf, alpha, _, _ = ops.lazy_append_rows(
        state.l_buf, state.li_buf, cols.T, cs, resid, state.n,
        implementation=implementation)
    return dataclasses.replace(
        state, x_buf=x_buf, y_buf=y_buf, l_buf=l_buf, li_buf=li_buf,
        alpha=alpha, n=n_new)


def truncate(state: LazyGPState, n_real: Array) -> LazyGPState:
    """Roll back every row >= n_real to the identity-padded empty state.

    Bitwise-exact by the padding invariant (DESIGN.md §3/§12): appends only
    ever write row n of `l_buf`/`li_buf` and row n of `x_buf`/`y_buf`, and
    before the rows being rolled back were appended, those rows were exactly
    identity (factor/inverse) and exactly zero (points/observations).
    Restoring the constants therefore restores the pre-append buffers bit
    for bit — no arithmetic is undone, rows are simply re-padded.  Alpha is
    recomputed against the restored inverse; any real append that follows
    (the tell replay) recomputes it again through the ordinary fused path,
    so the post-replay state is bitwise-identical to a never-fantasized run.

    `since_refit`/`clamp_count` are untouched because `fantasize` never
    advanced them.  Batched: `n_real (S,)` truncates every study in one
    dispatch.
    """
    if state.is_batched:
        return _vmap_states(truncate, state, n_real)
    n_max = state.n_max
    idx = jnp.arange(n_max)
    pad = idx[:, None] >= n_real
    eye = jnp.eye(n_max, dtype=state.l_buf.dtype)
    st = dataclasses.replace(
        state,
        x_buf=jnp.where(pad, 0.0, state.x_buf),
        y_buf=jnp.where(idx >= n_real, 0.0, state.y_buf),
        l_buf=jnp.where(pad, eye, state.l_buf),
        li_buf=jnp.where(pad, eye, state.li_buf),
        n=jnp.asarray(n_real, jnp.int32))
    return dataclasses.replace(st, alpha=_recompute_alpha(st))


def posterior(state: LazyGPState, kernel: KernelFn, x_star: Array,
              *, implementation: str = "auto",
              ymean: Array | None = None) -> tuple[Array, Array]:
    """Posterior mean and variance at query points x_star (m, d).

    mean = k_*^T alpha + ymean ; var = k_** - v^T v with v = L^{-1} k_*
    (paper Alg. 1 lines 3-6), on padded buffers.

    `ymean` is the active-observation mean; it is recomputed from the state
    when omitted.  Callers that query one frozen state many times (the EI
    ascent: steps x restarts posteriors per suggest call) hoist `_ymean`
    once and pass it in — the loop-invariant reduction then runs once per
    call instead of once per posterior (pinned by a trace-count test).

    Batched: stacked state + `x_star (S, m, d)` returns `(S, m)` mean/var
    (`ymean`, if hoisted, is the matching `(S,)` vector).
    """
    if state.is_batched:
        if ymean is None:
            return _vmap_states(
                lambda st, xq: posterior(st, kernel, xq,
                                         implementation=implementation),
                state, x_star)
        return jax.vmap(
            lambda st, xq, ym: posterior(st, kernel, xq,
                                         implementation=implementation,
                                         ymean=ym))(state, x_star, ymean)
    if ymean is None:
        ymean = _ymean(state)
    k_star = ops.kernel_gram(kernel, state.x_buf, x_star, state.params,
                             implementation=implementation)   # (n_max, m)
    k_star = jnp.where(_active_mask(state)[:, None], k_star, 0.0)
    mean = k_star.T @ state.alpha + ymean
    # v = L^{-1} k_* as a matmul against the maintained inverse (exact on
    # the padded buffers: k_* is zero beyond n).  Matmul-only keeps the EI
    # ascent batchable over the study axis (DESIGN.md §7).
    v = ops.state_matmul(state.li_buf, k_star)                # (n_max, m)
    k_ss = kernel(x_star, x_star, state.params)
    var = jnp.maximum(jnp.diag(k_ss) - jnp.sum(v * v, axis=0), 1e-12)
    return mean, var


def log_marginal_likelihood(state: LazyGPState) -> Array:
    """log p(y | X) = -1/2 y^T alpha - sum log L_ii - n/2 log 2pi (Alg. 1 l.7).

    Identity padding contributes log(1) = 0 to the diagonal sum, so the padded
    computation is exact.  Batched: returns `(S,)` per-study LMLs.
    """
    if state.is_batched:
        return _vmap_states(log_marginal_likelihood, state)
    m = _active_mask(state)
    resid = jnp.where(m, state.y_buf - _ymean(state), 0.0)
    quad = resid @ state.alpha
    logdet = jnp.sum(jnp.where(m, jnp.log(jnp.diagonal(state.l_buf)), 0.0))
    return -0.5 * quad - logdet - 0.5 * state.n * jnp.log(2.0 * jnp.pi)


# ---------------------------------------------------------------------------
# Lag-event refit (paper Sec. 4.1, the lagging factor l).
# ---------------------------------------------------------------------------

def refactor(state: LazyGPState, kernel: KernelFn,
             params: KernelParams | None = None,
             *, implementation: str = "auto") -> LazyGPState:
    """Full O(n^3) refactorization (optionally with new kernel params).

    Routed through the substrate's blocked factorization on the identity-
    padded Gram buffer.

    Batched: refactors every study in one dispatch; `params`, if given, must
    carry `(S,)` leaves (per-study hyper-parameters).
    """
    if state.is_batched:
        if params is None:
            return _vmap_states(
                lambda st: refactor(st, kernel,
                                    implementation=implementation), state)
        return _vmap_states(
            lambda st, p: refactor(st, kernel, p,
                                   implementation=implementation),
            state, params)
    params = params or state.params
    st = dataclasses.replace(state, params=params)
    k_pad = ops.masked_gram(st.x_buf, st.n, kernel, params,
                            implementation=implementation)
    l_buf = chol.lazy_full_refactor(k_pad, st.n, n_max=st.n_max,
                                    implementation=implementation)
    # Rebuild the maintained inverse from scratch (the one place a
    # triangular solve runs; lag-amortized like the factorization itself).
    li_buf = ops.padded_tri_inverse(l_buf, implementation=implementation)
    st = dataclasses.replace(st, l_buf=l_buf, li_buf=li_buf,
                             since_refit=jnp.asarray(0, jnp.int32))
    return dataclasses.replace(
        st, alpha=_recompute_alpha(st, implementation))


def _lml_for(state: LazyGPState, kernel: KernelFn, params: KernelParams,
             implementation: str = "auto") -> Array:
    """LML under candidate params (full rebuild; only used at lag events)."""
    st = refactor(state, kernel, params, implementation=implementation)
    return log_marginal_likelihood(st)


def refit_params(state: LazyGPState, kernel: KernelFn,
                 rho_grid: Array | None = None,
                 sigma2_grid: Array | None = None,
                 *, implementation: str = "auto") -> KernelParams:
    """Multi-restart (grid) LML maximization over (sigma2, rho).

    The paper refits "at reasonable intervals"; a coarse grid is robust, jits
    to a fixed program, and costs l-amortized O(G n^3).

    Batched: returns per-study `KernelParams` with `(S,)` leaves.
    """
    if state.is_batched:
        return _vmap_states(
            lambda st: refit_params(st, kernel, rho_grid, sigma2_grid,
                                    implementation=implementation), state)
    if rho_grid is None:
        # Unit-box length scales (inputs are normalized by the BO driver).
        rho_grid = jnp.asarray([0.05, 0.1, 0.2, 0.4, 0.8, 1.6],
                               state.x_buf.dtype)
    if sigma2_grid is None:
        sigma2_grid = jnp.asarray([0.25, 1.0, 4.0], state.x_buf.dtype)

    rr, ss = jnp.meshgrid(rho_grid, sigma2_grid, indexing="ij")
    cand = jnp.stack([ss.ravel(), rr.ravel()], axis=-1)  # (G, 2) [sigma2, rho]

    def score(c):
        p = KernelParams(sigma2=c[0], rho=c[1], noise2=state.params.noise2)
        return _lml_for(state, kernel, p, implementation)

    lmls = jax.lax.map(score, cand)
    best = jnp.argmax(lmls)
    return KernelParams(sigma2=cand[best, 0], rho=cand[best, 1],
                        noise2=state.params.noise2)


def maybe_refit(state: LazyGPState, kernel: KernelFn, lag: int,
                *, implementation: str = "auto") -> LazyGPState:
    """Apply the lag policy: every `lag` appends, refit params + refactor.

    lag <= 0 means never (the fully lazy GP); lag == 1 reproduces the standard
    per-iteration refit (the paper's baseline semantics).
    """
    if lag <= 0:
        return state

    def do_refit(st):
        params = refit_params(st, kernel, implementation=implementation)
        return refactor(st, kernel, params, implementation=implementation)

    return jax.lax.cond(state.since_refit >= lag, do_refit, lambda s: s, state)


# ---------------------------------------------------------------------------
# Reference (non-lazy) GP for parity tests and the naive baseline.
# ---------------------------------------------------------------------------

def dense_posterior(x: Array, y: Array, x_star: Array, kernel: KernelFn,
                    params: KernelParams,
                    implementation: str = "auto") -> tuple[Array, Array]:
    """Textbook GP posterior with a fresh full factorization (paper Alg. 1)."""
    n = x.shape[0]
    k = kernel(x, x, params) + params.noise2 * jnp.eye(n, dtype=x.dtype)
    l = ops.cholesky(k, implementation=implementation)
    ymean = jnp.mean(y)
    resid = y - ymean
    k_star = kernel(x, x_star, params)
    k_ss_diag = jnp.diag(kernel(x_star, x_star, params))
    mean, var = ops.gp_posterior_solve(l, resid, k_star, k_ss_diag,
                                       implementation=implementation)
    return mean + ymean, var
