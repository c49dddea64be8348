"""Acquisition functions and their optimizers.

Expected Improvement (paper Sec. 3.2.1) plus a jit-able multi-start optimizer
that returns either the single argmax (sequential BO) or the top-t *local
maxima* (paper Sec. 3.4's parallel strategy: "not only use the maximal
expected improvement ... but the t best local maxima").

Local maxima are approximated by multi-start projected gradient ascent from R
random restarts followed by spatial deduplication: ascended points that
converge to the same basin collapse to one representative, and the t best
distinct basins are returned.  This is fixed-shape (R restarts, S ascent
steps) so the whole suggestion step compiles once.

The EI ascent runs on the **fused megakernel** (DESIGN.md §11) wherever the
substrate covers it: every step evaluates EI value + analytic gradient for
the whole (R, d) restart batch in one dispatch (`ops.fused_ei_grad`), with
the loop-invariant pieces — f_best, the active-observation mean,
`A = li_buf^T li_buf`, and the active mask — hoisted once per suggest call.
`AcqConfig.fused` controls the path: "auto" (default) uses it for every
substrate except "ref", which stays on the generic autodiff ascent as the
independent oracle the parity suite compares against.

Restart selection quantizes the acquisition values (low-mantissa clearing)
before the argmax / top-t sort, so substrate- and layout-level round-off
(mesh="none" vs. a sharded ascent) never flips which restart wins a
numerical tie — the chosen cell is identical across layouts.  Reported
values stay exact.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import descriptor as desc_mod
from repro.core import gp as gp_mod
from repro.core.kernels import KernelFn
from repro.kernels import ops

Array = jax.Array

_SQRT2 = 1.4142135623730951


def _norm_pdf(z: Array) -> Array:
    return jnp.exp(-0.5 * z * z) / jnp.sqrt(2.0 * jnp.pi)


def _norm_cdf(z: Array) -> Array:
    return 0.5 * (1.0 + jax.lax.erf(z / _SQRT2))


def expected_improvement(mean: Array, var: Array, f_best: Array,
                         xi: float = 0.01) -> Array:
    """EI(x) = gamma Phi(Z) + sigma phi(Z)  (paper Eq. 11, maximization form).

    gamma = mu(x) - f_best - xi ; Z = gamma / sigma.  xi trades exploration
    for exploitation.
    """
    sigma = jnp.sqrt(var)
    gamma = mean - f_best - xi
    z = jnp.where(sigma > 0, gamma / jnp.maximum(sigma, 1e-12), 0.0)
    ei = gamma * _norm_cdf(z) + sigma * _norm_pdf(z)
    return jnp.where(sigma > 0, jnp.maximum(ei, 0.0), 0.0)


def upper_confidence_bound(mean: Array, var: Array, f_best: Array,
                           beta: float = 2.0) -> Array:
    del f_best
    return mean + beta * jnp.sqrt(var)


ACQUISITIONS: dict[str, Callable[..., Array]] = {
    "ei": expected_improvement,
    # EI-per-unit-cost (FABOLAS-style): the posterior term is plain EI; the
    # division by the predicted cost happens in `_acq_value` when the caller
    # supplies a `log_cost_fn` (a learned log-cost head — see
    # repro.core.neural_basis).  Without one it degrades to plain EI, so a
    # study configured for cost-aware acquisition still serves on tiers
    # that carry no cost model.
    "ei_per_cost": expected_improvement,
    "ucb": upper_confidence_bound,
}

# Predicted log-cost is clipped before exponentiation so a wild early cost
# head can never zero out (or explode) the acquisition surface.
_LOG_COST_CLIP = 20.0


def cost_scaled(value: Array, log_cost: Array) -> Array:
    """acq / exp(log_cost): EI per unit of predicted cost (FABOLAS)."""
    return value * jnp.exp(-jnp.clip(log_cost, -_LOG_COST_CLIP,
                                     _LOG_COST_CLIP))


FUSED_MODES = ("auto", "on", "off")


@dataclasses.dataclass(frozen=True)
class AcqConfig:
    name: str = "ei"
    xi: float = 0.01
    restarts: int = 64          # R multi-start seeds
    ascent_steps: int = 25      # S projected-gradient steps per seed
    lr: float = 0.05            # in units of the box width
    dedup_radius: float = 0.08  # basin-merge radius, units of box width
    fused: str = "auto"         # fused EI megakernel (DESIGN.md §11):
    # "auto" = fused wherever the substrate covers it except "ref" (the
    # autodiff oracle), "on" = force fused (parity tests), "off" = never.


def _acq_value(state: gp_mod.LazyGPState, kernel: KernelFn, x: Array,
               f_best: Array, cfg: AcqConfig,
               implementation: str = "auto",
               ymean: Array | None = None,
               log_cost_fn: Callable[[Array], Array] | None = None) -> Array:
    mean, var = gp_mod.posterior(state, kernel, x[None, :],
                                 implementation=implementation, ymean=ymean)
    fn = ACQUISITIONS[cfg.name]
    val = fn(mean, var, f_best, cfg.xi)[0]
    if cfg.name == "ei_per_cost" and log_cost_fn is not None:
        val = cost_scaled(val, log_cost_fn(x))
    return val


def _f_best(state: gp_mod.LazyGPState) -> Array:
    m = jnp.arange(state.n_max) < state.n
    return jnp.max(jnp.where(m, state.y_buf, -jnp.inf))


# Mantissa bits cleared by the selection tie-break: values within ~2^-11
# relative distance collapse to one bucket — orders of magnitude wider than
# substrate/layout round-off (a few ulps), orders of magnitude tighter than
# any real EI difference between distinct basins.
_TIEBREAK_MANTISSA_BITS = 12


def _quantize_for_tiebreak(vals: Array) -> Array:
    """Scale-free float32 quantization used ONLY for restart selection.

    Clearing low mantissa bits is monotone (never reorders values beyond
    collapsing near-ties), so argmax / the stable descending sort pick the
    same (first) restart index under mesh="none" and any sharded layout
    even when the two layouts' arithmetic differs by ulps.  Reported
    acquisition values stay exact — this never touches them.
    """
    bits = jax.lax.bitcast_convert_type(vals.astype(jnp.float32), jnp.uint32)
    bits = bits & jnp.uint32((0xFFFFFFFF << _TIEBREAK_MANTISSA_BITS)
                             & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _use_fused(cfg: AcqConfig, kernel: KernelFn, implementation: str) -> bool:
    """Host-side fused-path policy (baked into the jitted program)."""
    if cfg.fused not in FUSED_MODES:
        raise ValueError(f"unknown AcqConfig.fused {cfg.fused!r}; "
                         f"expected one of {FUSED_MODES}")
    if cfg.fused == "off" or not ops.fused_supported(kernel, cfg.name):
        return False
    return cfg.fused == "on" or implementation != "ref"


def _make_eval_batch(state: gp_mod.LazyGPState, kernel: KernelFn,
                     cfg: AcqConfig, implementation: str, fused: bool,
                     f_best: Array, ymean: Array, tune_s: int,
                     log_cost_fn: Callable[[Array], Array] | None = None):
    """Build `eval(X (r, d)) -> (vals (r,), grads (r, d))` for the ascent.

    Fused: hoists the loop-invariant precompute — the active mask,
    `A = li_buf^T li_buf` (one GEMM amortized over every ascent step), and
    the scalar shift `ymean - f_best - xi` — and closes over it, so each
    step is a single `ops.fused_ei_grad` dispatch for the whole batch.

    Unfused: the generic autodiff path (any acquisition, any kernel),
    with `f_best`/`ymean` still hoisted out of the jitted restart loop.
    """
    if fused:
        amask = (jnp.arange(state.n_max) < state.n).astype(state.x_buf.dtype)
        a_buf = ops.state_matmul(state.li_buf.T, state.li_buf)
        shift = ymean - f_best - cfg.xi
        cont_mask = getattr(kernel, "cont_mask", None)
        cat_mask = getattr(kernel, "cat_mask", None)

        def eval_batch(x):
            return ops.fused_ei_grad(
                x, state.x_buf, amask, state.alpha, a_buf,
                state.params.sigma2, state.params.rho, shift,
                cont_mask=cont_mask, cat_mask=cat_mask,
                implementation=implementation, tune_s=tune_s)

        return eval_batch
    value = lambda x: _acq_value(state, kernel, x, f_best, cfg,
                                 implementation, ymean=ymean,
                                 log_cost_fn=log_cost_fn)
    return jax.vmap(jax.value_and_grad(value))


def ei_value_and_grad(state: gp_mod.LazyGPState, kernel: KernelFn,
                      x: Array, cfg: AcqConfig | None = None, *,
                      implementation: str = "auto", fused: bool = True,
                      tune_s: int = 1) -> tuple[Array, Array]:
    """Acquisition value + gradient for a whole (r, d) candidate batch.

    `fused=True` runs the megakernel step (DESIGN.md §11); `fused=False`
    runs the generic autodiff oracle on the same hoisted invariants.  One
    ascent iteration evaluates exactly this — exposed so the parity suite
    and the phase benchmarks exercise the real step in isolation.
    Single-study states; vmap over a stacked state for the batched form.
    """
    cfg = cfg or AcqConfig()
    eval_batch = _make_eval_batch(
        state, kernel, cfg, implementation, fused,
        _f_best(state), gp_mod._ymean(state), tune_s)
    return eval_batch(x)


def ascend_acquisition(eval_batch, lo: Array, hi: Array, key: Array,
                       cfg: AcqConfig, top_t: int = 1,
                       *, project=None,
                       restart_axis: str | None = None,
                       restart_shards: int = 1,
                       dtype=jnp.float32) -> tuple[Array, Array]:
    """Model-free multi-start ascent + layout-stable selection core.

    `eval_batch(X (r, d)) -> (vals (r,), grads (r, d))` is the acquisition
    oracle; everything else — seed generation, projected-gradient ascent,
    restart sharding, tie-break-quantized argmax / greedy top-t dedup with
    jittered backfill — is model-independent and shared between the
    lazy-GP tier (`optimize_acquisition` builds the oracle from a
    `LazyGPState`) and the neural-basis tier (repro.core.neural_basis
    builds it from the Bayesian linear head, optionally cost-scaled).
    `project` (optional) repairs each iterate onto a feasible lattice
    (mixed spaces, DESIGN.md §10).
    """
    if cfg.restarts % restart_shards:
        raise ValueError(
            f"restart shards ({restart_shards}) must divide "
            f"cfg.restarts ({cfg.restarts})")
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    d = lo.shape[-1]
    width = hi - lo

    seeds = lo + (hi - lo) * jax.random.uniform(key, (cfg.restarts, d),
                                                dtype=dtype)

    point_project = project if project is not None else (lambda u: u)
    project_rows = ((lambda u: jax.vmap(point_project)(u))
                    if project is not None else (lambda u: u))

    def ascend_batch(x):
        # Whole-batch ascent: every step evaluates the (r, d) candidate
        # matrix in one fused dispatch (or one vmapped autodiff pass on
        # the unfused path).  Mixed ascent: gradient step on the
        # continuous coordinates (the categorical factor carries no
        # gradient), then round-and-repair back onto the int/categorical
        # lattice — every iterate, and the seed itself, is feasible.
        def step(_, x):
            _, g = eval_batch(x)
            gn = jnp.linalg.norm(g, axis=-1, keepdims=True)
            g = jnp.where(gn > 0, g / jnp.maximum(gn, 1e-12), 0.0)
            return project_rows(jnp.clip(x + cfg.lr * width * g, lo, hi))
        finals = jax.lax.fori_loop(0, cfg.ascent_steps, step,
                                   project_rows(x))
        vals, _ = eval_batch(finals)
        return finals, vals

    if restart_axis is not None and restart_shards > 1:
        # Ascend only this shard's contiguous slice of the seeds, then
        # reassemble: all_gather(tiled) concatenates in axis-index order,
        # restoring the exact unsharded restart order.
        r_local = cfg.restarts // restart_shards
        idx = jax.lax.axis_index(restart_axis)
        local = jax.lax.dynamic_slice_in_dim(seeds, idx * r_local, r_local)
        finals, vals = ascend_batch(local)          # (R/shards, d), (R/shards,)
        finals = jax.lax.all_gather(finals, restart_axis, tiled=True)
        vals = jax.lax.all_gather(vals, restart_axis, tiled=True)
    else:
        finals, vals = ascend_batch(seeds)              # (R, d), (R,)

    # Selection runs on tie-break-quantized values (layout-stable winner);
    # the returned values are the exact ones.
    qvals = _quantize_for_tiebreak(vals)
    if top_t == 1:
        # Fast path: the greedy dedup below returns the plain argmax when
        # only one suggestion is requested, so skip its R-iteration loop.
        best = jnp.argmax(qvals)
        return finals[best][None, :], vals[best][None]

    # Spatial dedup: greedy pick best, suppress all restarts within radius.
    order = jnp.argsort(-qvals)
    finals = finals[order]
    vals = vals[order]
    radius = cfg.dedup_radius * jnp.linalg.norm(width)

    def pick(i, carry):
        chosen, chosen_vals, suppressed, count = carry
        is_free = ~suppressed[i] & (count < top_t)
        chosen = jax.lax.cond(
            is_free,
            lambda c: jax.lax.dynamic_update_slice(c, finals[i][None, :],
                                                   (count, 0)),
            lambda c: c, chosen)
        chosen_vals = jax.lax.cond(
            is_free,
            lambda c: jax.lax.dynamic_update_slice(c, vals[i][None], (count,)),
            lambda c: c, chosen_vals)
        dist = jnp.linalg.norm(finals - finals[i], axis=-1)
        suppressed = jnp.where(is_free, suppressed | (dist < radius), suppressed)
        count = count + jnp.where(is_free, 1, 0)
        return chosen, chosen_vals, suppressed, count

    chosen0 = jnp.zeros((top_t, d), finals.dtype)
    vals0 = jnp.full((top_t,), -jnp.inf, vals.dtype)
    suppressed0 = jnp.zeros((cfg.restarts,), bool)
    chosen, chosen_vals, _, count = jax.lax.fori_loop(
        0, cfg.restarts, pick, (chosen0, vals0, suppressed0, 0))

    # If fewer than top_t distinct basins exist, back-fill with jittered
    # copies of the best point so the batch shape stays fixed (re-projected
    # so mixed-space backfills stay on the feasible lattice).
    jitter = 0.01 * width * jax.random.normal(
        jax.random.fold_in(key, 1), (top_t, d), dtype=finals.dtype)
    fallback = jax.vmap(point_project)(jnp.clip(chosen[0] + jitter, lo, hi))
    filled = jnp.arange(top_t) < count
    chosen = jnp.where(filled[:, None], chosen, fallback)
    chosen_vals = jnp.where(filled, chosen_vals, chosen_vals[0])
    return chosen, chosen_vals


def optimize_acquisition(state: gp_mod.LazyGPState, kernel: KernelFn,
                         lo: Array, hi: Array, key: Array,
                         cfg: AcqConfig, top_t: int = 1,
                         *, implementation: str = "auto",
                         restart_axis: str | None = None,
                         restart_shards: int = 1,
                         desc: desc_mod.TypeDescriptor | None = None,
                         log_cost_fn: Callable[[Array], Array] | None = None,
                         _tune_s: int = 1) -> tuple[Array, Array]:
    """Return (points (top_t, d), acq values (top_t,)), best first.

    top_t = 1 is standard sequential BO; top_t = t implements the paper's
    parallel suggestion of the t best distinct local maxima.  `implementation`
    selects the linalg substrate for the posterior solves inside the ascent.

    Batched (DESIGN.md §7): a stacked state (leading study axis S) returns
    `((S, top_t, d), (S, top_t))` — one vmapped dispatch suggests for every
    study at once.  `key` may be a single key (split per study) or `(S,)`
    stacked keys; `lo`/`hi` may be shared `(d,)` or per-study `(S, d)`.

    Sharded (DESIGN.md §8): inside a `shard_map` whose mesh carries a
    `restart_axis` of size `restart_shards`, each shard ascends only its
    R/restart_shards slice of the seeds and an `all_gather` reassembles the
    full (R,) candidate set before dedup — every shard then computes the
    identical result (replicated outputs).  Seeds are generated from the
    full `key` on every shard and sliced by `axis_index`, so the sharded
    ascent sees exactly the seeds the unsharded path would.

    Mixed spaces (DESIGN.md §10): with a `TypeDescriptor`, every ascent
    step interleaves the projected-gradient update on the continuous
    coordinates with `descriptor.project_units` round-and-repair onto the
    int/categorical lattice, so candidates are always feasible.  The
    projection is masked arithmetic on the descriptor arrays — batched
    states may carry a stacked `(S, d)`-leaved descriptor (studies with
    *different* type layouts vmap together), but then `kernel` must itself
    be layout-correct per study (the engine builds per-study closures; a
    shared `(d,)` descriptor works with one shared kernel).
    """
    if state.is_batched:
        n_studies = state.x_buf.shape[0]
        keys = key if key.ndim == 2 else jax.random.split(key, n_studies)
        lo = jnp.asarray(lo)
        hi = jnp.asarray(hi)
        d_ax = 0 if desc is not None and desc.is_batched else None
        return jax.vmap(
            lambda st, k, l, h, dc: optimize_acquisition(
                st, kernel, l, h, k, cfg, top_t,
                implementation=implementation, restart_axis=restart_axis,
                restart_shards=restart_shards, desc=dc,
                log_cost_fn=log_cost_fn, _tune_s=n_studies),
            in_axes=(0, 0,
                     0 if lo.ndim == 2 else None,
                     0 if hi.ndim == 2 else None,
                     d_ax))(state, keys, lo, hi, desc)
    # Loop-invariant hoist: f_best and the active-observation mean are
    # computed once per suggest call and closed over — never re-reduced
    # inside the jitted restart loop (pinned by a trace-count test).
    f_best = _f_best(state)
    ymean = gp_mod._ymean(state)

    fused = _use_fused(cfg, kernel, implementation)
    eval_batch = _make_eval_batch(state, kernel, cfg, implementation, fused,
                                  f_best, ymean, _tune_s, log_cost_fn)
    project = ((lambda u: desc_mod.project_units(u, desc))
               if desc is not None else None)
    return ascend_acquisition(eval_batch, lo, hi, key, cfg, top_t,
                              project=project, restart_axis=restart_axis,
                              restart_shards=restart_shards,
                              dtype=state.x_buf.dtype)


def suggest_q(state: gp_mod.LazyGPState, kernel: KernelFn,
              lo: Array, hi: Array, key: Array, cfg: AcqConfig, q: int,
              *, liar: str = "mean", implementation: str = "auto",
              desc: desc_mod.TypeDescriptor | None = None,
              _tune_s: int = 1
              ) -> tuple[Array, Array, gp_mod.LazyGPState]:
    """Sequential-fantasy q-suggestion (qEI, DESIGN.md §12).

    One `lax.scan` of q steps over a single-study state: each step ascends
    the acquisition against the *current* (fantasized) posterior, then
    appends the chosen point as a fantasy row (`gp.fantasize`: liar
    observation, one bordered `li_buf` row, no refit counters), so step
    i + 1 suggests against a posterior whose variance has collapsed at the
    first i picks.  The whole loop is one jitted program — a q = 32 ask is
    ONE dispatch, not 32 serialized suggest ticks.

    The liar value per step is computed against the current fantasized
    state, so "mean" is the exact kriging-believer recursion and
    "pessimistic" is Snoek et al.'s constant liar.

    Returns `(xs (q, d), vals (q,), fantasized state)` — the caller decides
    whether the fantasized state persists (the serving protocol keeps it
    until the tell-time rollback) or is discarded.
    """
    keys = jax.random.split(key, q)

    def step(st, k):
        x, v = optimize_acquisition(
            st, kernel, lo, hi, k, cfg, 1,
            implementation=implementation, desc=desc, _tune_s=_tune_s)
        st = gp_mod.fantasize(st, kernel, x, liar,
                              implementation=implementation)
        return st, (x[0], v[0])

    st, (xs, vals) = jax.lax.scan(step, state, keys)
    return xs, vals, st
