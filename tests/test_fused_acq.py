"""Fused EI-ascent megakernel: parity, autotuner, and hoist contracts.

Covers DESIGN.md §11: the fused value+gradient step (`ops.fused_ei_grad`,
hand-derived adjoint in `kernels/acq.py`) must match the unfused autodiff
oracle to <= 1e-5 on every substrate, for float-only and mixed descriptors,
single states and heterogeneous stacked states; the block-size autotuner
must be deterministic per cache key and inert under REPRO_ACQ_AUTOTUNE=off;
and the loop-invariant hoists (`_f_best`, `_ymean` once per suggest call)
are pinned by a trace-count test so a refactor can't silently re-inline
them into the ascent loop.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GPConfig, append_batch, init_state, matern52
from repro.core import acquisition as acq_mod
from repro.core import gp as gp_mod
from repro.core.acquisition import (AcqConfig, ei_value_and_grad,
                                    optimize_acquisition)
from repro.core.kernels import make_mixed_kernel
from repro.kernels import ops

IMPLEMENTATIONS = ["xla", "ref", "pallas"]
CONT_MASK = jnp.asarray([1.0, 1.0, 1.0, 0.0, 0.0])
CAT_MASK = jnp.asarray([0.0, 0.0, 0.0, 1.0, 1.0])
MIXED_KERNEL = make_mixed_kernel(CONT_MASK, CAT_MASK)


@pytest.fixture(autouse=True)
def _fresh_tune_cache():
    ops._ACQ_TUNE_CACHE.clear()
    yield
    ops._ACQ_TUNE_CACHE.clear()


def _seed_state(key, n0, d, n_max, kernel=matern52, implementation="xla"):
    cfg = GPConfig(n_max=n_max, dim=d, implementation=implementation)
    xs = jax.random.uniform(key, (n0, d))
    ys = jnp.sin(3.0 * xs.sum(-1)) + 0.1 * xs[:, 0]
    return append_batch(init_state(cfg), kernel, xs, ys,
                        implementation=implementation)


def _hetero_stack(kernel=matern52, n0s=(3, 6, 9), d=3, n_max=16):
    singles = [_seed_state(jax.random.PRNGKey(20 + i), n0, d, n_max,
                           kernel=kernel) for i, n0 in enumerate(n0s)]
    return gp_mod.stack_states(singles), singles


# ---------------------------------------------------------------------------
# Fused vs unfused parity (value AND gradient), per substrate
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_fused_matches_unfused_float(implementation):
    st = _seed_state(jax.random.PRNGKey(0), 9, 4, 16)
    x = jax.random.uniform(jax.random.PRNGKey(1), (13, 4))
    v_f, g_f = ei_value_and_grad(st, matern52, x,
                                 implementation=implementation, fused=True)
    for oracle in ("xla", "ref"):
        v_u, g_u = ei_value_and_grad(st, matern52, x,
                                     implementation=oracle, fused=False)
        np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_u),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_u),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_fused_matches_unfused_mixed(implementation):
    st = _seed_state(jax.random.PRNGKey(2), 8, 5, 16, kernel=MIXED_KERNEL)
    x = jax.random.uniform(jax.random.PRNGKey(3), (11, 5))
    v_f, g_f = ei_value_and_grad(st, MIXED_KERNEL, x,
                                 implementation=implementation, fused=True)
    v_u, g_u = ei_value_and_grad(st, MIXED_KERNEL, x,
                                 implementation="xla", fused=False)
    np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_u),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_u),
                               rtol=1e-4, atol=1e-5)
    # The categorical factor is stop_gradient'd: the fused adjoint must
    # report exactly zero gradient on the cat coordinates, like autodiff.
    np.testing.assert_array_equal(
        np.asarray(g_f * CAT_MASK), np.zeros_like(np.asarray(g_f)))


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_fused_stacked_heterogeneous_matches_per_study(implementation):
    """Vmapped fused step over a het-n stack == per-study unfused oracle."""
    stacked, singles = _hetero_stack()
    x = jax.random.uniform(jax.random.PRNGKey(4), (len(singles), 7, 3))
    v, g = jax.vmap(lambda st, xi: ei_value_and_grad(
        st, matern52, xi, implementation=implementation, fused=True,
        tune_s=len(singles)))(stacked, x)
    assert v.shape == (len(singles), 7) and g.shape == x.shape
    for i, st in enumerate(singles):
        v_u, g_u = ei_value_and_grad(st, matern52, x[i],
                                     implementation="xla", fused=False)
        np.testing.assert_allclose(np.asarray(v[i]), np.asarray(v_u),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g[i]), np.asarray(g_u),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_fused_suggest_matches_unfused_suggest(implementation):
    """End to end: the whole ascent lands on the same point either way."""
    st = _seed_state(jax.random.PRNGKey(5), 9, 3, 16)
    lo, hi = jnp.zeros(3), jnp.ones(3)
    key = jax.random.PRNGKey(6)
    cfg_on = AcqConfig(restarts=8, ascent_steps=6, fused="on")
    cfg_off = AcqConfig(restarts=8, ascent_steps=6, fused="off")
    p_on, v_on = optimize_acquisition(st, matern52, lo, hi, key, cfg_on, 2,
                                      implementation=implementation)
    p_off, v_off = optimize_acquisition(st, matern52, lo, hi, key, cfg_off,
                                        2, implementation=implementation)
    np.testing.assert_allclose(np.asarray(p_on), np.asarray(p_off),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(v_on), np.asarray(v_off),
                               rtol=1e-4, atol=1e-5)


def test_fused_suggest_deterministic():
    st = _seed_state(jax.random.PRNGKey(7), 6, 3, 16)
    lo, hi = jnp.zeros(3), jnp.ones(3)
    cfg = AcqConfig(restarts=8, ascent_steps=4)
    args = (st, matern52, lo, hi, jax.random.PRNGKey(8), cfg, 2)
    p1, v1 = optimize_acquisition(*args)
    p2, v2 = optimize_acquisition(*args)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_unsupported_acquisition_falls_back_unfused():
    """fused="on" with a non-EI acquisition silently runs the generic
    path (fused_supported gates on the acquisition name)."""
    assert not ops.fused_supported(matern52, "ucb")
    assert ops.fused_supported(matern52, "ei")
    assert ops.fused_supported(MIXED_KERNEL, "ei")
    st = _seed_state(jax.random.PRNGKey(9), 6, 3, 16)
    lo, hi = jnp.zeros(3), jnp.ones(3)
    cfg = AcqConfig(name="ucb", restarts=4, ascent_steps=3, fused="on")
    pts, vals = optimize_acquisition(st, matern52, lo, hi,
                                     jax.random.PRNGKey(10), cfg, 1)
    assert pts.shape == (1, 3) and vals.shape == (1,)


def test_invalid_fused_mode_raises():
    st = _seed_state(jax.random.PRNGKey(11), 4, 2, 8)
    cfg = AcqConfig(fused="maybe")
    with pytest.raises(ValueError, match="fused"):
        optimize_acquisition(st, matern52, jnp.zeros(2), jnp.ones(2),
                             jax.random.PRNGKey(12), cfg, 1)


# ---------------------------------------------------------------------------
# Block-size autotuner (ops.acq_tile_config)
# ---------------------------------------------------------------------------
def test_autotuner_same_key_same_config_no_remeasure(monkeypatch):
    monkeypatch.setenv("REPRO_ACQ_AUTOTUNE", "on")   # CI pins it off
    calls = []

    def fake_measure(block_r, d_pad, n_pad, s):
        calls.append(block_r)
        return float(abs(block_r - 64))       # 64 wins, deterministically

    cfg1 = ops.acq_tile_config(256, 5, 1, True, measure_fn=fake_measure)
    n_first = len(calls)
    assert n_first == len(ops.ACQ_BLOCK_R_CANDIDATES)
    assert cfg1.block_r == 64 and cfg1.measured
    cfg2 = ops.acq_tile_config(256, 5, 1, True, measure_fn=fake_measure)
    assert cfg2 == cfg1
    assert len(calls) == n_first              # cache hit: no re-measure
    ops.acq_tile_config(256, 7, 1, True, measure_fn=fake_measure)
    assert len(calls) == 2 * n_first          # new key does re-measure


def test_autotuner_env_off_pins_heuristic(monkeypatch):
    monkeypatch.setenv("REPRO_ACQ_AUTOTUNE", "off")
    called = []
    cfg = ops.acq_tile_config(
        256, 5, 1, False,
        measure_fn=lambda *a: called.append(a) or 0.0)
    assert not called and not cfg.measured
    assert cfg.block_r == ops.ACQ_DEFAULT_BLOCK_R
    assert cfg.d_pad == 128
    assert not ops._ACQ_TUNE_CACHE            # bypasses the cache entirely


def test_autotuner_interpret_defaults_to_heuristic():
    cfg = ops.acq_tile_config(256, 5, 1, True)
    assert not cfg.measured
    assert cfg.block_r == ops.ACQ_DEFAULT_BLOCK_R
    assert ops.acq_tile_config(256, 5, 1, True) == cfg


def test_autotuner_times_concrete_arrays_inside_a_trace(monkeypatch):
    """The tuner runs while the engine's closures are traced; its race must
    still run the real kernel on concrete arrays (a tracer would time the
    tracing, and the kernel must trace cleanly from there)."""
    monkeypatch.setenv("REPRO_ACQ_AUTOTUNE", "on")
    from repro.kernels.acq import fused_ei_grad_pallas
    seen = []

    def measure(block_r, d_pad, n_pad, s):
        row = jnp.zeros((1, n_pad))
        ei, g = fused_ei_grad_pallas(
            jnp.ones((2 * block_r, d_pad)), jnp.zeros((n_pad, d_pad)), row,
            row, jnp.zeros((n_pad, n_pad)), 1.0, 0.25, 0.0,
            block_r=block_r, interpret=True)
        jax.block_until_ready((ei, g))
        seen.append(isinstance(g, jax.core.Tracer))
        # float() of a tracer raises: the timing is a concrete number
        return float(jnp.sum(ei)) * 0.0 + abs(block_r - 32)

    @jax.jit
    def traced(v):
        cfg = ops.acq_tile_config(128, 5, 1, False, measure_fn=measure)
        return v * cfg.block_r

    assert float(traced(1.0)) == 32.0
    assert len(seen) == len(ops.ACQ_BLOCK_R_CANDIDATES) and not any(seen)
    assert ops._ACQ_TUNE_CACHE[(128, 5, 1, "compiled")].measured


# The ascent's standardized improvement Z = gamma / sigma; the CDF takes
# erfc(-Z / sqrt2).  |Z| > 8.5 puts Phi within 1e-17 of 0 or 1.
ERF_RANGE = np.linspace(-6.0, 6.0, 4801)


def test_erfc_replacement_error_against_erf():
    from repro.kernels import acq
    got = np.asarray(acq.erfc(jnp.asarray(ERF_RANGE, jnp.float32)),
                     np.float64)
    exact = np.array([math.erfc(v) for v in ERF_RANGE])
    # As erf = 1 - erfc: the fit's fractional 1.2e-7 of erfc <= 2, plus
    # half a float32 ulp at 2 — and, against jax.lax.erf, that function's
    # own float32 error (about 2e-7 over this range).
    erf_exact = np.array([math.erf(v) for v in ERF_RANGE])
    assert np.abs((1.0 - got) - erf_exact).max() <= 3.6e-7
    erf_lax = np.asarray(jax.lax.erf(jnp.asarray(ERF_RANGE, jnp.float32)),
                         np.float64)
    assert np.abs((1.0 - got) - erf_lax).max() <= 6e-7
    # Relative error of erfc itself, including the lower tail of Phi where
    # 0.5 * (1 + erf) would cancel: dominated by the float32 rounding of
    # exp's argument -x^2 + poly (a few ulps of about x^2).
    rel = np.abs(got - exact) / exact
    assert np.all(rel <= 2.4e-7 * (ERF_RANGE ** 2 + 2.0)), rel.max()


def test_next_power_of_2():
    assert [ops.next_power_of_2(v) for v in (1, 2, 3, 5, 8, 9, 129)] == [
        1, 2, 4, 8, 8, 16, 256]


# ---------------------------------------------------------------------------
# Selection tie-break quantization (layout-stable top-t)
# ---------------------------------------------------------------------------
def test_tiebreak_quantization_collapses_ulp_ties():
    v = jnp.float32(0.7)
    near = jnp.asarray([v, jnp.nextafter(v, jnp.float32(1.0))])
    q = acq_mod._quantize_for_tiebreak(near)
    assert q[0] == q[1]                       # 1-ulp apart -> same bucket
    # argmax of the quantized values picks the FIRST of a tied pair, so
    # every device layout agrees on the winning restart.
    vals = jnp.asarray([jnp.nextafter(v, jnp.float32(1.0)), v, 0.2])
    assert int(jnp.argmax(acq_mod._quantize_for_tiebreak(vals))) == 0
    vals = jnp.asarray([v, jnp.nextafter(v, jnp.float32(1.0)), 0.2])
    assert int(jnp.argmax(acq_mod._quantize_for_tiebreak(vals))) == 0


def test_tiebreak_quantization_is_monotone():
    vs = jnp.sort(jax.random.normal(jax.random.PRNGKey(13), (64,)) * 100.0)
    q = np.asarray(acq_mod._quantize_for_tiebreak(vs))
    assert (np.diff(q) >= 0).all()            # order-preserving
    np.testing.assert_allclose(q, np.asarray(vs), rtol=2e-3, atol=1e-30)


# ---------------------------------------------------------------------------
# Loop-invariant hoists pinned by trace count (f_best / ymean once per call)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", ["auto", "off"])
def test_f_best_and_ymean_hoisted_once_per_trace(monkeypatch, fused):
    st = _seed_state(jax.random.PRNGKey(14), 6, 3, 16)
    lo, hi = jnp.zeros(3), jnp.ones(3)
    counts = {"f_best": 0, "ymean": 0}
    real_fb, real_ym = acq_mod._f_best, gp_mod._ymean

    def counting_fb(s):
        counts["f_best"] += 1
        return real_fb(s)

    def counting_ym(s):
        counts["ymean"] += 1
        return real_ym(s)

    monkeypatch.setattr(acq_mod, "_f_best", counting_fb)
    monkeypatch.setattr(gp_mod, "_ymean", counting_ym)
    cfg = AcqConfig(restarts=4, ascent_steps=3, fused=fused)
    jax.make_jaxpr(lambda k: optimize_acquisition(
        st, matern52, lo, hi, k, cfg, 1))(jax.random.PRNGKey(15))
    assert counts == {"f_best": 1, "ymean": 1}

    # Batched path: vmap traces the per-study body exactly once too.
    stacked, singles = _hetero_stack()
    keys = jax.random.split(jax.random.PRNGKey(16), len(singles))
    counts["f_best"] = counts["ymean"] = 0
    jax.make_jaxpr(lambda ks: optimize_acquisition(
        stacked, matern52, lo, hi, ks, cfg, 1))(keys)
    assert counts == {"f_best": 1, "ymean": 1}
