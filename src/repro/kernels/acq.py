"""Fused EI value+gradient megakernel for the acquisition ascent (DESIGN.md §11).

The multi-start EI ascent is the serving hot loop: ~`steps x restarts`
iterations, each of which used to dispatch a gram-vs-train build, two
`li_buf` matmuls, the posterior mean/var, EI, and the EI gradient as
separate ops.  This module collapses one whole ascent iteration — for the
entire (r, d) restart batch at once — into a single fused pass:

    K       = kern(X, x_buf) * amask          (r, n_max)   cross-gram
    gamma   = K alpha + shift                 (r, 1)       shift = ymean - f_best - xi
    U       = K A                             (r, n_max)   A = li_buf^T li_buf (hoisted)
    var     = max(sigma2 - rowsum(U o K), VAR_FLOOR)
    EI      = gamma Phi(Z) + sigma phi(Z),    Z = gamma / sigma
    dEI/dx  = analytic (below)                (r, d)

`A` is hoisted once per suggest call (one (n_max, n_max) GEMM, amortized
over every ascent step), turning the posterior-variance solves into ONE
cross-gram-shaped GEMM per step.  The gradient is hand-derived, not
autodiff: the classic EI identities dEI/dmu = Phi(Z) and dEI/dsigma =
phi(Z) (the Z cross-terms cancel), chained through the Matérn-2.5 factor
with the |x - y| singularity cancelled analytically (see `matern.py`):

    dEI/dK_i  = Phi(Z) alpha_i - 2 (phi(Z) / 2 sigma) U_i
    dK_i/dx   = -sigma2 (5 / 3 rho^2) e^{-z} (1 + z) cat_i * (x - xb_i)
    dEI/dvar is zeroed where raw var hit VAR_FLOOR, mirroring autodiff of
    the clamp, so fused and unfused gradients agree even at the floor.

The mixed (Matérn x categorical, DESIGN.md §10) form multiplies the
categorical factor into K and the gradient factor but never differentiates
it — the continuous-block-only contract of `mixed.py` (one-hot coordinates
move by round-and-repair projection, not by gradient).

The Pallas kernel streams candidate tiles (grid over r / block_r) against
the train-side operands, which stay **resident in VMEM** for the whole
pass: `x_buf`, `alpha`, `amask`, and the (n_pad, n_pad) `A` — so the
(restarts, n) cross-gram/`U` intermediates live and die in VMEM,
flash-attention-style, and never round-trip through HBM.  `ops.py` owns
padding, the block-size autotuner, and the mask split; beyond its VMEM
residency bound it falls back to `ei_grad_jnp` (the same math as one fused
XLA program — this is also the "xla"/"ref" oracle the parity suite pins
the kernel against), with a `KernelFallbackWarning`.  The kernel body
uses only what the TPU compiler lowers: `erf` is built from `exp`
(`erfc` below), and every row reduction keeps its 2-D layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chol import vmem_limit

Array = jax.Array

# Variance clamp shared with `gp.posterior` — the fused gradient mirrors
# autodiff of this exact floor.
VAR_FLOOR = 1e-12
# The kernel's matmuls run at full f32: at TPU's default (one bf16 pass)
# the posterior variance behind EI errs by a sizeable share of sigma2, and
# the ascent then climbs precision noise.
_HI = jax.lax.Precision.HIGHEST
_SQRT5 = 2.23606797749979
_SQRT2 = 1.4142135623730951
_INV_SQRT_2PI = 0.3989422804014327
# Chebyshev coefficients of `erfc` (Numerical Recipes, "erfcc").
_ERFC_COEFFS = (-1.26551223, 1.00002368, 0.37409196, 0.09678418,
                -0.18628806, 0.27886807, -1.13520398, 1.48851587,
                -0.82215223, 0.17087277)


def erfc(x: Array) -> Array:
    """Complementary error function from one `exp` and one division.

    The TPU kernel compiler has no `erf`, so the EI's normal CDF is built
    from this Chebyshev fit (Numerical Recipes' `erfcc`): its fractional
    error is below 1.2e-7 for every x in exact arithmetic, i.e. at most
    2.4e-7 absolute against `erf` (erfc <= 2) once used as
    `erf(x) = 1 - erfc(x)`.  In float32 the rounding of `exp`'s argument
    (a few ulps of x^2) adds relative error in the tails;
    `tests/test_fused_acq.py` pins both.  Unlike `0.5 (1 + erf(z))`,
    `0.5 erfc(-z / sqrt2)` keeps its relative accuracy deep in the lower
    tail, where EI lives far from the incumbent.
    """
    z = jnp.abs(x)
    t = 1.0 / (1.0 + 0.5 * z)
    poly = _ERFC_COEFFS[-1]
    for c in _ERFC_COEFFS[-2::-1]:
        poly = c + t * poly
    ans = t * jnp.exp(-z * z + poly)
    return jnp.where(x >= 0.0, ans, 2.0 - ans)


def _sqdist(a: Array, b: Array) -> Array:
    """|a - b|^2 via the MXU-friendly expansion (same tiling as matern.py)."""
    aa = jnp.sum(a * a, axis=-1, keepdims=True)
    bb = jnp.sum(b * b, axis=-1)[None, :]
    cross = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    return jnp.maximum(aa + bb - 2.0 * cross, 0.0)


def _fused_ei_grad_math(xc, xbc, amask, alpha, a_buf, sigma2, rho, shift,
                        xk=None, xbk=None):
    """One fused EI value+grad pass; shared by the Pallas kernel body and
    the jnp (xla/ref) path.

    Args:
      xc: (r, d) candidates (continuous block if mixed — pre-mask-split).
      xbc: (n, d) train buffer (continuous block if mixed).
      amask: (1, n) active-row 0/1 mask.
      alpha: (1, n) padded (K + noise I)^{-1} residual.
      a_buf: (n, n) hoisted A = li_buf^T li_buf.
      sigma2, rho, shift: scalars; shift = ymean - f_best - xi.
      xk/xbk: categorical blocks (mixed spaces only).

    Returns (ei (r, 1), grad (r, d)); the grad is w.r.t. xc (zero on
    masked-out coordinates by construction).
    """
    dist = jnp.sqrt(_sqdist(xc, xbc) + 1e-36)
    z = _SQRT5 * dist / rho
    ez = jnp.exp(-z)
    k = sigma2 * (1.0 + z + z * z / 3.0) * ez
    if xk is not None:
        cat = jnp.exp(-0.5 * _sqdist(xk, xbk) / rho)
        k = k * cat
    else:
        cat = 1.0
    km = k * amask                                           # (r, n)
    # A lane reduction, not a matvec: the TPU compiler folds a scalar added
    # to a matmul result into its accumulator, which it refuses when the
    # scalar is read from a ref.
    gam = jnp.sum(km * alpha, axis=-1, keepdims=True) + shift   # (r, 1)
    u = jax.lax.dot_general(                                 # (r, n)
        km, a_buf, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    raw_var = sigma2 - jnp.sum(u * km, axis=-1, keepdims=True)  # (r, 1)
    var = jnp.maximum(raw_var, VAR_FLOOR)
    sig = jnp.sqrt(var)
    zs = gam / jnp.maximum(sig, 1e-12)
    cdf = 0.5 * erfc(-zs / _SQRT2)
    pdf = jnp.exp(-0.5 * zs * zs) * _INV_SQRT_2PI
    ei = jnp.maximum(gam * cdf + sig * pdf, 0.0)             # (r, 1)
    # dEI/dvar = phi(Z) / 2 sigma, dead where the raw variance hit the
    # clamp (autodiff of jnp.maximum routes the cotangent to the floor).
    dvar = jnp.where(raw_var > VAR_FLOOR, pdf / (2.0 * sig), 0.0)
    c = cdf * (alpha * amask) - 2.0 * dvar * u               # dEI/dK (r, n)
    s = (-sigma2 * (5.0 / (3.0 * rho * rho))) * (1.0 + z) * ez * cat
    w = c * s * amask                                        # (r, n)
    grad = jnp.sum(w, axis=-1, keepdims=True) * xc - jax.lax.dot_general(
        w, xbc, (((1,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    return ei, grad


def ei_grad_jnp(x: Array, x_buf: Array, amask: Array, alpha: Array,
                a_buf: Array, sigma2, rho, shift, *,
                cont_mask: Array | None = None,
                cat_mask: Array | None = None) -> tuple[Array, Array]:
    """Fused EI value+grad as one XLA program (the xla/ref substrate path
    and the beyond-VMEM fallback).  Exact shapes, no padding contract."""
    if cont_mask is not None:
        cm = cont_mask.astype(x.dtype)
        km = cat_mask.astype(x.dtype)
        ei, g = _fused_ei_grad_math(
            x * cm, x_buf * cm, amask[None, :], alpha[None, :], a_buf,
            sigma2, rho, shift, xk=x * km, xbk=x_buf * km)
    else:
        ei, g = _fused_ei_grad_math(
            x, x_buf, amask[None, :], alpha[None, :], a_buf,
            sigma2, rho, shift)
    return ei[:, 0], g


def _acq_tile_kernel(xc_ref, xbc_ref, am_ref, al_ref, ab_ref, par_ref,
                     ei_ref, g_ref):
    ei, g = _fused_ei_grad_math(
        xc_ref[...].astype(jnp.float32), xbc_ref[...].astype(jnp.float32),
        am_ref[...], al_ref[...], ab_ref[...],
        par_ref[0, 0], par_ref[0, 1], par_ref[0, 2])
    ei_ref[...] = jnp.broadcast_to(ei, ei_ref.shape).astype(ei_ref.dtype)
    g_ref[...] = g.astype(g_ref.dtype)


def _acq_mixed_tile_kernel(xc_ref, xk_ref, xbc_ref, xbk_ref, am_ref, al_ref,
                           ab_ref, par_ref, ei_ref, g_ref):
    ei, g = _fused_ei_grad_math(
        xc_ref[...].astype(jnp.float32), xbc_ref[...].astype(jnp.float32),
        am_ref[...], al_ref[...], ab_ref[...],
        par_ref[0, 0], par_ref[0, 1], par_ref[0, 2],
        xk=xk_ref[...].astype(jnp.float32),
        xbk=xbk_ref[...].astype(jnp.float32))
    ei_ref[...] = jnp.broadcast_to(ei, ei_ref.shape).astype(ei_ref.dtype)
    g_ref[...] = g.astype(g_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def fused_ei_grad_pallas(xc: Array, xbc: Array, amask: Array, alpha: Array,
                         a_buf: Array, sigma2, rho, shift, *,
                         xk: Array | None = None, xbk: Array | None = None,
                         block_r: int = 128,
                         interpret: bool = False) -> tuple[Array, Array]:
    """Raw megakernel call: xc (r, d) with r % block_r == 0, train-side
    operands at the (n_pad, d_pad) 128-aligned envelope (`ops.py` pads and
    picks `block_r` via the autotuner).

    Grid streams candidate tiles; everything train-side is one full
    VMEM-resident block.  Returns (ei (r,), grad (r, d)).  Not
    differentiable — the gradient IS an output (the ascent never
    re-differentiates it).  Batches over a leading study axis through
    `pallas_call`'s native batching rule.
    """
    r, d = xc.shape
    n = xbc.shape[0]
    assert r % block_r == 0 and n % 128 == 0 and d % 128 == 0, (r, n, d)
    params = jnp.stack([jnp.asarray(sigma2, jnp.float32),
                        jnp.asarray(rho, jnp.float32),
                        jnp.asarray(shift, jnp.float32),
                        jnp.asarray(0.0, jnp.float32)]).reshape(1, 4)
    grid = (r // block_r,)
    cand_spec = pl.BlockSpec((block_r, d), lambda i: (i, 0))
    train_spec = pl.BlockSpec((n, d), lambda i: (0, 0))
    row_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    if xk is None:
        kernel = _acq_tile_kernel
        operands = (xc, xbc, amask, alpha, a_buf, params)
        in_specs = [cand_spec, train_spec, row_spec, row_spec,
                    pl.BlockSpec((n, n), lambda i: (0, 0)),
                    pl.BlockSpec((1, 4), lambda i: (0, 0))]
    else:
        kernel = _acq_mixed_tile_kernel
        operands = (xc, xk, xbc, xbk, amask, alpha, a_buf, params)
        in_specs = [cand_spec, cand_spec, train_spec, train_spec,
                    row_spec, row_spec,
                    pl.BlockSpec((n, n), lambda i: (0, 0)),
                    pl.BlockSpec((1, 4), lambda i: (0, 0))]
    ei, g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_r, 128), lambda i: (i, 0)),
                   pl.BlockSpec((block_r, d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, 128), xc.dtype),
                   jax.ShapeDtypeStruct((r, d), xc.dtype)],
        # Inside a whole program XLA may place the batched operands and
        # outputs in VMEM too, on top of the blocks; the 16 MiB default
        # overflowed at n = 1024 on a v5e.  Twice the blocks' bytes again.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            2 * 4 * (n * n + 2 * n * d + 2 * n + 4 * block_r * (d + 128)))),
        interpret=interpret,
    )(*operands)
    return ei[:, 0], g
