"""Pallas TPU kernel: tiled pairwise Matérn-2.5 covariance build.

The covariance build is the second hot spot of the lazy GP (the O(n^2 d)
column build feeding every append, and the O(n_max^2 d) full Gram at lag
events).  The TPU-native formulation computes squared distances through the
MXU as |x|^2 + |y|^2 - 2 x.y^T with (bn, d) x (d, bm) tiles, then applies the
Matérn polynomial-exponential on the VPU — one pass, no HBM intermediate for
the distance matrix.

Tiling: grid (n/bn, m/bm); each program reads an x row-panel and a y
row-panel (both resident in VMEM) and writes one (bn, bm) output tile.
The feature dim d is zero-padded to a lane multiple by `ops.py`; zero padding
is exact for squared distances.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BLOCK_N = 128
BLOCK_M = 128


def _matern_tile_kernel(x_ref, y_ref, sig_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (bn, d)
    y = y_ref[...].astype(jnp.float32)          # (bm, d)
    sigma2 = sig_ref[0, 0]
    rho = sig_ref[0, 1]
    xx = jnp.sum(x * x, axis=-1)[:, None]       # (bn, 1)
    yy = jnp.sum(y * y, axis=-1)[None, :]       # (1, bm)
    cross = jax.lax.dot_general(                # MXU: (bn, d) x (bm, d)^T
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # full f32: the sum cancels
    sq = jnp.maximum(xx + yy - 2.0 * cross, 0.0)
    dist = jnp.sqrt(sq + 1e-36)
    z = jnp.sqrt(5.0) * dist / rho
    out_ref[...] = (sigma2 * (1.0 + z + z * z / 3.0)
                    * jnp.exp(-z)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _matern_pallas_raw(x: Array, y: Array, sigma2, rho,
                       *, interpret: bool = False) -> Array:
    """The raw pallas_call (no AD rule — wrapped by the custom VJP below)."""
    n, d = x.shape
    m = y.shape[0]
    assert n % BLOCK_N == 0 and m % BLOCK_M == 0, (n, m)
    params = jnp.asarray([[sigma2, rho]], jnp.float32)  # (1, 2)
    grid = (n // BLOCK_N, m // BLOCK_M)
    return pl.pallas_call(
        _matern_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_M, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        interpret=interpret,
    )(x, y, params)


# The acquisition optimizer differentiates the posterior w.r.t. the query
# points, which flow through this gram build — and `pallas_call` has no
# linearization rule.  The backward pass is the analytic Matérn-2.5 gradient
# in plain jnp (one matmul-dominated pass; never re-differentiated):
#   k = sigma2 g(z) e^{-z},  z = sqrt5 |x - y| / rho,  g = 1 + z + z^2/3
#   dk/dx_i = -sigma2 (5 / 3 rho^2) e^{-z} (1 + z) (x_i - y_j)
# (the apparent 1/|x-y| singularity cancels analytically).

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _matern_vjp(x: Array, y: Array, sigma2, rho, interpret: bool) -> Array:
    return _matern_pallas_raw(x, y, sigma2, rho, interpret=interpret)


def _matern_fwd(x, y, sigma2, rho, interpret):
    k = _matern_pallas_raw(x, y, sigma2, rho, interpret=interpret)
    return k, (x, y, sigma2, rho)


def _matern_bwd(interpret, res, g):
    x, y, sigma2, rho = res
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    sig = jnp.asarray(sigma2, jnp.float32)
    rho32 = jnp.asarray(rho, jnp.float32)
    xx = jnp.sum(x32 * x32, axis=-1)[:, None]
    yy = jnp.sum(y32 * y32, axis=-1)[None, :]
    sq = jnp.maximum(xx + yy - 2.0 * (x32 @ y32.T), 0.0)
    dist = jnp.sqrt(sq + 1e-36)
    z = jnp.sqrt(5.0) * dist / rho32
    ez = jnp.exp(-z)
    poly = 1.0 + z + z * z / 3.0
    dsigma2 = jnp.sum(g32 * poly * ez)
    # dk/dz = -sigma2 e^{-z} z (1 + z) / 3 ;  dz/drho = -z / rho
    drho = jnp.sum(g32 * sig * ez * z * z * (1.0 + z) / (3.0 * rho32))
    # s_ij = g_ij dk_ij/d(x_i - y_j) / (x_i - y_j): the d-cancelled factor
    s = -g32 * sig * ez * (1.0 + z) * (5.0 / (3.0 * rho32 * rho32))
    dx = jnp.sum(s, axis=1)[:, None] * x32 - s @ y32
    dy = jnp.sum(s, axis=0)[:, None] * y32 - s.T @ x32
    return (dx.astype(x.dtype), dy.astype(y.dtype),
            dsigma2.astype(jnp.result_type(sigma2)),
            drho.astype(jnp.result_type(rho)))


_matern_vjp.defvjp(_matern_fwd, _matern_bwd)


def matern52_gram_pallas(x: Array, y: Array, sigma2, rho,
                         *, interpret: bool = False) -> Array:
    """x: (n, d), y: (m, d) with n, m multiples of 128 (ops.py pads).

    Returns the (n, m) Matérn-2.5 covariance tile grid.  Differentiable in
    x, y, sigma2, rho via the analytic VJP above.
    """
    return _matern_vjp(x, y, sigma2, rho, interpret)
