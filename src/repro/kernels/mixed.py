"""Pallas TPU kernel: fused mixed-space (continuous x categorical) gram.

One tile pass builds the DESIGN.md §10 mixed covariance

    k(x, y) = sigma2 * M52(|xc - yc| sqrt5 / rho) * exp(-|xk - yk|^2 / 2 rho)

where `xc = x * cont_mask` / `xk = x * cat_mask` are the mask-split views
of the encoded unit vectors (the split happens in `ops.py`, so the kernel
sees four dense operands and both squared distances ride the MXU via the
|x|^2 + |y|^2 - 2 x.y^T expansion — same tiling as `matern.py`, one extra
matmul per tile, still no HBM intermediate).

The custom VJP differentiates the **continuous block only**: the
categorical factor scales the Matérn gradient but contributes no gradient
of its own (`dxk = dyk = 0`, and `drho` excludes the factor's rho) —
matching the jnp formulation's stop_gradient and the acquisition contract
that one-hot coordinates move by round-and-repair, never by gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

BLOCK_N = 128
BLOCK_M = 128


def _mixed_tile_kernel(xc_ref, yc_ref, xk_ref, yk_ref, par_ref, out_ref):
    xc = xc_ref[...].astype(jnp.float32)        # (bn, d)
    yc = yc_ref[...].astype(jnp.float32)        # (bm, d)
    xk = xk_ref[...].astype(jnp.float32)
    yk = yk_ref[...].astype(jnp.float32)
    sigma2 = par_ref[0, 0]
    rho = par_ref[0, 1]

    def sqdist(a, b):
        aa = jnp.sum(a * a, axis=-1)[:, None]
        bb = jnp.sum(b * b, axis=-1)[None, :]
        cross = jax.lax.dot_general(            # MXU: (bn, d) x (bm, d)^T
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # full f32: the sum cancels
            preferred_element_type=jnp.float32)
        return jnp.maximum(aa + bb - 2.0 * cross, 0.0)

    dist = jnp.sqrt(sqdist(xc, yc) + 1e-36)
    z = jnp.sqrt(5.0) * dist / rho
    cat = jnp.exp(-0.5 * sqdist(xk, yk) / rho)
    out_ref[...] = (sigma2 * (1.0 + z + z * z / 3.0)
                    * jnp.exp(-z) * cat).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mixed_pallas_raw(xc: Array, yc: Array, xk: Array, yk: Array,
                      sigma2, rho, *, interpret: bool = False) -> Array:
    n, d = xc.shape
    m = yc.shape[0]
    assert n % BLOCK_N == 0 and m % BLOCK_M == 0, (n, m)
    params = jnp.asarray([[sigma2, rho]], jnp.float32)  # (1, 2)
    grid = (n // BLOCK_N, m // BLOCK_M)
    return pl.pallas_call(
        _mixed_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_M, d), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_N, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_M, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), xc.dtype),
        interpret=interpret,
    )(xc, yc, xk, yk, params)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mixed_vjp(xc, yc, xk, yk, sigma2, rho, interpret):
    return _mixed_pallas_raw(xc, yc, xk, yk, sigma2, rho,
                             interpret=interpret)


def _mixed_fwd(xc, yc, xk, yk, sigma2, rho, interpret):
    k = _mixed_pallas_raw(xc, yc, xk, yk, sigma2, rho, interpret=interpret)
    return k, (xc, yc, xk, yk, sigma2, rho)


def _mixed_bwd(interpret, res, g):
    xc, yc, xk, yk, sigma2, rho = res
    xc32, yc32 = xc.astype(jnp.float32), yc.astype(jnp.float32)
    xk32, yk32 = xk.astype(jnp.float32), yk.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    sig = jnp.asarray(sigma2, jnp.float32)
    rho32 = jnp.asarray(rho, jnp.float32)

    def sqdist(a, b):
        aa = jnp.sum(a * a, axis=-1)[:, None]
        bb = jnp.sum(b * b, axis=-1)[None, :]
        return jnp.maximum(aa + bb - 2.0 * (a @ b.T), 0.0)

    dist = jnp.sqrt(sqdist(xc32, yc32) + 1e-36)
    z = jnp.sqrt(5.0) * dist / rho32
    ez = jnp.exp(-z)
    cat = jnp.exp(-0.5 * sqdist(xk32, yk32) / rho32)
    poly = 1.0 + z + z * z / 3.0
    dsigma2 = jnp.sum(g32 * poly * ez * cat)
    # Continuous-only rho gradient (the categorical factor's rho is frozen
    # behind the stop_gradient contract): dk/dz = -sig e^{-z} z (1+z)/3.
    drho = jnp.sum(g32 * sig * cat * ez * z * z * (1.0 + z)
                   / (3.0 * rho32))
    # Matérn gradient on the continuous block, scaled by the cat factor;
    # the |x-y| singularity cancels analytically (see matern.py).
    s = -g32 * sig * cat * ez * (1.0 + z) * (5.0 / (3.0 * rho32 * rho32))
    dxc = jnp.sum(s, axis=1)[:, None] * xc32 - s @ yc32
    dyc = jnp.sum(s, axis=0)[:, None] * yc32 - s.T @ xc32
    return (dxc.astype(xc.dtype), dyc.astype(yc.dtype),
            jnp.zeros_like(xk), jnp.zeros_like(yk),
            dsigma2.astype(jnp.result_type(sigma2)),
            drho.astype(jnp.result_type(rho)))


_mixed_vjp.defvjp(_mixed_fwd, _mixed_bwd)


def mixed_gram_pallas(xc: Array, yc: Array, xk: Array, yk: Array,
                      sigma2, rho, *, interpret: bool = False) -> Array:
    """Mask-split operands (n, d) x (m, d), n/m multiples of 128 (ops.py
    pads).  Differentiable in xc/yc/sigma2/rho; xk/yk get zero cotangents
    (the categorical block has no VJP by contract)."""
    return _mixed_vjp(xc, yc, xk, yk, sigma2, rho, interpret)
