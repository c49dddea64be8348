"""Pallas TPU kernel: blocked triangular solve (forward/backward substitution).

This is the paper's O(n^2) incremental-Cholesky hot path (Alg. 3 line 11,
``solve L q = p``) made TPU-native.  The paper's formulation is a scalar
recurrence; here it is blocked into 128-row panels so that the dominant work
— the off-diagonal update ``rhs_b -= L[b, :b] @ q[:b]`` — is an MXU matmul,
and only the 128x128 diagonal block runs the sequential substitution (as a
128-step VPU loop).  Same O(n^2) asymptotics, ~(n/128)x fewer sequential
steps.

Supports matrix right-hand sides (n, r) so the GP posterior's ``L^{-1} K_*``
solve reuses the same kernel, and a ``trans`` variant (backward substitution
on L^T) for the alpha refresh.

The whole factor stays VMEM-resident (16 MiB at `ops.MAX_PALLAS_N` = 2048,
f32) while the grid walks the right-hand side in 128-column slabs.  Every
slice is a static ref slice, and the 128x128 diagonal solves pick rows and
columns out with masked reductions: the TPU compiler lowers no dynamic
slice of an in-register value.  `ops.py` hands larger n to XLA, with a
`KernelFallbackWarning`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chol import (solve_lower_block, solve_upper_block,
                                vmem_limit)

Array = jax.Array

BLOCK = 128
_HI = jax.lax.Precision.HIGHEST


def _trsv_kernel(l_ref, b_ref, out_ref, *, trans: bool, n_blocks: int):
    """q = L^{-1} b (or L^{-T} b) for one 128-column slab of the right-hand
    side, into `out_ref`, one 128-row block at a time; the solved blocks in
    `out_ref` feed the later blocks' updates."""
    n = n_blocks * BLOCK
    order = range(n_blocks - 1, -1, -1) if trans else range(n_blocks)
    for kb in order:                              # static block offsets
        s, e = kb * BLOCK, (kb + 1) * BLOCK
        rhs = b_ref[s:e, :].astype(jnp.float32)
        ldiag = l_ref[s:e, s:e].astype(jnp.float32)
        if trans:
            if e < n:
                # Row block of L^T = (L[e:, s:e])^T against the solved rows.
                rhs = rhs - jax.lax.dot_general(
                    l_ref[e:, s:e].astype(jnp.float32), out_ref[e:, :],
                    (((0,), (0,)), ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32)
            qblk = solve_upper_block(ldiag.T, rhs)
        else:
            if s > 0:
                rhs = rhs - jax.lax.dot_general(
                    l_ref[s:e, :s].astype(jnp.float32), out_ref[:s, :],
                    (((1,), (0,)), ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32)
            qblk = solve_lower_block(ldiag, rhs)
        out_ref[s:e, :] = qblk


@functools.partial(jax.jit, static_argnames=("trans", "interpret"))
def _trsv_pallas_raw(l: Array, b: Array, *, trans: bool = False,
                     interpret: bool = False) -> Array:
    """The raw pallas_call (no AD rule — wrapped by the custom VJP below).

    The grid walks the right-hand side in 128-column slabs (the columns are
    independent solves); the factor is one single-buffered block that stays
    resident across the whole grid."""
    n, r = b.shape
    assert n % BLOCK == 0 and r % BLOCK == 0 and l.shape == (n, n), \
        (l.shape, b.shape)
    kernel = functools.partial(_trsv_kernel, trans=trans, n_blocks=n // BLOCK)
    slab = pl.BlockSpec((n, BLOCK), lambda j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=(r // BLOCK,),
        in_specs=[pl.BlockSpec((n, n), lambda j: (0, 0),
                               pipeline_mode=pl.Buffered(1)),
                  slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct(b.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(4 * n * n + 2 * 2 * 4 * n * BLOCK)),
        interpret=interpret,
    )(l, b).astype(b.dtype)


# `pallas_call` has no linearization rule, but the acquisition optimizer
# differentiates through the posterior solves — so the solve carries the
# textbook triangular-solve VJP, with both backward solves riding the same
# Pallas kernel:
#   q = L^{-1} b :  b_bar = L^{-T} q_bar,  L_bar = -tril(b_bar q^T)
#   q = L^{-T} b :  b_bar = L^{-1} q_bar,  L_bar = -tril(q b_bar^T)

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _trsv_vjp(l: Array, b: Array, trans: bool, interpret: bool) -> Array:
    return _trsv_pallas_raw(l, b, trans=trans, interpret=interpret)


def _trsv_fwd(l, b, trans, interpret):
    q = _trsv_pallas_raw(l, b, trans=trans, interpret=interpret)
    return q, (l, q)


def _trsv_bwd(trans, interpret, res, g):
    l, q = res
    db = _trsv_pallas_raw(l, g, trans=not trans, interpret=interpret)
    dl = -jnp.tril(q @ db.T if trans else db @ q.T)
    return dl.astype(l.dtype), db.astype(q.dtype)


_trsv_vjp.defvjp(_trsv_fwd, _trsv_bwd)


def trsv_pallas(l: Array, b: Array, *, trans: bool = False,
                interpret: bool = False) -> Array:
    """Solve L q = b (trans=False) or L^T q = b (trans=True).  Differentiable.

    l: (n, n) lower triangular, n a multiple of 128.  b: (n, r) with r a lane
    multiple (ops.py pads vector RHS to (n, 128)).
    """
    return _trsv_vjp(l, b, trans, interpret)
