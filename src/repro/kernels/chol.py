"""Pallas TPU kernel: blocked right-looking Cholesky factorization.

The lag-event refactorization (paper Sec. 4.1: refit the kernel every l
iterations and refactorize fully).  The paper's Alg. 2 is the scalar
three-loop factorization; the TPU-native version is the classic blocked
right-looking schedule with all three stages mapped to the MXU where
possible:

  for each 128-wide block column kb:
    1. factor the 128x128 diagonal block     (VPU outer-product loop)
    2. invert it (unit 128-step solve)        (VPU) — turns the panel TRSM
       into an MXU matmul: panel = A[:, kb] @ inv(L_kk)^T
    3. trailing update A -= panel @ panel^T   (MXU, one 128-row block of
       the trailing submatrix at a time)

The output buffer is the working matrix: the whole (n, n) factor is
VMEM-resident, and every slice of it is a ref slice (static block offsets,
or an aligned `pl.ds` row window) — the TPU compiler lowers no dynamic slice
of an in-register value.  Inside a 128x128 block, row/column j is picked
out by a masked reduction that keeps its 2-D layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

BLOCK = 128
# In-kernel matmuls carry the factor itself, so they run at full f32.
_HI = jax.lax.Precision.HIGHEST
# Diagonal floor of the factorization (same as the pre-blocked kernel).
_DIAG_FLOOR = 1e-12


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def col_of(a: Array, j) -> Array:
    """Column j of a 2-D block as a (rows, 1) value (masked lane reduce)."""
    return jnp.sum(jnp.where(_iota(a.shape, 1) == j, a, 0.0), axis=1,
                   keepdims=True)


def row_of(a: Array, i) -> Array:
    """Row i of a 2-D block as a (1, cols) value (masked sublane reduce)."""
    return jnp.sum(jnp.where(_iota(a.shape, 0) == i, a, 0.0), axis=0,
                   keepdims=True)


def solve_lower_block(l: Array, rhs: Array) -> Array:
    """Forward substitution L X = R on a (B, B) lower block, R (B, r).

    Right-looking: row i of X is final once the rows above it have been
    eliminated, then its outer product with column i of L is subtracted
    from the rows below."""
    b = l.shape[0]
    rows = _iota((b, 1), 0)
    xrows = _iota(rhs.shape, 0)

    def step(i, carry):
        r, x = carry
        lcol = col_of(l, i)                                   # (B, 1)
        lii = row_of(lcol, i)                                 # (1, 1)
        xi = row_of(r, i) / lii                               # (1, r)
        r = r - jnp.where(rows > i, lcol, 0.0) * xi
        return r, jnp.where(xrows == i, xi, x)

    return jax.lax.fori_loop(0, b, step, (rhs, jnp.zeros_like(rhs)))[1]


def solve_upper_block(u: Array, rhs: Array) -> Array:
    """Backward substitution U X = R on a (B, B) upper block, R (B, r)."""
    b = u.shape[0]
    rows = _iota((b, 1), 0)
    xrows = _iota(rhs.shape, 0)

    def step(k, carry):
        r, x = carry
        i = b - 1 - k
        ucol = col_of(u, i)
        uii = row_of(ucol, i)
        xi = row_of(r, i) / uii
        r = r - jnp.where(rows < i, ucol, 0.0) * xi
        return r, jnp.where(xrows == i, xi, x)

    return jax.lax.fori_loop(0, b, step, (rhs, jnp.zeros_like(rhs)))[1]


def _chol_block(a: Array) -> Array:
    """Cholesky of a (B, B) SPD block: right-looking outer-product loop."""
    b = a.shape[0]
    rows = _iota((b, 1), 0)
    cols = _iota((1, b), 1)
    lcols = _iota(a.shape, 1)

    def step(j, carry):
        a, l = carry
        colj = col_of(a, j)                                   # (B, 1)
        d = jnp.sqrt(jnp.maximum(row_of(colj, j), _DIAG_FLOOR))
        lcol = jnp.where(rows >= j, colj / d, 0.0)
        lrow = jnp.where(cols >= j, row_of(a, j) / d, 0.0)    # == lcol^T
        return a - lcol * lrow, jnp.where(lcols == j, lcol, l)

    return jax.lax.fori_loop(0, b, step, (a, jnp.zeros_like(a)))[1]


def _chol_kernel(k_ref, out_ref, *, n_blocks: int):
    n = n_blocks * BLOCK
    out_ref[...] = k_ref[...].astype(jnp.float32)
    eye = (_iota((BLOCK, BLOCK), 0) == _iota((BLOCK, BLOCK), 1)
           ).astype(jnp.float32)
    for kb in range(n_blocks):                   # static block offsets
        s, e = kb * BLOCK, (kb + 1) * BLOCK
        ldiag = _chol_block(out_ref[s:e, s:e])
        out_ref[s:e, s:e] = ldiag
        if e == n:
            break
        linv = solve_lower_block(ldiag, eye)
        panel = jax.lax.dot_general(                          # MXU TRSM
            out_ref[e:, s:e], linv, (((1,), (1,)), ((), ())),
            precision=_HI, preferred_element_type=jnp.float32)
        out_ref[e:, s:e] = panel

        def trailing(rb, _, s=s, e=e):
            # Trailing SYRK update, one 128-row block at a time.
            r0 = pl.multiple_of(rb * BLOCK, BLOCK)
            prow = out_ref[pl.ds(r0, BLOCK), s:e]
            upd = jax.lax.dot_general(
                prow, out_ref[e:, s:e], (((1,), (1,)), ((), ())),
                precision=_HI, preferred_element_type=jnp.float32)
            out_ref[pl.ds(r0, BLOCK), e:] = \
                out_ref[pl.ds(r0, BLOCK), e:] - upd
            return 0

        jax.lax.fori_loop(kb + 1, n_blocks, trailing, 0)
    # Zero the strict upper triangle, one row block at a time.
    for kb in range(n_blocks):
        s, e = kb * BLOCK, (kb + 1) * BLOCK
        out_ref[s:e, s:e] = jnp.where(
            _iota((BLOCK, BLOCK), 0) >= _iota((BLOCK, BLOCK), 1),
            out_ref[s:e, s:e], 0.0)
        if e < n:
            out_ref[s:e, e:] = jnp.zeros((BLOCK, n - e), jnp.float32)


def vmem_limit(buffer_bytes: int) -> int:
    """Scoped-VMEM request for a kernel whose blocks take `buffer_bytes`:
    twice that (batched over a study axis, the blocks become a pipelined,
    double-buffered grid) plus 8 MiB for in-kernel temporaries, never below
    the compiler's 16 MiB default."""
    return max(16 << 20, 2 * buffer_bytes + (8 << 20))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cholesky_pallas(k: Array, *, interpret: bool = False) -> Array:
    """Blocked Cholesky of an SPD (n, n) f32 matrix, n a multiple of 128."""
    n = k.shape[0]
    assert n % BLOCK == 0, n
    kernel = functools.partial(_chol_kernel, n_blocks=n // BLOCK)
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec((n, n), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(k.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(2 * 4 * n * n)),
        interpret=interpret,
    )(k).astype(k.dtype)
