"""The linalg substrate: single dispatch surface for every GP operation.

Dispatch policy (`implementation`):
  * "auto"   — Pallas on TPU backends, XLA elsewhere (this CPU container).
  * "pallas" — force Pallas (interpret=True off-TPU; used by the test suite).
  * "xla"    — XLA-native ops (`jnp.linalg.cholesky`, `solve_triangular`).
  * "ref"    — the pure-jnp oracles in `ref.py`.

Every wrapper pads to the kernels' 128-aligned envelope and slices the result
back, so callers never see alignment constraints.

Two families of entry points:

  * Active-shape ops take exact (n, …) arrays.
  * Padded-state ops understand the identity-padded (n_max, n_max) buffers
    of DESIGN.md §3: the active top-left (n, n) block is real data, the
    remainder is the identity, and right-hand sides are zero beyond the
    active block.  These are what `repro.core` dispatches through — no
    direct `solve_triangular` / dense-Cholesky call sites exist above this
    module.

The full dispatch surface (P = real Pallas kernel; x = served by the
implementation; ref column = the jnp oracle in `ref.py`; "batched" = accepts
a leading study axis per DESIGN.md §7):

  op                 | shape contract    | pallas | xla | ref | batched | see
  -------------------|-------------------|--------|-----|-----|---------|------
  matern52_gram      | (n,d)x(m,d) exact |   P    |  x  |  x  | via gram | §6
  mixed_gram         | (n,d)x(m,d) exact |   P    |  x  |  x  | via gram | §10
  trsv               | (n,n),(n[,r])     |   P    |  x  |  x  | no*      | §6
  cholesky           | (n,n) SPD         |   P    |  x  |  x  | no*      | §6
  chol_append        | active factor     |   P    |  x  |  x  | no*      | §6
  gp_posterior_solve | active factor     |   P    |  x  |  x  | no*      | §6
  kernel_gram        | any kernel fn     |   P†   |  x  |  x  | yes      | §6
  masked_gram        | padded buffers    |   -    |  x  |  x  | yes      | §3,§4
  padded_trsv        | padded buffers    |   P    |  x  |  x  | yes      | §3
  padded_cholesky    | padded buffers    |   P    |  x  |  x  | yes      | §3
  padded_tri_inverse | padded buffers    |   P    |  x  |  x  | yes      | §4
  padded_append_row  | padded buffers    |   ‡    |  ‡  |  ‡  | yes      | §4,§7
  lazy_append        | padded buffers    |   ‡    |  ‡  |  ‡  | yes      | §4,§7
  lazy_append_rows   | padded buffers    |   ‡    |  ‡  |  ‡  | yes      | §4,§12
  fused_ei_grad      | (r,d) + padded    |   P§   |  x  |  x  | yes      | §11

  *  active-shape ops serve the tests and naive baselines; the batched hot
     path runs exclusively on the padded-state ops below them.
  †  Pallas gram build applies when the kernel fn opts in via its
     `pallas_gram` attribute (Matérn-2.5 does); other kernels fall back to
     their own jnp formulation under every implementation.
  §  fused EI value+gradient megakernel (`kernels/acq.py`): one streaming
     pass per ascent step for the whole restart batch, block size picked by
     the autotuner below (`acq_tile_config`); xla/ref serve the identical
     math as one fused XLA program (`ei_grad_jnp`), which is also the
     beyond-VMEM fallback.
  ‡  matmul-only against the maintained inverse factor: mathematically the
     same on every substrate (no dispatch below the entry point), which is
     what keeps the batched/sharded study axis on the native GEMM path
     (DESIGN.md §7/§8).

The padded-state ops are **rank-polymorphic over a leading study axis**
(DESIGN.md §7): stacked `(S, n_max, …)` buffers with a per-study active
count `n (S,)` dispatch through `jax.vmap` of the single-study path, so one
jitted program advances S independent factors at once.  The Pallas kernels
batch through `pallas_call`'s native batching rule (the study axis becomes a
grid dimension) and the custom VJPs vmap with them, so the batched path is
differentiable on every substrate.

**The appends are matmul-based against a maintained inverse factor.**  The
steady-state transitions (`padded_append_row`, `lazy_append`) take the
identity-padded inverse `li_buf = L^{-1}` alongside the factor and compute
the paper's row solve as the matvec `q = L^{-1} p`, updating the inverse
with the closed-form bordered-inverse row
`L'^{-1} = [[L^{-1}, 0], [-(1/d) q^T L^{-1}, 1/d]]` — O(n_max^2) like the
paper's solve, but expressed entirely as matmuls.  This is what makes the
batched study axis fast everywhere: batched triangular solves lower
pathologically on some backends (XLA CPU runs them ~100x slower per element
than the unbatched LAPACK call), while batched matmuls hit the native GEMM
path on every backend (and the MXU on TPU).  Triangular solves survive only
in the rare lag-event refactorization (`padded_tri_inverse`) and in the
`trsv` entry points the tests and the naive baselines exercise.

`lazy_append` is the fused paper-Alg. 3 step: row append + inverse update +
alpha refresh in four matvec passes over one factor residency.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import time
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import acq as acq_kernels
from repro.kernels import ref
from repro.kernels.chol import cholesky_pallas
from repro.kernels.matern import matern52_gram_pallas
from repro.kernels.mixed import mixed_gram_pallas
from repro.kernels.trsv import trsv_pallas

Array = jax.Array

ALIGN = 128
# Whole-factor VMEM residency bound of the Cholesky and trsv kernels (f32).
# At 2048 the Cholesky's input and output blocks take 32 MiB; the kernels
# request that scoped VMEM explicitly (`chol.vmem_limit`), and
# tests/test_tpu_compile.py compiles both at this bound for a v5e.
MAX_PALLAS_N = 2048
# Floor for the squared new-diagonal d^2 = c - q.q in the incremental append.
# The paper's lemma guarantees d^2 > 0 in exact arithmetic; hitting this floor
# means float32 ill-conditioning, which the padded ops report to callers.
CLAMP_EPS = 1e-10

IMPLEMENTATIONS = ("auto", "pallas", "xla", "ref")


class KernelFallbackWarning(UserWarning):
    """A call that would run a Pallas kernel exceeds the kernel's size bound
    and is served on the XLA path instead (raised once per call site and
    size, at trace time)."""


def _warn_fallback(op: str, n: int, bound: int) -> None:
    warnings.warn(
        f"{op}: n={n} exceeds the Pallas kernel's bound {bound}; this call "
        "runs on the XLA path", KernelFallbackWarning, stacklevel=3)


def state_matmul(a: Array, b: Array) -> Array:
    """Matmul against the maintained inverse `li_buf` at full f32.

    TPU runs a default-precision f32 matmul as one bf16 pass.  The posterior
    variance `k** - |li_buf k*|^2` then erred by up to 0.3 sigma2 at n = 259
    on a v5e, and the EI variance `k^T A k` with `A = li_buf^T li_buf` is
    the same sum.  (The appends' matvecs stayed exact at either precision
    there, so they keep the default.)"""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(implementation: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if implementation == "pallas":
        return True, not _on_tpu()
    if implementation == "auto":
        return _on_tpu(), False
    return False, False


def _pad_to(x: Array, n: int, axis: int) -> Array:
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _round_up(n: int) -> int:
    return ((n + ALIGN - 1) // ALIGN) * ALIGN


def matern52_gram(x: Array, y: Array, sigma2, rho,
                  implementation: str = "auto") -> Array:
    """Pairwise Matérn-2.5 covariance, arbitrary (n, d) x (m, d)."""
    use, interp = _use_pallas(implementation)
    if implementation == "ref" or not use:
        return ref.matern52_gram_ref(x, y, sigma2, rho)
    n, m = x.shape[0], y.shape[0]
    npad, mpad = _round_up(n), _round_up(m)
    dpad = _round_up(x.shape[1])
    # Zero-padding features is exact for squared distances; padded rows
    # produce garbage covariances that are sliced away below.
    xp = _pad_to(_pad_to(x, npad, 0), dpad, 1)
    yp = _pad_to(_pad_to(y, mpad, 0), dpad, 1)
    out = matern52_gram_pallas(xp, yp, sigma2, rho, interpret=interp)
    return out[:n, :m]


def mixed_gram(x: Array, y: Array, sigma2, rho, cont_mask: Array,
               cat_mask: Array, implementation: str = "auto") -> Array:
    """Mixed-space covariance (DESIGN.md §10): Matérn-2.5 over the
    continuous coordinates x exchangeable/Hamming factor over the one-hot
    block.  Masks are (d,) 0/1 selectors from the space's TypeDescriptor;
    zero-padding features is exact (a coordinate masked out of both blocks
    contributes to neither squared distance)."""
    use, interp = _use_pallas(implementation)
    if implementation == "ref" or not use:
        return ref.mixed_gram_ref(x, y, sigma2, rho, cont_mask, cat_mask)
    n, m = x.shape[0], y.shape[0]
    npad, mpad = _round_up(n), _round_up(m)
    dpad = _round_up(x.shape[1])
    # The mask split happens here (outside the custom VJP), so the zero
    # cotangent on the categorical operands chain-rules to
    # dx = cont_mask * dxc — the continuous-block-only gradient contract.
    cm = _pad_to(cont_mask.astype(x.dtype), dpad, 0)
    km = _pad_to(cat_mask.astype(x.dtype), dpad, 0)
    xp = _pad_to(_pad_to(x, npad, 0), dpad, 1)
    yp = _pad_to(_pad_to(y, mpad, 0), dpad, 1)
    out = mixed_gram_pallas(xp * cm, yp * cm, xp * km, yp * km,
                            sigma2, rho, interpret=interp)
    return out[:n, :m]


def trsv(l: Array, b: Array, *, trans: bool = False,
         implementation: str = "auto") -> Array:
    """Triangular solve L q = b / L^T q = b; b (n,) or (n, r)."""
    use, interp = _use_pallas(implementation)
    if use and l.shape[0] > MAX_PALLAS_N:
        _warn_fallback("trsv", l.shape[0], MAX_PALLAS_N)
    if implementation == "ref" or not use or l.shape[0] > MAX_PALLAS_N:
        return ref.trsv_ref(l, b, trans=trans)
    n = l.shape[0]
    npad = _round_up(n)
    vec = b.ndim == 1
    b2 = b[:, None] if vec else b
    rpad = _round_up(b2.shape[1])
    lp = _pad_to(_pad_to(l, npad, 0), npad, 1)
    # Identity-pad the factor so padded solves stay well-defined.
    if npad != n:
        idx = jnp.arange(npad)
        lp = jnp.where((idx[:, None] == idx[None, :]) & (idx[:, None] >= n),
                       1.0, lp)
    bp = _pad_to(_pad_to(b2, npad, 0), rpad, 1)
    q = trsv_pallas(lp, bp, trans=trans, interpret=interp)[:n, : b2.shape[1]]
    return q[:, 0] if vec else q


def cholesky(k: Array, implementation: str = "auto") -> Array:
    """Blocked Cholesky of an SPD matrix (lower factor)."""
    use, interp = _use_pallas(implementation)
    if use and k.shape[0] > MAX_PALLAS_N:
        _warn_fallback("cholesky", k.shape[0], MAX_PALLAS_N)
    if implementation == "ref" or not use or k.shape[0] > MAX_PALLAS_N:
        return ref.cholesky_ref(k)
    n = k.shape[0]
    npad = _round_up(n)
    kp = _pad_to(_pad_to(k, npad, 0), npad, 1)
    if npad != n:
        idx = jnp.arange(npad)
        kp = jnp.where((idx[:, None] == idx[None, :]) & (idx[:, None] >= n),
                       1.0, kp)
    return cholesky_pallas(kp, interpret=interp)[:n, :n]


def chol_append(l: Array, p: Array, c: Array,
                implementation: str = "auto") -> tuple[Array, Array]:
    """Fused incremental append on the active factor: q = L^{-1}p, d."""
    q = trsv(l, p, implementation=implementation)
    d = jnp.sqrt(jnp.maximum(c - q @ q, CLAMP_EPS))
    return q, d


def gp_posterior_solve(l: Array, resid: Array, k_star: Array, k_ss_diag: Array,
                       implementation: str = "auto") -> tuple[Array, Array]:
    """Fused GP posterior solves (mean, var) sharing one factor residency."""
    if implementation == "ref":
        return ref.gp_posterior_solve_ref(l, resid, k_star, k_ss_diag)
    z = trsv(l, resid, implementation=implementation)
    alpha = trsv(l, z, trans=True, implementation=implementation)
    v = trsv(l, k_star, implementation=implementation)
    mean = k_star.T @ alpha
    var = jnp.maximum(k_ss_diag - jnp.sum(v * v, axis=0), 1e-12)
    return mean, var


# ---------------------------------------------------------------------------
# Padded-state ops: the identity-padded (n_max, n_max) buffers of DESIGN.md §3.
# ---------------------------------------------------------------------------

def check_implementation(implementation: str) -> str:
    """Validate the dispatch knob early (host-side, before any tracing)."""
    if implementation not in IMPLEMENTATIONS:
        raise ValueError(
            f"unknown implementation {implementation!r}; "
            f"expected one of {IMPLEMENTATIONS}")
    return implementation


def padded_trsv(l_buf: Array, b: Array, *, trans: bool = False,
                implementation: str = "auto") -> Array:
    """Triangular solve on the identity-padded factor buffer.

    Exact for right-hand sides that are zero beyond the active block (rows
    >= n have zeros left of a unit diagonal), which is the invariant every
    padded GP solve relies on.  Same dispatch as `trsv`; named separately so
    call sites document which shape contract they use.

    Batched form: `l_buf (S, n_max, n_max)` with `b (S, n_max)` or
    `(S, n_max, r)` solves S independent systems in one dispatch.
    """
    if l_buf.ndim == 3:
        return jax.vmap(lambda l, rhs: padded_trsv(
            l, rhs, trans=trans, implementation=implementation))(l_buf, b)
    return trsv(l_buf, b, trans=trans, implementation=implementation)


def padded_cholesky(k_pad: Array, implementation: str = "auto") -> Array:
    """Blocked Cholesky of an identity-padded Gram buffer.

    The identity padding is SPD, and the factor of a block-diagonal
    [[K, 0], [0, I]] matrix is [[L, 0], [0, I]] — so factoring the padded
    buffer directly yields the identity-padded factor the lazy state stores.

    Batched form: `k_pad (S, n_max, n_max)` factors S buffers in one
    dispatch.
    """
    if k_pad.ndim == 3:
        return jax.vmap(lambda k: padded_cholesky(
            k, implementation=implementation))(k_pad)
    return cholesky(k_pad, implementation=implementation)


def kernel_gram(kernel_fn, x: Array, y: Array, params,
                implementation: str = "auto") -> Array:
    """Covariance build through the substrate.

    Kernel functions opt into a Pallas build by carrying a `pallas_gram`
    attribute naming their kernel (set by `repro.core.kernels`); anything
    else — including wrappers that drop the attribute — falls back to the
    kernel's own jnp formulation (already one fused MXU-friendly matmul
    under XLA).  `params` is duck-typed: needs `.sigma2` and `.rho`.
    """
    use, _ = _use_pallas(implementation)
    tag = getattr(kernel_fn, "pallas_gram", None)
    if use and tag == "matern52":
        return matern52_gram(x, y, params.sigma2, params.rho,
                             implementation=implementation)
    if use and tag == "mixed":
        return mixed_gram(x, y, params.sigma2, params.rho,
                          kernel_fn.cont_mask, kernel_fn.cat_mask,
                          implementation=implementation)
    return kernel_fn(x, y, params)


def masked_gram(x_buf: Array, n: Array, kernel_fn, params,
                implementation: str = "auto") -> Array:
    """Full identity-padded Gram K + noise2 I over the padded point buffer.

    Rows/cols >= n are replaced by the identity so `padded_cholesky` of the
    result is the identity-padded factor (the lag-event refactorization
    input).  `n` may be traced; the output shape is always (n_max, n_max).

    Row i is the kernel of the differences `x_buf - x_i` against the origin
    (every kernel here is stationary), so each squared distance is a plain
    sum of squares, as in the appends' covariance columns (`gp._cov_column`):
    the expanded |x|^2 + |y|^2 - 2 x.y^T cancels for near-duplicate points
    with a float32 error above a 1e-5 noise floor.  An O(n_max^2 d)
    elementwise build, so it runs as plain jnp for every implementation.

    Batched form: `x_buf (S, n_max, d)` with per-study `n (S,)` and `params`
    whose leaves carry a leading `(S,)` axis builds S padded Grams in one
    dispatch.
    """
    del implementation  # elementwise: no substrate dispatch below this line
    if x_buf.ndim == 3:
        return jax.vmap(lambda xb, nn, pp: masked_gram(
            xb, nn, kernel_fn, pp))(x_buf, n, params)
    n_max, d = x_buf.shape
    origin = jnp.zeros((1, d), x_buf.dtype)
    k = jax.vmap(lambda xi: kernel_fn(x_buf - xi[None, :], origin,
                                      params)[:, 0])(x_buf)
    eye = jnp.eye(n_max, dtype=k.dtype)
    k = k + params.noise2 * eye
    idx = jnp.arange(n_max)
    active = (idx[:, None] < n) & (idx[None, :] < n)
    return jnp.where(active, k, eye)


def write_append_row(buf: Array, q: Array, d: Array, n: Array) -> Array:
    """Replace row n of a padded triangular buffer with [q^T, d, 0, ...]."""
    n_max = buf.shape[0]
    row = jnp.where(jnp.arange(n_max) < n, q, 0.0).at[n].set(d)
    return jax.lax.dynamic_update_slice(buf, row[None, :], (n, 0))


def padded_tri_inverse(l_buf: Array, *,
                       implementation: str = "auto") -> Array:
    """Identity-padded inverse of the identity-padded factor: `L^{-1}`.

    Solving `L X = I` on the padded buffer yields `[[L^{-1}, 0], [0, I]]`
    directly (the identity block is self-inverse).  One O(n_max^3) solve —
    only runs at refactor events; the appends maintain the inverse
    incrementally in O(n_max^2).

    Batched form: `(S, n_max, n_max)` inverts every study in one dispatch.
    """
    if l_buf.ndim == 3:
        return jax.vmap(lambda l: padded_tri_inverse(
            l, implementation=implementation))(l_buf)
    eye = jnp.eye(l_buf.shape[0], dtype=l_buf.dtype)
    return padded_trsv(l_buf, eye, implementation=implementation)


def padded_append_row(l_buf: Array, li_buf: Array, p_pad: Array, c: Array,
                      n: Array, *, implementation: str = "auto"
                      ) -> tuple[Array, Array, Array, Array]:
    """Paper Alg. 3 row append on the padded factor + inverse, O(n_max^2).

    The row solve is the matvec `q = L^{-1} p` against the maintained
    inverse, and the inverse grows by the closed-form bordered row
    `[-(1/d) q^T L^{-1}, 1/d]` — no triangular solve anywhere, so the op
    batches over a study axis at native GEMM speed (see module docstring).

    Args:
      l_buf: (n_max, n_max) identity-padded factor of K_n + noise I.
      li_buf: (n_max, n_max) identity-padded inverse factor L^{-1}.
      p_pad: (n_max,) new covariance column k(X, x_new), zero beyond n.
      c: scalar k(x_new, x_new) + noise.
      n: active count (traced int32); the new row lands at index n.

    Returns (l_new, li_new, d, clamped) where `clamped` is 1 iff d^2 hit
    the CLAMP_EPS conditioning floor (float32 breakdown — DESIGN.md §6).

    Batched form: `(S, n_max, n_max)` factors/inverses with `(S, n_max)`
    columns, `(S,)` self-covariances and per-study `n (S,)` append one row
    per study in one dispatch.
    """
    del implementation  # matmul-only: no substrate dispatch below this line
    if l_buf.ndim == 3:
        return jax.vmap(lambda l, li, p, cc, nn: padded_append_row(
            l, li, p, cc, nn))(l_buf, li_buf, p_pad, c, n)
    # Rows >= n of li are identity and p is zero there, so q is exact and
    # already zero beyond the active block.
    q = li_buf @ p_pad
    d2 = c - q @ q
    clamped = (d2 < CLAMP_EPS).astype(jnp.int32)
    d = jnp.sqrt(jnp.maximum(d2, CLAMP_EPS))
    l_new = write_append_row(l_buf, q, d, n)
    # Bordered inverse: row n of L'^{-1} is [-(1/d) q^T L^{-1}, 1/d].
    r = -(q @ li_buf) / d
    li_new = write_append_row(li_buf, r, 1.0 / d, n)
    return l_new, li_new, d, clamped


def lazy_append(l_buf: Array, li_buf: Array, p_pad: Array, c: Array,
                resid: Array, n: Array, *, implementation: str = "auto"
                ) -> tuple[Array, Array, Array, Array, Array]:
    """Fused Alg. 3 append: row + inverse update + alpha refresh, O(n_max^2).

    Four matvec passes per observation — `q = L^{-1} p`, the bordered
    inverse row `-(1/d) q^T L^{-1}`, and the alpha refresh
    `alpha = L'^{-T} (L'^{-1} r)` as two matvecs against the new inverse.
    All GEMM traffic: the op batches over a study axis with no pathological
    batched-triangular-solve lowering on any backend.

    Args:
      resid: (n_max,) residual y - mean *including* the new observation at
        row n, zero beyond row n.

    Returns (l_new, li_new, alpha, d, clamped).

    Batched form: stacked `(S, n_max, …)` operands with per-study `n (S,)`
    run S fused appends in one dispatch (heterogeneous active counts are
    fine — each study's row lands at its own index).
    """
    del implementation  # matmul-only: no substrate dispatch below this line
    if l_buf.ndim == 3:
        return jax.vmap(lambda l, li, p, cc, r, nn: lazy_append(
            l, li, p, cc, r, nn))(l_buf, li_buf, p_pad, c, resid, n)
    n_max = l_buf.shape[0]
    idx = jnp.arange(n_max)
    l_new, li_new, d, clamped = padded_append_row(l_buf, li_buf, p_pad, c, n)
    # alpha = (K' + noise I)^{-1} r = L'^{-T} (L'^{-1} r); rows/cols >= n+1
    # of the padded inverse are identity against a zero-padded residual, so
    # the padded matvecs are exact and alpha is zero beyond the new row.
    z = li_new @ resid
    alpha = z @ li_new           # == li_new.T @ z
    return l_new, li_new, jnp.where(idx <= n, alpha, 0.0), d, clamped


def lazy_append_rows(l_buf: Array, li_buf: Array, p_pads: Array, cs: Array,
                     resid: Array, n: Array, *, implementation: str = "auto"
                     ) -> tuple[Array, Array, Array, Array, Array]:
    """Append q bordered rows + one alpha refresh in a single dispatch.

    The q-suggestion fast path (DESIGN.md §12): q sequential Alg. 3 border
    steps — row i lands at index n + i — followed by ONE fused alpha refresh
    against the final inverse.  Each border step is the same matmul-only
    bordered-inverse math as `padded_append_row`, so the whole op stays on
    the native GEMM path and batches over a study axis.  The alpha solves
    run once per call instead of once per row, matching the deferred-alpha
    economics of `append_batch` at the substrate level.

    Args:
      p_pads: (q, n_max) covariance columns; row i is the covariance of the
        i-th new point against the first n + i rows of the *final* point
        buffer (actives plus earlier new points), zero beyond index n + i.
      cs: (q,) self-covariances k(x_i, x_i) + noise.
      resid: (n_max,) residual y - mean *including* all q new rows, zero
        beyond row n + q - 1.
      n: active count before the appends (traced int32).

    Returns (l_new, li_new, alpha, ds (q,), clamped) where `clamped` counts
    how many of the q rows hit the CLAMP_EPS conditioning floor.

    Batched form: stacked `(S, n_max, …)` buffers with `(S, q, n_max)`
    columns, `(S, q)` self-covariances and per-study `n (S,)` run S × q
    appends in one dispatch.
    """
    del implementation  # matmul-only: no substrate dispatch below this line
    if l_buf.ndim == 3:
        return jax.vmap(lambda l, li, p, cc, r, nn: lazy_append_rows(
            l, li, p, cc, r, nn))(l_buf, li_buf, p_pads, cs, resid, n)
    n_max = l_buf.shape[0]
    q_rows = p_pads.shape[0]

    def body(i, carry):
        l, li, ds, cl = carry
        l2, li2, d, c2 = padded_append_row(l, li, p_pads[i], cs[i], n + i)
        return l2, li2, ds.at[i].set(d), cl + c2

    l_new, li_new, ds, clamped = jax.lax.fori_loop(
        0, q_rows, body,
        (l_buf, li_buf, jnp.zeros((q_rows,), l_buf.dtype),
         jnp.asarray(0, jnp.int32)))
    idx = jnp.arange(n_max)
    z = li_new @ resid
    alpha = z @ li_new           # == li_new.T @ z
    return (l_new, li_new, jnp.where(idx < n + q_rows, alpha, 0.0),
            ds, clamped)


# ---------------------------------------------------------------------------
# Fused EI-ascent megakernel + block-size autotuner (DESIGN.md §11).
# ---------------------------------------------------------------------------

# Whole-A VMEM residency bound for the megakernel (f32): 1024^2 * 4 B = 4 MiB
# for A alone, double-buffered under the study-axis grid; beyond this the
# fused jnp formulation takes over (with a KernelFallbackWarning).
MAX_ACQ_PALLAS_N = 1024
# Candidate-tile row counts the autotuner races (all >= the f32 sublane
# minimum of 8; the default restart count R = 64 pads to one or two tiles).
ACQ_BLOCK_R_CANDIDATES = (16, 32, 64, 128, 256)
ACQ_DEFAULT_BLOCK_R = 128


@dataclasses.dataclass(frozen=True)
class AcqTileConfig:
    """One tuned tile choice for the megakernel.

    `measured` distinguishes a raced-and-timed pick from the heuristic
    fallback (interpret mode, or autotuning disabled via
    `REPRO_ACQ_AUTOTUNE=off`).
    """

    block_r: int    # candidate-tile rows per grid step
    d_pad: int      # feature-depth envelope (next_power_of_2, lane-aligned)
    measured: bool


# Cache key: (n_pad, d, S, substrate).  Lifecycle = process lifetime; the
# first fused trace per key pays the (tiny) measurement, every retrace and
# every jit cache hit after that is free.  Tests reset it directly.
_ACQ_TUNE_CACHE: dict[tuple, AcqTileConfig] = {}


def next_power_of_2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _acq_autotune_enabled() -> bool:
    """`REPRO_ACQ_AUTOTUNE=off|0|false` pins the heuristic config (and
    bypasses the cache entirely) so CI can prove correctness does not
    depend on any measured tile choice."""
    return os.environ.get("REPRO_ACQ_AUTOTUNE", "on").strip().lower() \
        not in ("off", "0", "false")


def _measure_acq_config(block_r: int, d_pad: int, n_pad: int, s: int) -> float:
    """Wall-time one tile config on dummy operands (best of 3, seconds).

    Only meaningful on a compiled backend; `acq_tile_config` never calls it
    in interpret mode, and calls it from a thread of its own, so that even
    inside a trace the operands are concrete arrays and the kernel really
    runs on the device before `block_until_ready` returns.  Measures
    the single-study call — the study axis batches to an extra grid
    dimension, which scales every candidate equally and preserves the
    ranking.
    """
    del s
    r = 2 * block_r
    xc = jnp.zeros((r, d_pad), jnp.float32)
    xbc = jnp.zeros((n_pad, d_pad), jnp.float32)
    row = jnp.zeros((1, n_pad), jnp.float32)
    ab = jnp.zeros((n_pad, n_pad), jnp.float32)
    args = (xc, xbc, row, row, ab, 1.0, 0.25, 0.0)

    def run():
        ei, g = acq_kernels.fused_ei_grad_pallas(
            *args, block_r=block_r, interpret=False)
        jax.block_until_ready((ei, g))

    run()  # compile + warm up
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def acq_tile_config(n_pad: int, d: int, s: int, interpret: bool,
                    *, measure_fn=None) -> AcqTileConfig:
    """Pick the megakernel tile config for a `(n_pad, d, S, substrate)` key.

    Heuristic default: `block_r = 128` (one MXU-sized candidate tile) and
    `d_pad = max(128, next_power_of_2(d))`.  On a compiled backend the
    candidates in `ACQ_BLOCK_R_CANDIDATES` are raced once and the winner is
    cached per key; interpret mode keeps the heuristic (interpreter
    timings reflect the emulator, not the hardware) so CPU-emulated runs
    stay deterministic.  `measure_fn(block_r, d_pad, n_pad, s) -> seconds`
    is injectable for tests.  Runs host-side at trace time — the choice is
    baked into the jitted program.  The race runs in a worker thread: JAX's
    trace state is per thread, so the race times the kernel on the device
    and not the tracing of it.
    """
    d_pad = max(ALIGN, next_power_of_2(d))
    heuristic = AcqTileConfig(block_r=ACQ_DEFAULT_BLOCK_R, d_pad=d_pad,
                              measured=False)
    if not _acq_autotune_enabled():
        return heuristic
    key = (n_pad, d, s, "interpret" if interpret else "compiled")
    hit = _ACQ_TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    if measure_fn is None and interpret:
        cfg = heuristic
    else:
        fn = measure_fn or _measure_acq_config
        with concurrent.futures.ThreadPoolExecutor(1) as worker:
            times = worker.submit(lambda: [
                float(fn(block_r, d_pad, n_pad, s))
                for block_r in ACQ_BLOCK_R_CANDIDATES]).result()
        best = ACQ_BLOCK_R_CANDIDATES[times.index(min(times))]
        cfg = AcqTileConfig(block_r=best, d_pad=d_pad, measured=True)
    _ACQ_TUNE_CACHE[key] = cfg
    return cfg


def tuned_acq_tiles() -> dict[tuple, AcqTileConfig]:
    """The tile configs picked so far, keyed `(n_pad, d, S, substrate)`."""
    return dict(_ACQ_TUNE_CACHE)


def fused_supported(kernel_fn, acq_name: str) -> bool:
    """True iff the fused megakernel covers this (kernel, acquisition)
    pair: EI over the Matérn-2.5 / mixed kernels (the `pallas_gram` tags).
    Anything else takes the generic autodiff ascent."""
    return acq_name == "ei" and \
        getattr(kernel_fn, "pallas_gram", None) in ("matern52", "mixed")


def fused_ei_grad(x: Array, x_buf: Array, amask: Array, alpha: Array,
                  a_buf: Array, sigma2, rho, shift, *,
                  cont_mask: Array | None = None,
                  cat_mask: Array | None = None,
                  implementation: str = "auto",
                  tune_s: int = 1) -> tuple[Array, Array]:
    """Fused EI value + gradient for a whole (r, d) candidate batch.

    One ascent iteration of the multi-start EI optimizer as a single
    dispatch (DESIGN.md §11): cross-gram, posterior mean/var through the
    hoisted `a_buf = li_buf^T li_buf`, EI, and the analytic EI gradient.

    Args:
      x: (r, d) candidate batch (the restart set).
      x_buf: (n_max, d) padded train buffer.
      amask: (n_max,) 0/1 active-row mask.
      alpha: (n_max,) padded weights, zero beyond the active block.
      a_buf: (n_max, n_max) hoisted A = li_buf^T li_buf.
      sigma2, rho: kernel hyper-parameters.
      shift: hoisted scalar ymean - f_best - xi.
      cont_mask/cat_mask: (d,) type masks for mixed spaces (None = float).
      tune_s: study count for the autotuner key (the batched suggest path
        passes its S; the kernel itself batches via vmap).

    Returns (ei (r,), grad (r, d)).  The mask split for mixed spaces
    happens here, so the gradient is zero on categorical coordinates by
    construction (the continuous-block-only contract).

    Batched: a leading study axis on the state-side operands (and scalar
    leaves) vmaps through — the Pallas kernel via its native batching
    rule, the jnp path natively.
    """
    use, interp = _use_pallas(implementation)
    n_max = x_buf.shape[0]
    if use and n_max > MAX_ACQ_PALLAS_N:
        _warn_fallback("fused_ei_grad", n_max, MAX_ACQ_PALLAS_N)
    if not use or n_max > MAX_ACQ_PALLAS_N:
        return acq_kernels.ei_grad_jnp(
            x, x_buf, amask.astype(x.dtype), alpha, a_buf, sigma2, rho,
            shift, cont_mask=cont_mask, cat_mask=cat_mask)
    r, d = x.shape
    n_pad = _round_up(n_max)
    cfg = acq_tile_config(n_pad, d, tune_s, interp)
    r_pad = ((r + cfg.block_r - 1) // cfg.block_r) * cfg.block_r
    # Zero-padding is exact everywhere it matters: features cancel in the
    # squared distances, padded train rows are masked out of K by `amask`,
    # and padded candidate rows compute garbage that is sliced away.
    xp = _pad_to(_pad_to(x, r_pad, 0), cfg.d_pad, 1)
    xbp = _pad_to(_pad_to(x_buf, n_pad, 0), cfg.d_pad, 1)
    amp = _pad_to(amask.astype(x.dtype), n_pad, 0)[None, :]
    alp = _pad_to(alpha, n_pad, 0)[None, :]
    abp = _pad_to(_pad_to(a_buf, n_pad, 0), n_pad, 1)
    if cont_mask is not None:
        cm = _pad_to(cont_mask.astype(x.dtype), cfg.d_pad, 0)
        km = _pad_to(cat_mask.astype(x.dtype), cfg.d_pad, 0)
        ei, g = acq_kernels.fused_ei_grad_pallas(
            xp * cm, xbp * cm, amp, alp, abp, sigma2, rho, shift,
            xk=xp * km, xbk=xbp * km, block_r=cfg.block_r,
            interpret=interp)
    else:
        ei, g = acq_kernels.fused_ei_grad_pallas(
            xp, xbp, amp, alp, abp, sigma2, rho, shift,
            block_r=cfg.block_r, interpret=interp)
    return ei[:r], g[:r, :d]
