"""Covariance kernel functions for the lazy Gaussian process.

The paper (Sec. 3.2) uses a Matérn-2.5 kernel with fixed length scale rho=1
between lag events; we implement Matérn-1.5/2.5 and squared-exponential, all
vectorized so a full (n x n) covariance build is a single MXU-friendly
pairwise-distance computation (|x|^2 + |y|^2 - 2 x.y^T).

All kernels take `theta = KernelParams(sigma2, rho, noise2)` so that the lag
policy can refit them as a unit.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class KernelParams:
    """Kernel hyper-parameters (the quantities frozen between lag events)."""

    sigma2: Array | float  # signal variance sigma^2
    rho: Array | float  # length scale
    noise2: Array | float  # observation noise sigma_n^2 (jitter)

    @staticmethod
    def default() -> "KernelParams":
        # Paper fixes rho = 1 (Sec. 3.2); noise2 is the numerical jitter that
        # plays the role of sigma^2 I in K_y = k(x, x) + sigma^2 I.
        return KernelParams(sigma2=1.0, rho=1.0, noise2=1e-6)


def pairwise_sqdist(x: Array, y: Array) -> Array:
    """Squared Euclidean distances between rows of x (n,d) and y (m,d).

    Uses the expansion |x-y|^2 = |x|^2 + |y|^2 - 2 x.y^T so the dominant cost
    is one (n,d)x(d,m) matmul — this is the form the Pallas kernel tiles.
    The matmul runs at full f32: the expansion cancels, and at TPU's default
    (one bf16 pass) even |x - x|^2 comes out near 1e-2, which takes the
    self-covariance k(x, x) about 10% below sigma2.
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    cross = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(xx + yy - 2.0 * cross, 0.0)


def matern52(x: Array, y: Array, params: KernelParams) -> Array:
    """Matérn-2.5 kernel matrix (paper Eq. 3, with the exponent sign fixed)."""
    d = jnp.sqrt(pairwise_sqdist(x, y) + 1e-36)
    z = jnp.sqrt(5.0) * d / params.rho
    return params.sigma2 * (1.0 + z + z * z / 3.0) * jnp.exp(-z)


def matern32(x: Array, y: Array, params: KernelParams) -> Array:
    d = jnp.sqrt(pairwise_sqdist(x, y) + 1e-36)
    z = jnp.sqrt(3.0) * d / params.rho
    return params.sigma2 * (1.0 + z) * jnp.exp(-z)


def rbf(x: Array, y: Array, params: KernelParams) -> Array:
    sq = pairwise_sqdist(x, y)
    return params.sigma2 * jnp.exp(-0.5 * sq / (params.rho * params.rho))


KernelFn = Callable[[Array, Array, KernelParams], Array]

# Explicit substrate tag: kernels with a tiled Pallas gram build advertise it
# here, and `repro.kernels.ops.kernel_gram` dispatches on the attribute (a
# name match would silently break for wrapped/renamed kernels).
matern52.pallas_gram = "matern52"

KERNELS: dict[str, KernelFn] = {
    "matern52": matern52,
    "matern32": matern32,
    "rbf": rbf,
}


# --- mixed (continuous x categorical) spaces, DESIGN.md §10 ----------------

def mixed_matern52(x: Array, y: Array, params: KernelParams,
                   cont_mask: Array, cat_mask: Array) -> Array:
    """Mixed-space kernel: Matérn-2.5 over the continuous (float + int)
    coordinates x an exchangeable factor over the one-hot block.

    The categorical factor is `exp(-d²_cat / 2 rho)` — on feasible one-hot
    encodings `d²_cat` is twice the number of differing groups, so this is
    the Hamming-exponential kernel `exp(-h / rho)`; off the lattice it is
    an RBF in the one-hot embedding, PSD everywhere either way, and the
    product with the Matérn term stays PSD.  It carries **no gradient**
    (stop_gradient): the acquisition moves one-hot coordinates by
    round-and-repair projection, never by gradient steps, matching the
    Pallas kernel's continuous-block-only VJP.
    """
    xc, yc = x * cont_mask, y * cont_mask
    d = jnp.sqrt(pairwise_sqdist(xc, yc) + 1e-36)
    z = jnp.sqrt(5.0) * d / params.rho
    sqk = pairwise_sqdist(x * cat_mask, y * cat_mask)
    cat = jax.lax.stop_gradient(jnp.exp(-0.5 * sqk / params.rho))
    return params.sigma2 * (1.0 + z + z * z / 3.0) * jnp.exp(-z) * cat


def make_mixed_kernel(cont_mask: Array, cat_mask: Array) -> KernelFn:
    """Close a `KernelFn` over a space's type masks (from its
    `TypeDescriptor`).  The masks may be concrete `(d,)` arrays or traced
    values (the batched engine builds one closure per study inside its
    vmapped closures); the `pallas_gram = "mixed"` tag routes the gram
    build through the substrate's fused mixed kernel.
    """
    def mixed(x: Array, y: Array, params: KernelParams) -> Array:
        return mixed_matern52(x, y, params, cont_mask, cat_mask)

    mixed.pallas_gram = "mixed"
    mixed.cont_mask = cont_mask
    mixed.cat_mask = cat_mask
    return mixed


def gram(kernel: KernelFn, x: Array, params: KernelParams) -> Array:
    """K_y = k(X, X) + noise2 * I (paper's K + sigma^2 I)."""
    k = kernel(x, x, params)
    return k + params.noise2 * jnp.eye(x.shape[0], dtype=k.dtype)
