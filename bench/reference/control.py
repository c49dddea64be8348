"""The precision control: the reference, computed in float32 with each
matmul taken in fewer bfloat16 passes than the configuration states.

The configurations state float32 with the GP-state matmuls at HIGHEST (six
bfloat16 passes on a TPU).  The nearest precision below is HIGH, three
passes (bf16_3x); one pass is TPU's DEFAULT.  The passes are spelled out
here (split each float32 operand into a bfloat16 head and tail, multiply
the parts in bfloat16 with float32 accumulation), so the passes do not
depend on a backend's matmul-precision default; the CPU backend ignores
that default.  The computation mirrors the served path's structure: the Gram
by the distance expansion, and the posterior through the inverse factor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PASSES = {"default": 1, "high": 3}


def _split(a):
    """a = head + tail + O(2^-18 |a|), head and tail bfloat16.  The head
    is each float32 rounded to its top 16 bits (exact in bfloat16) by
    integer arithmetic on its bits, not by a round trip through bfloat16,
    which an XLA backend may drop as excess precision (it then reads a
    tail of 0: one pass)."""
    bits = (jax.lax.bitcast_convert_type(a, jnp.uint32) + jnp.uint32(
        0x8000)) & jnp.uint32(0xFFFF0000)
    head = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return head.astype(jnp.bfloat16), (a - head).astype(jnp.bfloat16)


def matmul(a, b, passes: int):
    """a @ b in float32 from `passes` bfloat16 products (1 or 3)."""
    dot = lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32)
    ah, at = _split(a)
    bh, bt = _split(b)
    out = dot(ah, bh)
    if passes >= 3:
        out = out + dot(ah, bt) + dot(at, bh)
    return out


def _matern52(a, b, sigma2, rho, passes):
    aa = jnp.sum(a * a, axis=-1)[:, None]
    bb = jnp.sum(b * b, axis=-1)[None, :]
    d2 = jnp.maximum(aa + bb - 2.0 * matmul(a, b.T, passes), 0.0)
    z = jnp.sqrt(5.0) * jnp.sqrt(d2 + 1e-36) / rho
    return sigma2 * (1.0 + z + z * z / 3.0) * jnp.exp(-z)


PAD = 128


def posterior(x, y, xq, sigma2: float, rho: float, noise2: float,
              precision: str) -> tuple[np.ndarray, np.ndarray]:
    """Posterior mean and variance at `xq`, float32 at `precision`.

    The history is padded to a multiple of 128 rows with an identity block
    (exact: padded rows carry no covariance and no residual), so a run
    compiles one program per block count, not one per history length."""
    n, m = len(y), len(xq)
    n_pad = -(-n // PAD) * PAD
    m_pad = -(-m // PAD) * PAD
    xp = np.zeros((n_pad, np.shape(x)[1]), np.float32)
    xp[:n] = x
    yp = np.zeros((n_pad,), np.float32)
    yp[:n] = np.asarray(y, np.float32) - np.float32(np.mean(y))
    qp = np.zeros((m_pad, np.shape(xq)[1]), np.float32)
    qp[:m] = xq
    mask = np.zeros((n_pad,), np.float32)
    mask[:n] = 1.0
    mean, var = _padded(jnp.asarray(xp), jnp.asarray(yp), jnp.asarray(qp),
                        jnp.asarray(mask), jnp.float32(sigma2),
                        jnp.float32(rho), jnp.float32(noise2), PASSES[
                            precision])
    mean = np.asarray(mean, np.float64)[:m] + float(np.mean(y, dtype=np.float32))
    return mean, np.asarray(var, np.float64)[:m]


@functools.partial(jax.jit, static_argnums=(7,))
def _padded(x, resid, xq, mask, sigma2, rho, noise2, p):
    n = x.shape[0]
    both = mask[:, None] * mask[None, :]
    eye = jnp.eye(n, dtype=jnp.float32)
    k = _matern52(x, x, sigma2, rho, p) * both + noise2 * eye
    k = jnp.where(both > 0, k, eye)
    with jax.default_matmul_precision("highest"):
        chol = jnp.linalg.cholesky(k)
        li = jax.scipy.linalg.solve_triangular(chol, eye, lower=True)
    z = matmul(li, resid[:, None], p)
    alpha = matmul(li.T, z, p)[:, 0] * mask
    ks = _matern52(x, xq, sigma2, rho, p) * mask[:, None]
    mean = matmul(ks.T, alpha[:, None], p)[:, 0]
    v = matmul(li, ks, p)
    var = jnp.maximum(sigma2 - jnp.sum(v * v, axis=0), 1e-12)
    return mean, var
