"""Plain exact GP posterior and expected improvement, in float64 numpy.

The yardstick for every cell's `correct`: a textbook Matérn-5/2 GP
(Rasmussen & Williams, Alg. 2.1) over a study's told history, with the
prior mean taken as the mean of the observations, and EI in its
maximisation form.  Squared distances are taken as differences, not by the
|a|^2 + |b|^2 - 2ab expansion, so nothing here cancels.
"""
from __future__ import annotations

import numpy as np
from scipy.linalg import cho_solve, cholesky, solve_triangular
from scipy.special import erfc, erfcx

SQRT5 = np.sqrt(5.0)


def matern52(a, b, sigma2: float, rho: float) -> np.ndarray:
    """k(a_i, b_j) for a (n, d), b (m, d)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    d2 = np.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    z = SQRT5 * np.sqrt(d2) / rho
    return sigma2 * (1.0 + z + z * z / 3.0) * np.exp(-z)


class Posterior:
    """Exact posterior of one study: factor once, query many times."""

    def __init__(self, x, y, sigma2: float, rho: float, noise2: float):
        self.x = np.asarray(x, np.float64)
        self.y = np.asarray(y, np.float64)
        self.sigma2, self.rho = float(sigma2), float(rho)
        n = len(self.y)
        k = matern52(self.x, self.x, self.sigma2, self.rho)
        self.chol = cholesky(k + noise2 * np.eye(n), lower=True)
        self.ymean = float(np.mean(self.y))
        self.alpha = cho_solve((self.chol, True), self.y - self.ymean)
        self.f_best = float(np.max(self.y))

    def __call__(self, xq) -> tuple[np.ndarray, np.ndarray]:
        ks = matern52(self.x, xq, self.sigma2, self.rho)
        mean = ks.T @ self.alpha + self.ymean
        v = solve_triangular(self.chol, ks, lower=True)
        var = np.maximum(self.sigma2 - np.sum(v * v, axis=0), 1e-12)
        return mean, var

    def log_ei(self, xq, xi: float) -> np.ndarray:
        mean, var = self(xq)
        return log_expected_improvement(mean, var, self.f_best, xi)


def expected_improvement(mean, var, f_best: float, xi: float) -> np.ndarray:
    """EI = gamma Phi(z) + sigma phi(z), gamma = mean - f_best - xi."""
    sigma = np.sqrt(var)
    gamma = mean - f_best - xi
    z = gamma / sigma
    cdf = 0.5 * erfc(-z / np.sqrt(2.0))
    pdf = np.exp(-0.5 * z * z) / np.sqrt(2.0 * np.pi)
    return np.maximum(gamma * cdf + sigma * pdf, 0.0)


def log_expected_improvement(mean, var, f_best: float, xi: float
                             ) -> np.ndarray:
    """log EI, finite where EI itself underflows (Ament et al., NeurIPS
    2023, "Unexpected Improvements to Expected Improvement", Eq. 9):
    EI = sigma h(z), h(z) = phi(z) + z Phi(z), and for z < -1
    log h(z) = -z^2/2 - log(2 pi)/2 + log(1 - |z| sqrt(pi/2) erfcx(|z|/sqrt2)).
    """
    sigma = np.sqrt(var)
    z = np.asarray((mean - f_best - xi) / sigma, np.float64)
    out = np.empty_like(z)
    up = z > -1.0
    zu = z[up]
    out[up] = np.log(np.exp(-0.5 * zu * zu) / np.sqrt(2.0 * np.pi)
                     + zu * 0.5 * erfc(-zu / np.sqrt(2.0)))
    zl = -z[~up]
    inner = np.log(zl * erfcx(zl / np.sqrt(2.0))) + 0.5 * np.log(np.pi / 2)
    out[~up] = (-0.5 * zl * zl - 0.5 * np.log(2.0 * np.pi)
                + np.log(-np.expm1(inner)))
    return out + np.log(sigma)
