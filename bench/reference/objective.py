"""The Levy function (Laguna & Martí 2005; the paper's Eq. 19), float64.

The studies maximise -Levy on [-10, 10]^d.  A unit-cube point `u` maps to
x = -10 + 20 u; a tenant's own optimum is moved by a per-tenant shift,
and the values are divided by a scale so that they span about a unit.
"""
from __future__ import annotations

import numpy as np


def levy(x) -> np.ndarray:
    """Levy function of x (..., d); its minimum 0 lies at x = 1."""
    x = np.asarray(x, np.float64)
    w = 1.0 + (x - 1.0) / 4.0
    term1 = np.sin(np.pi * w[..., 0]) ** 2
    wi = w[..., :-1]
    term2 = np.sum((wi - 1.0) ** 2
                   * (1.0 + 10.0 * np.sin(np.pi * wi + 1.0) ** 2), axis=-1)
    wd = w[..., -1]
    term3 = (wd - 1.0) ** 2 * (1.0 + np.sin(2.0 * np.pi * wd) ** 2)
    return term1 + term2 + term3


def neg_levy_unit(u, shift, lo: float = -10.0, hi: float = 10.0,
                  scale: float = 1.0) -> float:
    """-Levy / scale at the box point of unit point `u`, moved by `shift`."""
    x = lo + (hi - lo) * np.asarray(u, np.float64) - shift
    return float(-levy(x) / scale)
