"""What decides `correct`: the served path against the plain reference.

After the window closes, a sample of the served studies (drawn from the
seed, with the longest study in it, and in a churned cell the studies that
were evicted and restored) is read back from the device, and its GP state
is compared with the exact posterior over the history the benchmark told
it.  A sample of the window's suggestions is ranked by the reference EI,
under the history each one was conditioned on, among random points.
Exact counts cover the accounting.  Each number is compared with its limit (`limits`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from reference import control as control_mod
from reference.gp import Posterior, matern52

# Exact counts, limit 0.  The measured numbers (mean_err, var_err, ei_rank)
# take their limits from the cell's traffic file ("check" ->
# "limits"): each lies between the largest reading of sound runs and the
# least reading of the control or the planted fault; PERF.md gives them.
EXACT = ("asks_unanswered", "tells_unabsorbed", "state_n_mismatch",
         "fantasy_rows", "escalated", "config_mismatch")
MEASURED = ("mean_err", "var_err", "ei_rank")
_SAMPLE, _QUERY, _CAND = 9, 10, 11


def read_state(gw, sid: int) -> dict:
    """Host copy of one resident study's served GP state."""
    return read_slot(gw, gw.study_info(sid)["slot"])


def read_slot(gw, slot: int) -> dict:
    """Host copy of the GP state in one slot of the stacked state."""
    st = gw.pool.engine.study_state(slot)
    return {"x": np.asarray(st.x_buf), "y": np.asarray(st.y_buf),
            "li": np.asarray(st.li_buf), "alpha": np.asarray(st.alpha),
            "n": int(st.n), "sigma2": float(st.params.sigma2),
            "rho": float(st.params.rho)}


def served_posterior(state: dict, xq) -> tuple[np.ndarray, np.ndarray]:
    """The posterior that a served state encodes, read in float64:
    mean = k*^T alpha + mean(y), var = sigma2 - |L^-1 k*|^2."""
    n = state["n"]
    x = state["x"][:n].astype(np.float64)
    ks = matern52(x, xq, state["sigma2"], state["rho"])
    mean = ks.T @ state["alpha"][:n].astype(np.float64) + float(
        np.mean(state["y"][:n].astype(np.float64)))
    v = state["li"][:n, :n].astype(np.float64) @ ks
    var = np.maximum(state["sigma2"] - np.sum(v * v, axis=0), 1e-12)
    return mean, var


@dataclasses.dataclass
class Sample:
    sid: int
    state: dict
    hist: list
    restored: bool


def pick_studies(gw, traffic, k: int, seed: int) -> list[Sample]:
    """Up to k studies: the longest, up to a quarter restored after an
    eviction, up to a quarter past a re-anchor, the rest at random."""
    rng = np.random.default_rng([seed, _SAMPLE])
    cands = []
    for sid in gw.study_ids():
        info = gw.study_info(sid)
        if info["resident"] and len(traffic.hist[sid]) >= 2:
            cands.append((sid, info["evictions"] > 0))
    for sid in sorted(traffic.captured):
        cands.append((sid, False))
    if not cands:
        return []
    length = {sid: len(traffic.hist[sid]) for sid, _ in cands}
    order = list(rng.permutation(len(cands)))
    chosen: list[int] = [max(range(len(cands)),
                             key=lambda i: length[cands[i][0]])]
    restored = [i for i in order if cands[i][1]]
    long = [i for i in order if length[cands[i][0]] > 128]
    for pool in (restored[:k // 4], long[:k // 4], order):
        for i in pool:
            if len(chosen) < k and i not in chosen:
                chosen.append(i)
    out = []
    for i in chosen:
        sid, rest = cands[i]
        state = traffic.captured.get(sid) or read_state(gw, sid)
        out.append(Sample(sid, state, list(traffic.hist[sid]), rest))
    return out


def pick_suggestions(traffic, window, k: int, seed: int) -> list:
    """Up to k EI suggestions issued in the window, the one conditioned on
    the longest history among them."""
    rng = np.random.default_rng([seed, _SAMPLE, 1])
    t0, t1 = window
    asks = [a for a in traffic.asks
            if a.ok and a.n_cond >= 1 and t0 <= a.t_issue < t1]
    if not asks:
        return []
    first = max(range(len(asks)), key=lambda i: asks[i].n_cond)
    rest = [int(i) for i in rng.permutation(len(asks)) if i != first]
    return [asks[i] for i in [first] + rest[:k - 1]]


def _queries(sample: Sample, n_rand: int, rng, dim: int) -> np.ndarray:
    served = [u for u, _ in sample.hist[-8:]]
    return np.concatenate([np.asarray(served, np.float64).reshape(-1, dim),
                           rng.uniform(0.0, 1.0, (n_rand, dim))])


def posterior_numbers(samples, cfg: dict, seed: int, dim: int,
                      n_query: int, control: str | None = None) -> dict:
    """mean_err and var_err over the sampled studies: the served
    state (or, with `control`, the reference at that lower precision)
    against the float64 reference."""
    sch = cfg["scheduler"]
    sigma2, rho, noise2 = 1.0, float(sch["rho0"]), float(sch["noise2"])
    out = {"mean_err": 0.0, "var_err": 0.0}
    for s in samples:
        rng = np.random.default_rng([seed, _QUERY, s.sid])
        x = np.asarray([u for u, _ in s.hist], np.float64)
        y = np.asarray([v for _, v in s.hist], np.float64)
        xq = _queries(s, n_query, rng, dim)
        ref = Posterior(x, y, sigma2, rho, noise2)
        m_r, v_r = ref(xq)
        if control is None:
            m_p, v_p = served_posterior(s.state, xq)
        else:
            m_p, v_p = control_mod.posterior(x, y, xq, sigma2, rho, noise2,
                                             control)
        scale = float(np.std(y)) or 1.0
        for key, gap, norm in (("mean_err", m_p - m_r, scale),
                               ("var_err", v_p - v_r, sigma2)):
            out[key] = max(out[key], _worst(gap) / norm)
    return out


def _worst(gap) -> float:
    """Largest absolute gap; a NaN or infinite one reads as infinite."""
    gap = np.abs(np.asarray(gap, np.float64))
    return float(np.max(gap)) if np.all(np.isfinite(gap)) else float("inf")


def ei_rank(suggestions, traffic, cfg: dict, seed: int, dim: int,
            n_cand: int) -> float:
    """Median over the sampled suggestions of the share of `n_cand` random
    points whose reference EI exceeds the suggestion's, under the history
    the suggestion was conditioned on (compared in log EI, which stays
    finite where EI underflows).  The best of R ascended restarts ranks
    near 0; a point drawn at random ranks near 0.5."""
    sch = cfg["scheduler"]
    rho, noise2 = float(sch["rho0"]), float(sch["noise2"])
    xi = float(sch["acq"]["xi"])
    ranks = []
    for i, a in enumerate(suggestions):
        hist = traffic.hist[a.sid][:a.n_cond]
        x = np.asarray([u for u, _ in hist], np.float64)
        y = np.asarray([v for _, v in hist], np.float64)
        ref = Posterior(x, y, 1.0, rho, noise2)
        cand = np.random.default_rng([seed, _CAND, i]).uniform(
            0.0, 1.0, (n_cand, dim))
        got = ref.log_ei(np.asarray(a.unit, np.float64)[None, :], xi)[0]
        if not np.isfinite(got):
            got = -np.inf
        ranks.append(float(np.mean(ref.log_ei(cand, xi) > got)))
    return float(np.median(ranks)) if ranks else 0.0


def accounting(gw, traffic, samples, cfg: dict) -> dict:
    """Exact counts: every ask answered, every tell absorbed, the served
    state's n equal to the told history, no fantasy row, no escalated
    study, and the restarts and ascent steps as configured."""
    unanswered = sum(1 for a in traffic.asks if a.ok and a.unit is None)
    unabsorbed = 0
    for sid in gw.study_ids():
        unabsorbed += abs(len(traffic.hist[sid])
                          - gw.study_info(sid)["n_obs"])
    summ = gw.summary()
    acq = cfg["scheduler"]["acq"]
    mismatch = int(gw.cfg.acq.restarts != acq["restarts"]) + int(
        gw.cfg.acq.ascent_steps != acq["ascent_steps"])
    return {
        "asks_unanswered": unanswered,
        "tells_unabsorbed": unabsorbed + traffic.tell_failures,
        "state_n_mismatch": sum(abs(s.state["n"] - len(s.hist))
                                for s in samples),
        "fantasy_rows": int(summ["fantasy_active"]),
        "escalated": int(summ["escalated"]),
        "config_mismatch": mismatch,
    }


def limits(traffic_params: dict) -> dict:
    """Every compared number's limit: 0 for the exact counts, the cell's
    own for the measured ones."""
    own = traffic_params["check"]["limits"]
    return {**{k: 0 for k in EXACT}, **{k: own[k] for k in MEASURED}}
