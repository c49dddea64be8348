"""The traffic generator: one seed gives the same inputs, another seed
different ones, the Zipf pick follows its ranks, a study closes by the
observations it holds, and the loader refuses a traffic whose studies
could escalate."""
import asyncio
import json

import numpy as np
import pytest

import run
from conftest import CELL
from generator import Traffic


class _Engine:
    class gp_cfg:
        dim = 5


class _Pool:
    engine = _Engine()


class _GW:
    """Records what the generator tells; serves nothing."""

    class gw:
        slots = 4

    def __init__(self):
        self.pool = _Pool()
        self.next = 0
        self.told = []
        self.ticks = 0

    def create_study(self, name=None):
        self.next += 1
        return self.next - 1

    def tell(self, sid, trial, y):
        self.told.append((sid, np.asarray(trial.unit).tolist(), y))

    def tick(self):
        self.ticks += 1


PARAMS = {"tenants": 8, "history_tenants": 4, "history": [3, 9],
          "workers": 4,
          "think": {"dist": "lognormal", "median_s": 0.5, "sigma": 1.0},
          "session": {"dist": "geometric", "mean": 8},
          "pick": {"dist": "zipf", "s": 1.1, "max_workers_per_tenant": 2},
          "hot_shift": {"every_s": 5.0, "share": 0.5},
          "study_obs": 20, "warmup_s": 0.0}
OBJ = {"name": "neg_levy", "lo": -10.0, "hi": 10.0, "tenant_shift": 2.0,
       "scale": 50.0}


def _inputs(seed):
    gw = _GW()
    tr = Traffic(gw, PARAMS, OBJ, seed)
    tr.fill()
    rng = np.random.default_rng([seed, 2, 0])
    draws = [(tr._pick(0, rng).idx, tr._session(rng), tr._think(rng))
             for _ in range(50)]
    shifts = [t.shift.tolist() for t in tr.tenants]
    return gw.told, draws, shifts, gw.ticks


def test_same_seed_same_inputs():
    big = 2 ** 31 + 12345
    assert _inputs(big) == _inputs(big)


def test_other_seed_other_inputs():
    a, b = _inputs(7), _inputs(8)
    assert a[0] != b[0] and a[1] != b[1] and a[2] != b[2]


def test_fill_tells_each_history_tenant_in_one_tick():
    told, _, _, ticks = _inputs(3)
    assert ticks == 1
    assert {sid for sid, _, _ in told} <= set(range(4))
    per = np.bincount([sid for sid, _, _ in told], minlength=4)
    assert all(3 <= c <= 9 for c in per)


def test_every_seed_tells_the_same_history_lengths():
    """The seed orders the history lengths among the tenants; the lengths
    themselves, spread evenly over [lo, hi], are the same for every seed."""
    def lengths(seed):
        gw = _GW()
        Traffic(gw, PARAMS, OBJ, seed).fill()
        return np.bincount([sid for sid, _, _ in gw.told], minlength=4)

    a, b = lengths(7), lengths(2 ** 31 + 3)
    assert sorted(a) == sorted(b) == [3, 5, 7, 9]
    assert any(not np.array_equal(lengths(s), a) for s in range(8, 12))


def test_zipf_pick_favours_low_ranks():
    tr = Traffic(_GW(), dict(PARAMS, tenants=64), OBJ, 11)
    rng = np.random.default_rng(0)
    picks = np.bincount([tr._pick(0, rng).idx for _ in range(4000)],
                        minlength=64)
    assert picks[0] > picks[1] > picks[8] > picks[40]


def test_zipf_pick_skips_tenants_at_their_cap():
    tr = Traffic(_GW(), PARAMS, OBJ, 11)
    tr.tenants[0].workers = 2
    rng = np.random.default_rng(0)
    assert all(tr._pick(0, rng).idx != 0 for _ in range(200))


def test_hot_shift_permutes_ranks_only():
    tr = Traffic(_GW(), dict(PARAMS, hot_shift={"every_s": 0.0,
                                                "share": 0.5}), OBJ, 5)
    before = tr._rank_tenant.copy()

    async def one_move():
        task = asyncio.ensure_future(tr._hot_shift())
        await asyncio.sleep(0.01)
        tr.stopping = True
        await task

    asyncio.run(one_move())
    assert sorted(tr._rank_tenant) == sorted(before)
    assert not np.array_equal(tr._rank_tenant, before)


def test_history_counts_toward_study_obs():
    """A history tenant's first study starts full by its history; a
    tenant without one starts empty."""
    tr = Traffic(_GW(), PARAMS, OBJ, 9)
    tr.fill()
    held = [t.held for t in tr.tenants]
    assert all(3 <= h <= 9 for h in held[:4]) and held[4:] == [0] * 4


def test_study_closes_at_study_obs():
    """A tenant with history h is handed its study study_obs - h times,
    then the study closes and the next ask goes to a new study."""
    gw = _GW()
    gw.study_info = lambda sid: {"inflight": 0, "n_obs": len(tr.hist[sid])}
    gw.close_study = lambda sid: None
    gw._tick_count = 0
    tr = Traffic(gw, dict(PARAMS, pick={"dist": "own"}), OBJ, 9)
    tr.fill()
    t = tr.tenants[0]
    first, h = t.sid, t.held

    async def asks(n):
        return [await tr._study_for(t) for _ in range(n)]

    sids = asyncio.run(asks(PARAMS["study_obs"] - h + 1))
    assert sids[:-1] == [first] * (PARAMS["study_obs"] - h)
    assert sids[-1] != first and t.held == 1
    assert [(tick, idx) for _, tick, idx in tr.closes] == [(0, 0)]


_OWN = {"dist": "own"}
_ZIPF = {"dist": "zipf", "s": 1.1, "max_workers_per_tenant": 4}


def _sets(pick, study_obs):
    return ["config.scheduler.n_max=32", "traffic.history=[4,12]",
            f"traffic.pick={json.dumps(pick)}",
            f"traffic.study_obs={study_obs}"]


@pytest.mark.parametrize("pick,w", [(_OWN, 1), (_ZIPF, 4)])
@pytest.mark.parametrize("study_obs", ["history", "n_max"])
def test_loader_refuses_traffic_that_can_escalate(pick, w, study_obs):
    """study_obs at the longest history leaves a tenant no ask; one above
    n_max - w lets a study's rows reach n_max."""
    obs = 12 if study_obs == "history" else 32 - w + 1
    with pytest.raises(SystemExit, match="study_obs"):
        run.load_cell(CELL, _sets(pick, obs))


@pytest.mark.parametrize("pick,w", [(_OWN, 1), (_ZIPF, 4)])
def test_loader_takes_traffic_at_its_bounds(pick, w):
    for obs in (13, 32 - w):
        run.load_cell(CELL, _sets(pick, obs))


def test_loader_takes_the_cells_traffic():
    _, _, cfg, traffic = run.load_cell(CELL)
    assert traffic["history"][1] < traffic["study_obs"] \
        <= cfg["scheduler"]["n_max"] - 1
