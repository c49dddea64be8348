"""The traffic generator: one seed gives the same inputs, another seed
different ones, and the Zipf pick follows its ranks."""
import asyncio

import numpy as np

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
          "study_budget": 20, "warmup_s": 0.0}
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
