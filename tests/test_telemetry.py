"""Spans and per-tick phase counters of the served path (gateway -> pool ->
engine): every `stats` record carries its own tick's phase times, the spans
land on the profiler's host plane nested in their tick, and their number
per tick does not grow with the width."""
import asyncio
import time
import warnings

import jax
import numpy as np
import pytest

from repro.core.acquisition import AcqConfig
from repro.hpo import GatewayConfig, SchedulerConfig, StudyGateway
from repro.hpo import pool as pool_mod
from repro.hpo import telemetry
from repro.hpo.space import RESNET_SPACE

NEW_KEYS = ("queue_wait_ms", "stage_ms", "finish_ms", "wait_ms", "keys_ms")


def _gateway(d, slots, **gw_kw):
    cfg = SchedulerConfig(n_max=32, seed=0, ckpt_dir=str(d),
                          acq=AcqConfig(restarts=8, ascent_steps=4),
                          ckpt_every=10_000)
    gw = StudyGateway(RESNET_SPACE, cfg, GatewayConfig(slots=slots, **gw_kw))
    return gw, [gw.create_study() for _ in range(slots)]


def _value(unit):
    return float(-np.sum((np.asarray(unit) - 0.3) ** 2))


def _round(gw, sids):
    """One sync tick serving an ask from every study, then tell them back
    (absorbed by the next tick)."""
    for s in sids:
        gw.ask_nowait(s)
    gw.tick()
    for s in sids:
        tr = gw.pool.studies[gw.study_info(s)["slot"]].trials[-1]
        gw.tell(s, tr, _value(tr.unit))


@pytest.fixture(scope="module")
def gw4(tmp_path_factory):
    gw, sids = _gateway(tmp_path_factory.mktemp("gw4"), 4)
    for _ in range(4):                # past the compiles
        _round(gw, sids)
    return gw, sids


def test_every_record_has_the_phase_counters(gw4):
    gw, sids = gw4
    _round(gw, sids)
    assert len(gw.stats) >= 5
    for rec in gw.stats:
        for k in NEW_KEYS:
            assert rec[k] >= 0.0, (k, rec)
        assert rec["wait_ms"] <= rec["finish_ms"]
        assert rec["keys_ms"] <= rec["stage_ms"]
    served = [r for r in gw.stats if r["width"] and r["tick"] > 1]
    assert served and all(r["keys_ms"] > 0.0 for r in served)


def test_queue_wait_counts_from_enqueue(gw4):
    gw, sids = gw4
    gw.ask_nowait(sids[0])
    time.sleep(0.05)
    gw.tick()
    assert gw.stats[-1]["width"] == 1
    assert gw.stats[-1]["queue_wait_ms"] >= 50.0
    # an entry queued without an enqueue time counts as taken at once
    gw._studies[sids[1]].pending_asks += 1
    gw._asks.append((sids[1], None, 1))
    gw.tick()
    assert gw.stats[-1]["queue_wait_ms"] == 0.0
    for s in sids[:2]:
        tr = gw.pool.studies[gw.study_info(s)["slot"]].trials[-1]
        gw.tell(s, tr, _value(tr.unit))
    gw.tick()                          # a tell-only tick: width 0
    assert gw.stats[-1]["width"] == 0 and gw.stats[-1]["absorbed"] == 2
    assert gw.stats[-1]["queue_wait_ms"] == 0.0


def _host_spans(path):
    """{name: [(start_ns, end_ns, stats)]} of the spans on /host:CPU."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return _read_host_spans(path)


def _read_host_spans(path):
    pd = jax.profiler.ProfileData.from_file(str(path))
    out: dict = {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.end_ns, dict(e.stats)))
    return out


def test_spans_land_on_the_host_plane_inside_their_tick(gw4, tmp_path):
    gw, sids = gw4
    _round(gw, sids)                  # tells queued for every traced tick
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            _round(gw, sids)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(next(tmp_path.rglob("*.xplane.pb")))
    ticks = {r["tick"] for r in list(gw.stats)[-3:]}
    for tick_span, inner in (("gateway.tick_stage",
                              ("pool.split_keys", "engine.advance",
                               "gateway.place", "pool.round_begin")),
                             ("gateway.tick_finish",
                              ("pool.materialize", "pool.mint"))):
        outer = spans[tick_span]
        assert {st["tick"] for _a, _b, st in outer} == ticks
        for name in inner:
            assert len(spans[name]) == 3, name
            for a, b, _st in spans[name]:
                assert any(oa <= a and b <= ob for oa, ob, _ in outer), name


class _Counting:
    """Stands in for TraceAnnotation: counts the spans opened, by name."""

    def __init__(self, counts):
        self.counts = counts

    def __call__(self, name, **meta):
        self.counts[name] = self.counts.get(name, 0) + 1
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_span_count_per_tick_does_not_grow_with_width(gw4, tmp_path,
                                                      monkeypatch):
    gw8, sids8 = _gateway(tmp_path, 8)
    for _ in range(4):
        _round(gw8, sids8)
    per_width = []
    for gw, sids in (gw4, (gw8, sids8)):
        counts: dict = {}
        monkeypatch.setattr(telemetry, "TraceAnnotation", _Counting(counts))
        for _ in range(3):
            _round(gw, sids)
        monkeypatch.undo()
        per_width.append(counts)
    assert per_width[0] == per_width[1]
    assert per_width[0]["gateway.tick_stage"] == 3
    assert per_width[0]["pool.split_keys"] == 3


def test_pipelined_records_hold_their_own_stage_and_finish(tmp_path):
    """Two cohorts of asks (max_batch 2 over 4 studies) keep one tick in
    flight while the next is staged.  A stage and a finish are slowed on
    purpose; each slowdown must show in the record of the tick it
    belongs to, and not in its neighbour's, whose finish the slowed stage
    overlaps."""
    delay_s = 0.1
    gw, sids = _gateway(tmp_path, 4, max_batch=2)
    slow = {"stage": 9, "finish": 12}            # which call to slow
    calls = {"stage": 0, "finish": 0}
    slowed: dict = {}      # "stage"/"finish" -> the slowed round, and
    # "overlapped": whether a tick was in flight when the stage was slowed
    ticks: dict = {}       # "stage"/"finish" -> the slowed round's tick
    finishing: list = []
    begin = gw.pool.advance_round_begin

    def slow_begin(*a, **k):
        r = begin(*a, **k)
        calls["stage"] += 1
        if calls["stage"] == slow["stage"]:
            time.sleep(delay_s)
            slowed["stage"] = r
            slowed["overlapped"] = gw._pending is not None
        return r
    gw.pool.advance_round_begin = slow_begin

    materialize = pool_mod._materialize

    def slow_materialize(x):
        out = materialize(x)
        calls["finish"] += 1
        if calls["finish"] == slow["finish"]:
            time.sleep(delay_s)
            slowed["finish"] = finishing[-1].round
        return out

    finish = gw._tick_finish

    def tracked_finish(p):
        finishing.append(p)
        size = finish(p)
        for half in ("stage", "finish"):
            if slowed.get(half) is p.round:
                ticks[half] = gw.stats[-1]["tick"]
        return size
    gw._tick_finish = tracked_finish

    async def client(sid, n):
        for _ in range(n):
            tr = await gw.ask(sid)
            gw.tell(sid, tr, _value(tr.unit))
        await gw.drain()

    async def main():
        await asyncio.gather(*(client(s, 10) for s in sids))
        await gw.aclose()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pool_mod, "_materialize", slow_materialize)
        asyncio.run(main())
    assert slowed["overlapped"]
    by_tick = {r["tick"]: r for r in gw.stats}
    ks, kf = ticks["stage"], ticks["finish"]
    assert ks != kf
    assert by_tick[ks]["stage_ms"] >= 1e3 * delay_s
    assert by_tick[kf]["finish_ms"] >= 1e3 * delay_s
    assert by_tick[kf]["wait_ms"] >= 1e3 * delay_s
    for k in (ks - 1, ks + 1):
        assert by_tick[k]["stage_ms"] < 1e3 * delay_s
    for k in (kf - 1, kf + 1):
        assert by_tick[k]["finish_ms"] < 1e3 * delay_s
