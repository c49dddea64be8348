"""The readers of the gateway's per-tick phase counters, on ticks worked out
by hand, and on the records of a program that does not count them."""
import types

import pytest

import run

NEW = ["ask_queue_ms", "tick_host_ms", "key_split_ms", "device_wait_ms"]

TICKS = [
    {"width": 4, "queue_wait_ms": 10.0, "stage_ms": 100.0,
     "finish_ms": 50.0, "wait_ms": 20.0, "keys_ms": 30.0},
    {"width": 0, "queue_wait_ms": 0.0, "stage_ms": 10.0,
     "finish_ms": 5.0, "wait_ms": 1.0, "keys_ms": 0.0},
    {"width": 2, "queue_wait_ms": 40.0, "stage_ms": 200.0,
     "finish_ms": 70.0, "wait_ms": 60.0, "keys_ms": 60.0},
]

# the records of a gateway without the phase counters
OLD_TICKS = [{"tick": i, "width": 4, "suggestions": 4, "absorbed": 4,
              "deferred": 0, "queued_after": 0, "latency_ms": 700.0,
              "evictions": 0, "restores": 0} for i in (1, 2, 3)]


def _ctx(ticks):
    return types.SimpleNamespace(ticks=ticks, slots=4, trace=None, work={},
                                 peaks={})


@pytest.mark.parametrize("name, want", [
    ("ask_queue_ms", (10.0 * 4 + 40.0 * 2) / 6),
    ("tick_host_ms", ((100 + 50 - 20) + (10 + 5 - 1) + (200 + 70 - 60)) / 3),
    ("key_split_ms", (30.0 + 60.0) / 2),
    ("device_wait_ms", (20.0 + 1.0 + 60.0) / 3),
])
def test_reader_on_hand_worked_ticks(name, want):
    assert run._metric_reader(name)(_ctx(TICKS)) == pytest.approx(want)


@pytest.mark.parametrize("ticks", [OLD_TICKS, []], ids=["parent", "none"])
@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_without_its_key(name, ticks):
    assert run._metric_reader(name)(_ctx(ticks)) is None


@pytest.mark.parametrize("name", ["ask_queue_ms", "key_split_ms"])
def test_reader_gives_none_when_no_tick_served(name):
    idle = [dict(t, width=0) for t in TICKS]
    assert run._metric_reader(name)(_ctx(idle)) is None
