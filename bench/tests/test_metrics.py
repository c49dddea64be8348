"""Metric arithmetic, the reference GP and the trace reduction's interval
logic, on numbers worked out by hand."""
import types

import numpy as np
import pytest

import run
import xplane
from reference.gp import (Posterior, expected_improvement,
                          log_expected_improvement, matern52)


def _ctx(**kw):
    base = dict(ticks=[], slots=1024, trace=None, work={},
                peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    base.update(kw)
    return types.SimpleNamespace(**base)


TICKS = [{"latency_ms": 10.0}, {"latency_ms": 30.0}, {"latency_ms": 20.0}]


def test_tick_counters():
    ctx = _ctx(ticks=TICKS)
    assert run._metric_reader("tick_ms")(ctx) == pytest.approx(20.0)


def test_slots_per_ask_counts_every_slot_of_each_serving_tick():
    ticks = [{"width": 1024}, {"width": 256}, {"width": 0}]
    got = run._metric_reader("slots_per_ask")(_ctx(ticks=ticks))
    assert got == pytest.approx(2 * 1024 / 1280)


@pytest.mark.parametrize("name", ["tick_ms", "slots_per_ask",
                                  "device_idle_pct", "ei_grad_roofline",
                                  "reanchor_roofline"])
def test_nothing_to_read_gives_none(name):
    assert run._metric_reader(name)(_ctx()) is None


def _trace(ops, window=(0.0, 1e9)):
    t = xplane.Trace(window, {"/device:TPU:0": ops}, [])
    xplane._self_times(ops)
    return t


def test_idle_share_and_nested_self_time():
    ops = [xplane.Op("while.1", 0.0, 4e8),
           xplane.Op("fused_ei_grad_pallas.8", 1e8, 2e8),
           xplane.Op("copy.3", 6e8, 7e8)]
    tr = _trace(ops)
    assert tr.busy_s() == pytest.approx(0.5)
    idle = run._metric_reader("device_idle_pct")(_ctx(trace=tr))
    assert idle == pytest.approx(50.0)
    assert tr.op_seconds(r"^while") == pytest.approx(0.3)
    assert tr.op_seconds("fused_ei_grad_pallas") == pytest.approx(0.1)
    assert tr.top_ops(1) == [["while.1", pytest.approx(0.3)]]
    gaps = tr.idle_gaps(2)
    assert [g[1] for g in gaps] == [pytest.approx(0.3), pytest.approx(0.2)]


def test_instr_name():
    assert xplane.instr_name(
        "%fused_ei_grad_pallas.8 = (f32[1024,128,128]) custom-call(...)") \
        == "fused_ei_grad_pallas.8"


def test_ei_roofline_arithmetic():
    mod = run._metric_reader("ei_grad_roofline").__globals__
    n, r, d = 100, 48, 5
    f = mod["flops"](n, r, d)
    assert f == 2 * r * n * n + r * n * (5 * d + 28) + 30 * r
    b = mod["bytes_moved"](n, r, d)
    assert b == 4 * (n * n + n * d + 2 * n + 2 * r * d + r)
    secs = 1e-3
    tr = _trace([xplane.Op("fused_ei_grad_pallas.8", 0.0, secs * 1e9)])
    ctx = _ctx(trace=tr, work={"ei_calls": [(21, n, r, d)] * 10})
    want = 100 * max(210 * f / 197e12, 210 * b / 819e9) / secs
    assert run._metric_reader("ei_grad_roofline")(ctx) == pytest.approx(want)


def test_reanchor_roofline_arithmetic():
    tr = _trace([xplane.Op("cholesky_pallas.1", 0.0, 1e6),
                 xplane.Op("_trsv_pallas_raw.1", 1e6, 3e6)])
    ctx = _ctx(trace=tr, work={"reanchor_n": [1024, 512]})
    f = 2 * (1024 ** 3 + 512 ** 3) / 3
    b = 4 * 4 * (1024 ** 2 + 512 ** 2)
    want = 100 * max(f / 197e12, b / 819e9) / 3e-3
    assert run._metric_reader("reanchor_roofline")(ctx) == pytest.approx(want)


def test_quantile_nearest_rank_counts_failures():
    lat = [1.0, 2.0, 3.0, 4.0, float("inf")]
    assert run.quantile(lat, 0.5) == 3.0
    assert run.quantile(lat, 0.95) == float("inf")
    assert run.quantile(list(range(1, 101)), 0.95) == 95


def test_reference_posterior_matches_textbook():
    rng = np.random.default_rng(0)
    x = rng.uniform(size=(20, 3))
    y = np.sin(x.sum(axis=1))
    xq = rng.uniform(size=(7, 3))
    post = Posterior(x, y, 1.3, 0.4, 1e-4)
    mean, var = post(xq)
    k = matern52(x, x, 1.3, 0.4) + 1e-4 * np.eye(20)
    ks = matern52(x, xq, 1.3, 0.4)
    kinv = np.linalg.inv(k)
    assert np.allclose(mean, ks.T @ kinv @ (y - y.mean()) + y.mean())
    assert np.allclose(var, 1.3 - np.einsum("ij,ik,kj->j", ks, kinv, ks))


def test_log_ei_matches_ei_and_stays_finite():
    mean = np.array([0.3, -0.2, -1.0, -5.0, -60.0])
    var = np.array([0.04, 0.5, 0.2, 1.0, 1.0])
    ei = expected_improvement(mean, var, 0.1, 0.01)
    lei = log_expected_improvement(mean, var, 0.1, 0.01)
    assert np.allclose(np.exp(lei[:4]), ei[:4], rtol=1e-9)
    assert ei[4] == 0.0 and np.isfinite(lei[4]) and lei[4] < -1000
