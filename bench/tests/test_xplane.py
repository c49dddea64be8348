"""The trace reduction on a small trace recorded on a TPU v5e.

`data/levy_small.xplane.pb.xz` is a `--trace 1` run of one client's
sequential Levy-5D study on one slot (about 2.2 s traced, 328 served asks
at n_max 1024).  The numbers below
were read from it once; the invariants hold for any trace.
"""
import lzma
import pathlib

import pytest

import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "levy_small.xplane.pb"
    path.write_bytes(lzma.decompress(
        (DATA / "levy_small.xplane.pb.xz").read_bytes()))
    return xplane.load(path)


def test_recorded_numbers(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(2.221025624, rel=1e-9)
    assert trace.busy_s() == pytest.approx(0.121447432, rel=1e-9)
    assert trace.op_count("fused_ei_grad_pallas") == 6867
    assert trace.op_seconds("fused_ei_grad_pallas") == pytest.approx(
        0.080531282, rel=1e-9)
    assert trace.op_count(r"^cholesky_pallas") == 2
    assert trace.top_ops(1)[0][0] == "fused_ei_grad_pallas.7"


def test_invariants(trace):
    busy = trace.busy_s()
    assert 0 < busy <= trace.window_s
    self_total = sum(o.self_ns for o in trace.devices["/device:TPU:0"]) * 1e-9
    assert self_total == pytest.approx(busy, rel=1e-6)
    top = trace.top_ops(10)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = trace.idle_gaps(10)
    assert sum(s for _, s in gaps) <= trace.window_s - busy + 1e-9
    assert all(name.startswith(("bench.", "host outside"))
               for name, _ in gaps)


def test_ei_calls_match_the_ascent(trace):
    """Each advance runs 20 ascent steps and one final evaluation of the
    EI kernel: its call count is 21 per advance in the window."""
    advances = [s for s in trace.spans if s.name == "bench.engine.advance"
                and trace.window[0] <= s.start < trace.window[1]]
    calls = trace.op_count("fused_ei_grad_pallas")
    assert 20 * len(advances) <= calls <= 21 * (len(advances) + 1)
