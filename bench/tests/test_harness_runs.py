"""Whole runs of the harness on the CPU at tiny sizes: a sound run is
correct, studies close and reopen without escalating, each planted fault
of the timed path makes `correct` false, and the harness refuses to
measure without a chip."""
import json

import pytest

import generator
import run
from conftest import tiny_argv


def _result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["sequential", "churn", "resident"])
def test_sound_run_is_correct(cell, capsys):
    assert run.main(tiny_argv(cell, 4_000_000_007), require_chip=False) == 0
    res = _result(capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


def test_resident_studies_close_and_reopen(capsys, monkeypatch):
    """Every tenant of the tiny resident run fills a study to its
    `study_obs` (history included) and opens a new one; the closes fall
    in different ticks, no study escalates, and the run is correct."""
    made = []

    class Kept(generator.Traffic):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(generator, "Traffic", Kept)
    argv = tiny_argv("resident", 4_000_000_013, seconds=3.0)
    assert run.main(argv, require_chip=False) == 0
    out = capsys.readouterr().out.strip().splitlines()
    res = json.loads(out[-1])
    assert res["correct"], res["checks"]
    assert res["checks"]["escalated"][0] == 0
    closes = made[0].closes
    assert {idx for _, _, idx in closes} == set(range(8))
    assert len({tick for _, tick, _ in closes}) > 1
    assert any(line.startswith("studies closed inside the window: ")
               for line in out)


@pytest.mark.parametrize("fault,number", [
    ("answer_altered", "ei_rank"),
    ("absorb_dropped", "state_n_mismatch"),
])
@pytest.mark.parametrize("cell", ["churn", "resident"])
def test_planted_fault_fails(cell, fault, number, capsys):
    argv = tiny_argv(cell, 4_000_000_011, extra=["--fault", fault])
    assert run.main(argv, require_chip=False) == 0
    res = _result(capsys)
    assert not res["correct"]
    value, limit = res["checks"][number]
    assert value > limit


def test_refuses_without_a_chip(capsys):
    code = run.main(tiny_argv("resident", 5), require_chip=True)
    assert code != 0
    assert capsys.readouterr().out.strip() == ""


def test_refuses_outside_a_checkout(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program
    to measure: the run exits non-zero and prints no result."""
    import pathlib
    import shutil
    import subprocess
    import sys
    root = pathlib.Path(run.__file__).resolve().parents[1]
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "svc-resident-sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
