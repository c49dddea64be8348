"""The precision control fails the check at a size a test run can hold.

The control is the plain reference put in the served path's place and
computed in float32 with fewer bfloat16 passes per matmul than the
configuration states (`bench/reference/control.py` spells the passes
out).  The nearest precision below the stated HIGHEST, three passes
(HIGH), must fail `mean_err` or `var_err` against each cell's limits
on every seed; PERF.md gives the readings at the cells'
own sizes.
"""
import json
import pathlib

import numpy as np
import pytest

import check
from reference.objective import neg_levy_unit

BENCH = pathlib.Path(check.__file__).resolve().parent
CELLS = {  # cell -> (traffic file, configuration file, history lengths)
    "svc-resident-sat": ("resident-sat", "svc-lenet5-n256", (128, 200)),
}


def _sample(seed: int, n: int) -> check.Sample:
    rng = np.random.default_rng([seed, 123])
    shift = rng.uniform(-2.0, 2.0, 5)
    hist = [(u, neg_levy_unit(u, shift, scale=50.0))
            for u in rng.uniform(0.0, 1.0, (n, 5))]
    return check.Sample(seed, {}, hist, False)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_high_precision_control_fails(cell, seed):
    traffic, config, sizes = CELLS[cell]
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    lim = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())[
        "check"]["limits"]
    got = check.posterior_numbers([_sample(seed, n) for n in sizes], cfg,
                                  seed, 5, 16, control="high")
    assert any(got[k] > lim[k] for k in ("mean_err", "var_err")), got
