"""``correct`` has to come out false when it should. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct.py -q

Everything of a run after the look for a chip (``run.measure``) is driven at a
size a test can hold (65,536 rows, 15 leaves, a one-second window) under the
LIMITS OF THE CELL, unchanged:

* a sound run passes;
* the CONTROL (the reference with bfloat16 gradients and hessians, put in the
  program's place) fails at least one number, on three seeds;
* each fault a one-chip training cell can have, planted under the timed path
  through the kind's one seam ``make_system``, fails: a step that leaves the
  state unchanged, half of the rows left out of every sum, a leaf value
  altered where the model is written.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent)]
import plants
import reference
import run

CELL = "criteo_share.train"
CUT = {"data": {"rows": 65536}, "params": {"num_leaves": 15}}
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}


def drive(seed, plant=None, lower=False):
    bench, cell, config, traffic = run.load_cell(CELL)
    kind = run.load_module(run.HERE / "kinds" / f"{traffic['kind']}.py")
    if plant is not None:
        kind.make_system = plant(kind)
    ns = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0, trace=0)
    line, res = run.measure(ns, bench, cell, config, traffic, DEVICE,
                            on_chip=False, overrides=CUT, lower=lower, kind=kind)
    over = [k for k, c in line["checks"].items() if not c["value"] <= c["limit"]]
    return line, res, over


@pytest.mark.parametrize("seed", [11, 2147483659, 4000000007])
def test_sound_run_passes_and_the_control_fails(seed):
    line, res, over = drive(seed, lower=True)
    assert line["correct"], over
    assert line["attempted"] >= 1 and line["failed"] == 0
    json.dumps(line)                            # the result object is plain JSON
    assert list(line)[-1] == "checks"           # compared numbers come last
    limits = res["limits"]
    control = res["facts"]["reference"]["control"]
    # the control stands in the program's place: same names, same limits
    ok, rows = reference.decide({**res["numbers"], **control}, limits)
    assert not ok, rows
    assert [k for k, v, lim in rows if v > lim], "the control failed no number"


@pytest.mark.parametrize("plant", list(plants.ALL.values()), ids=list(plants.ALL))
def test_a_broken_timed_path_is_not_correct(plant):
    line, _res, over = drive(23, plant=plant)
    assert line["correct"] is False
    assert over, "no number went over its limit"
    print(plant.__name__, "failed:", over)
