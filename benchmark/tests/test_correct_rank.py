"""``correct`` has to come out false when it should, in a RANKING cell. Run by
hand, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct_rank.py -q

``test_correct.py``'s cases for the kind ``rank_loop`` (``run.measure`` at
65,536 rows, 15 leaves, a one-second window, the width cut to 24 columns so
that the reference's one-hot histogram fits a test) under the LIMITS OF THE
CELL, unchanged: a sound run passes; the control (bfloat16 gradients and
hessians) and both emulated faults fail at least one number; each fault of
``plants_rank.py`` planted under the timed path fails.
"""
import argparse
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent)]
import plants_rank
import reference
import run

CELL = "yahoo_ltr.train_rank"
CUT = {"data": {"rows": 65536, "features": 24, "uniform_columns": 24},
       "params": {"num_leaves": 15}}
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


@pytest.mark.parametrize("seed", [11, 4000000007])
def test_sound_ranking_run_passes_and_the_control_and_faults_fail(seed):
    line, res, over = drive(seed, lower=True)
    assert line["correct"], over
    assert line["attempted"] >= 1 and line["failed"] == 0
    json.dumps(line)
    ref = res["facts"]["reference"]
    assert ref["pairs_real"] > 0
    for stand_in in ("control", "half", "tail"):
        numbers = {**res["numbers"], **ref[stand_in]}
        if stand_in == "half":          # the emulation's counts differ by construction
            numbers["leaf_count"] = res["numbers"]["leaf_count"]
        ok, rows = reference.decide(numbers, res["limits"])
        assert not ok, (stand_in, rows)


@pytest.mark.parametrize("plant", list(plants_rank.ALL.values()),
                         ids=list(plants_rank.ALL))
def test_a_broken_ranking_path_is_not_correct(plant):
    line, _res, over = drive(23, plant=plant)
    assert line["correct"] is False
    assert over, "no number went over its limit"
    print(plant.__name__, "failed:", over)
